"""The engine's admission prefill passes no pad mask (PR 34).

One prompt is padded on the RIGHT to its bucket and attended causally
from cache position 0, so a real token never sees a pad: the padded
admission must give the last logits and the K/V of the slot's first S0
positions that the unpadded prefill of the same prompt gives, on the
dense path and on the flash kernel (interpret mode), for the Llama trunk,
for a latent-cache model and for multimodal ``inputs_embeds``; and
``serving_prefill_attention_total`` must count each admission under the
implementation the kernel gate recorded when the program was traced."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import generation
from paddle_tpu.generation import _PrefillStep
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import catalog as cat
from paddle_tpu.ops.pallas import backend
from paddle_tpu.serving import ContinuousBatchEngine, _Request

MAX_LEN = 64
# several S0 a bucket (buckets 8, 16, 32), none filling it
PADDED = [5, 7, 9, 13, 15, 17, 24, 31]


def _llama():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))


def _mla():
    from paddle_tpu.models.deepseek import (DeepseekV2Config,
                                            DeepseekV2ForCausalLM)

    paddle.seed(3)
    return DeepseekV2ForCausalLM(
        DeepseekV2Config.tiny_mla(num_hidden_layers=2))


FAMILIES = {"llama": (_llama, ("k", "v")), "mla": (_mla, ("c_kv", "k_pe"))}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    build, keys = FAMILIES[request.param]
    model = build()
    return model, keys, ContinuousBatchEngine(
        model, max_batch=2, max_len=MAX_LEN, page_size=8)


IMPLS = ("flash", "append", "xla")


def _attention_counts():
    return {impl: cat.SERVING_PREFILL_ATTENTION.labels(
        engine="decoder", impl=impl).value for impl in IMPLS}


def _attention_delta(before):
    return {impl: v - before[impl]
            for impl, v in _attention_counts().items()}


def _prompt(model, n, seed=0):
    return np.random.RandomState(seed + n).randint(
        1, model.config.vocab_size, (n,))


def _unpadded(model, ids, rope_len, embeds=None):
    """The prefill of exactly the prompt: no bucket, no pad."""
    step = _PrefillStep(model, len(ids), False, rope_len=rope_len,
                        embeds_input=embeds is not None)
    first = embeds if embeds is not None else jnp.asarray(ids[None],
                                                          jnp.int32)
    return step(first, jnp.asarray([len(ids)], jnp.int32))


def _assert_same_prefill(padded, exact, keys, S0, tol):
    (last_p, caches_p), (last_e, caches_e) = padded, exact
    np.testing.assert_allclose(np.asarray(last_p), np.asarray(last_e),
                               rtol=tol, atol=tol)
    assert len(caches_p) == len(caches_e)
    for cp, ce in zip(caches_p, caches_e):
        assert "allowed" not in cp
        for key in keys:
            np.testing.assert_allclose(
                np.asarray(cp[key][0, :S0], np.float32),
                np.asarray(ce[key][0, :S0], np.float32), rtol=tol, atol=tol)


# ---- the dense path (what the CPU's gates give) ----------------------------

@pytest.mark.parametrize("S0", PADDED)
def test_padded_admission_equals_the_unpadded_prefill(family, S0):
    model, keys, eng = family
    ids = _prompt(model, S0)
    last, caches, s0, bucket = eng._bucketed_prefill(_Request(-1, ids, 0))
    assert (s0, bucket) == (S0, eng._bucket(S0)) and bucket > S0
    _assert_same_prefill((last, caches), _unpadded(model, ids, MAX_LEN),
                         keys, S0, tol=2e-5)


@pytest.mark.parametrize("S0", [5, 13, 24])
def test_padded_admission_continues_as_solo_generate(family, S0):
    model, _, eng = family
    ids = _prompt(model, S0, seed=1)
    rid = eng.add_request(ids, max_new_tokens=8)
    done = eng.run_until_done()
    solo = model.generate(paddle.to_tensor(ids[None]),
                          max_new_tokens=8).numpy()[0]
    np.testing.assert_array_equal(done[rid], solo)


def test_multimodal_padded_admission_equals_the_unpadded_prefill():
    from paddle_tpu.models.llava import (LlavaConfig,
                                         LlavaForConditionalGeneration)

    paddle.seed(4)
    model = LlavaForConditionalGeneration(LlavaConfig.tiny())
    rng = np.random.RandomState(7)
    ids = rng.randint(1, 500, (11,))
    ids[2:6] = 511                               # the image placeholder run
    pixels = rng.randn(1, 3, 16, 16).astype(np.float32)
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=32, page_size=8)
    req = _Request(-1, ids, 0)
    req.pixel_values = paddle.to_tensor(pixels)
    merged = eng._multimodal_merge_fn((1, 11), pixels.shape)(
        jnp.asarray(ids[None], jnp.int32), jnp.asarray(pixels))
    last, caches, S0, bucket = eng._bucketed_prefill(req)
    assert (S0, bucket) == (11, 16)
    _assert_same_prefill((last, caches),
                         _unpadded(model, ids, 32, embeds=merged),
                         ("k", "v"), S0, tol=2e-5)
    # and the whole request: engine == solo generate with the image
    rid = eng.add_request(ids.tolist(), max_new_tokens=8,
                          pixel_values=pixels)
    solo = model.generate(paddle.to_tensor(ids[None]),
                          pixel_values=paddle.to_tensor(pixels),
                          max_new_tokens=8).numpy()[0]
    np.testing.assert_array_equal(np.asarray(eng.run_until_done()[rid]),
                                  solo)


# ---- the flash kernel, interpreted -----------------------------------------

@pytest.fixture
def interpreted_flash(monkeypatch):
    """The attention layers reach ``cached_attention`` without an
    ``interpret`` argument, so off-TPU the flash gate refuses. Ask for
    interpret mode on their behalf: the engine's own admission then runs
    the splash kernel, as on the chip."""
    real = generation.cached_attention

    def interpreted(*args, **kw):
        return real(*args, **{**kw, "interpret": True})

    monkeypatch.setattr(generation, "cached_attention", interpreted)


def _wide_head_llama():
    """The smallest trunk the splash gate takes: head width 128."""
    paddle.seed(5)
    return LlamaForCausalLM(LlamaConfig.tiny(
        num_hidden_layers=2, hidden_size=256, num_attention_heads=2,
        num_key_value_heads=1, intermediate_size=256,
        use_flash_attention=True))


@pytest.mark.parametrize("S0", [70, 100, 127])
def test_padded_admission_on_the_flash_kernel(interpreted_flash, S0):
    model = _wide_head_llama()
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=256,
                                page_size=16)
    ids = _prompt(model, S0)
    before = _attention_counts()
    last, caches, _, bucket = eng._bucketed_prefill(_Request(-1, ids, 0))
    assert bucket == 128
    step = generation._get_prefill_step(model, 128, False, rope_len=256)
    assert step.attention_impl == "flash"
    assert _attention_delta(before) == {"flash": 1, "append": 0, "xla": 0}
    # S0 is no multiple of 128: the unpadded prefill is the dense einsum
    exact = _unpadded(model, ids, 256)
    _assert_same_prefill((last, caches), exact, ("k", "v"), S0, tol=2e-4)


def test_flash_admission_continues_as_solo_generate(interpreted_flash):
    model = _wide_head_llama()
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=256,
                                page_size=16)
    ids = _prompt(model, 90, seed=2)
    rid = eng.add_request(ids, max_new_tokens=8)
    done = eng.run_until_done()
    solo = model.generate(paddle.to_tensor(ids[None]),
                          max_new_tokens=8).numpy()[0]
    np.testing.assert_array_equal(done[rid], solo)


# ---- the counter ------------------------------------------------------------

def test_counter_counts_each_admission_under_what_the_gate_recorded():
    """Off-TPU every gate refuses: three admissions, two programs traced
    (buckets 8 and 16), the third a run of a program jit already holds:
    it counts under what ITS program's trace recorded."""
    eng = ContinuousBatchEngine(_llama(), max_batch=2, max_len=MAX_LEN,
                                page_size=8)
    before = _attention_counts()
    for n in (5, 11, 6):
        eng.add_request(_prompt(eng.model, n), 2)
    eng.run_until_done()
    assert _attention_delta(before) == {"flash": 0, "append": 0, "xla": 3}


def test_counter_counts_a_prefix_hits_suffix_program_too():
    eng = ContinuousBatchEngine(_llama(), max_batch=2, max_len=MAX_LEN,
                                page_size=8, enable_prefix_cache=True)
    shared = _prompt(eng.model, 16)
    before = _attention_counts()
    eng.add_request(np.concatenate([shared, _prompt(eng.model, 3, 1)]), 6)
    eng.add_request(np.concatenate([shared, _prompt(eng.model, 4, 2)]), 2)
    eng.run_until_done()
    assert eng.prefix_pages_reused == 2
    # one whole prompt + one suffix = the two programs
    # serving_prefill_tokens_total counts
    assert sum(_attention_delta(before).values()) == 2


@pytest.mark.parametrize("taken,impl", [
    ([("rms_norm", backend.PALLAS), ("flash_attention", backend.PALLAS)],
     "flash"),
    ([("flash_attention", backend.INTERPRET)], "flash"),
    ([("flash_attention", backend.XLA), ("append_attention", backend.PALLAS)],
     "append"),
    ([("flash_attention", backend.XLA), ("append_attention", backend.XLA)],
     "xla"),
    ([("rms_norm", backend.PALLAS)], "xla"),
    ([], "xla"),
])
def test_attention_impl_of_a_trace(taken, impl):
    gates = [taken]            # the gates speak in the first call only

    def program():
        for site, how in (gates.pop() if gates else ()):
            backend.took(site, how)
        return "out"

    assert generation.traced_attention_impl(program) == "out"
    assert program.attention_impl == impl
    # a later call that traces nothing keeps what the trace recorded
    assert generation.traced_attention_impl(program) == "out"
    assert program.attention_impl == impl


def test_recording_is_per_thread_and_restores_the_outer_one():
    import threading

    with backend.recording() as outer:
        backend.took("rms_norm", backend.XLA)
        with backend.recording() as inner:
            backend.took("flash_attention", backend.PALLAS)
        other = threading.Thread(
            target=lambda: backend.took("fused_rope", backend.XLA))
        other.start()
        other.join()
        backend.took("add_rms_norm", backend.XLA)
    assert inner == [("flash_attention", backend.PALLAS)]
    assert outer == [("rms_norm", backend.XLA),
                     ("add_rms_norm", backend.XLA)]
    backend.took("rms_norm", backend.XLA)       # no recording open: fine
