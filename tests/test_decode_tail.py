"""Fused decode-tail megakernels (ops/pallas/decode_tail): kernel-level
parity against the discrete reference ops, and end-to-end
TOKEN-IDENTITY of the fused S=1 decode path vs the discrete kernels —
the acceptance contract of the FLAGS_use_fused_decode_tail flag. All of
it runs in interpret mode on CPU (tier-1; no TPU needed)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     fused_decode_supported)
from paddle_tpu.ops.pallas import decode_tail, fused_norm
from paddle_tpu.utils.flags import get_flags, set_flags


@pytest.fixture
def fused_flag():
    """Restore the flag and the once-per-shape announce dedupe set."""
    prev = get_flags("FLAGS_use_fused_decode_tail")[
        "FLAGS_use_fused_decode_tail"]
    seen = set(decode_tail._announced)
    yield
    set_flags({"FLAGS_use_fused_decode_tail": prev})
    decode_tail._announced.clear()
    decode_tail._announced.update(seen)


def _fusable_config(**kw):
    """Smallest shape that passes the structural gate: head_dim 128,
    hidden % 128 == 0."""
    base = dict(vocab_size=128, hidden_size=256, intermediate_size=512,
                num_hidden_layers=2, num_attention_heads=2,
                num_key_value_heads=1, max_position_embeddings=256,
                use_flash_attention=False, dtype="float32")
    base.update(kw)
    return LlamaConfig(**base)


# ---------------------------------------------------------------------------
# kernel-level parity
# ---------------------------------------------------------------------------

def _rope_ref_rows(x, cos, sin):
    """rope_ref specialized to per-row tables: x [B, n, D], cos/sin
    [B, D]."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (x.astype(jnp.float32) * cos[:, None, :]
            + rot.astype(jnp.float32) * sin[:, None, :]).astype(x.dtype)


def test_fused_qkv_rope_matches_discrete():
    rng = np.random.RandomState(0)
    B, hidden, H, hk, D = 4, 256, 2, 1, 128
    eps = 1e-6
    x = jnp.asarray(rng.randn(B, hidden), jnp.float32)
    wn = jnp.asarray(rng.randn(hidden), jnp.float32)
    wq = jnp.asarray(rng.randn(hidden, H * D) * 0.05, jnp.float32)
    wk = jnp.asarray(rng.randn(hidden, hk * D) * 0.05, jnp.float32)
    wv = jnp.asarray(rng.randn(hidden, hk * D) * 0.05, jnp.float32)
    cos = jnp.asarray(rng.randn(B, D), jnp.float32)
    sin = jnp.asarray(rng.randn(B, D), jnp.float32)

    q, k, v = decode_tail.fused_qkv_rope(x, wn, wq, wk, wv, cos, sin,
                                         eps, H, hk, D)

    normed = fused_norm._rmsnorm_ref(x, wn, eps)
    qr = _rope_ref_rows((normed @ wq).reshape(B, H, D), cos, sin)
    kr = _rope_ref_rows((normed @ wk).reshape(B, hk, D), cos, sin)
    vr = normed @ wv
    np.testing.assert_allclose(np.asarray(q), qr.reshape(B, H * D),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(k), kr.reshape(B, hk * D),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr),
                               rtol=1e-5, atol=1e-5)


def test_fused_epilogue_matches_discrete():
    rng = np.random.RandomState(1)
    B, width, hidden = 4, 256, 256
    eps = 1e-6
    attn = jnp.asarray(rng.randn(B, width), jnp.float32)
    wo = jnp.asarray(rng.randn(width, hidden) * 0.05, jnp.float32)
    res = jnp.asarray(rng.randn(B, hidden), jnp.float32)
    wn = jnp.asarray(rng.randn(hidden), jnp.float32)
    normed, new_res = decode_tail.fused_epilogue(attn, wo, res, wn, eps)
    h_ref = attn @ wo + res
    np.testing.assert_allclose(np.asarray(new_res), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(normed),
        np.asarray(fused_norm._rmsnorm_ref(h_ref, wn, eps)),
        rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end token identity (THE tier-1 parity gate)
# ---------------------------------------------------------------------------

def _gen(cfg, ids, **kw):
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    return np.asarray(model.generate(ids, **kw).numpy())


def test_generate_dense_token_identical(fused_flag):
    cfg = _fusable_config()
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (2, 8)))
    set_flags({"FLAGS_use_fused_decode_tail": False})
    ref = _gen(cfg, ids, max_new_tokens=12)
    decode_tail._announced.clear()
    set_flags({"FLAGS_use_fused_decode_tail": True})
    fused = _gen(cfg, ids, max_new_tokens=12)
    # the fused path must have actually activated — a silently declined
    # gate would make this test vacuous
    assert any(s[0] == "dense" for s in decode_tail._announced)
    np.testing.assert_array_equal(ref, fused)


def test_generate_paged_token_identical(fused_flag):
    cfg = _fusable_config()
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(0, 128, (2, 8)))
    set_flags({"FLAGS_use_fused_decode_tail": False})
    ref = _gen(cfg, ids, max_new_tokens=10, paged=True, page_size=16)
    decode_tail._announced.clear()
    set_flags({"FLAGS_use_fused_decode_tail": True})
    fused = _gen(cfg, ids, max_new_tokens=10, paged=True, page_size=16)
    assert any(s[0] == "paged" for s in decode_tail._announced)
    np.testing.assert_array_equal(ref, fused)


def test_generate_ragged_token_identical(fused_flag):
    """attention_mask path: per-row RoPE positions (row_pos) must gather
    the same table rows the discrete per-row rope reads."""
    cfg = _fusable_config()
    rng = np.random.RandomState(2)
    ids = rng.randint(1, 128, (3, 10))
    am = np.ones((3, 10), np.int64)
    am[0, 6:] = 0          # right-padded row
    am[2, :3] = 0          # left-padded row
    kw = dict(max_new_tokens=9, attention_mask=paddle.to_tensor(am),
              eos_token_id=5)
    set_flags({"FLAGS_use_fused_decode_tail": False})
    ref = _gen(cfg, paddle.to_tensor(ids), **kw)
    decode_tail._announced.clear()
    set_flags({"FLAGS_use_fused_decode_tail": True})
    fused = _gen(cfg, paddle.to_tensor(ids), **kw)
    assert decode_tail._announced
    np.testing.assert_array_equal(ref, fused)


def test_engine_token_identical(fused_flag):
    """The ContinuousBatchEngine decode step — the path the serving
    tier multiplies across workers — is token-identical under the
    flag."""
    from paddle_tpu.serving import ContinuousBatchEngine

    cfg = _fusable_config()

    def run():
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        eng = ContinuousBatchEngine(model, max_batch=4, max_len=64,
                                    page_size=16)
        rng = np.random.RandomState(1)
        for i in range(6):
            eng.add_request(rng.randint(0, 128, (4 + i,)), 8)
        return {rid: toks.tolist()
                for rid, toks in sorted(eng.run_until_done().items())}

    set_flags({"FLAGS_use_fused_decode_tail": False})
    ref = run()
    decode_tail._announced.clear()
    set_flags({"FLAGS_use_fused_decode_tail": True})
    fused = run()
    assert decode_tail._announced
    assert ref == fused


# ---------------------------------------------------------------------------
# gate behavior
# ---------------------------------------------------------------------------

def _decode_layer_and_cache(cfg, b=2):
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    layer = model.llama.layers[0]
    d = layer.self_attn.head_dim
    hk = cfg.num_key_value_heads
    cache = {"k": jnp.zeros((b, 16, hk, d), jnp.float32),
             "v": jnp.zeros((b, 16, hk, d), jnp.float32), "pos": 4}
    hidden = paddle.to_tensor(
        np.zeros((b, 1, cfg.hidden_size), np.float32))
    cos, sin = model.llama._rope(16)
    return layer, hidden, cache, cos


def test_gate_accepts_fusable_shape(fused_flag):
    set_flags({"FLAGS_use_fused_decode_tail": True})
    layer, hidden, cache, cos = _decode_layer_and_cache(_fusable_config())
    assert fused_decode_supported(layer, hidden, cache, cos)


def test_gate_declines_flag_off(fused_flag):
    set_flags({"FLAGS_use_fused_decode_tail": False})
    layer, hidden, cache, cos = _decode_layer_and_cache(_fusable_config())
    assert not fused_decode_supported(layer, hidden, cache, cos)


@pytest.mark.parametrize("kw", [
    dict(num_attention_heads=4, num_key_value_heads=2),  # head_dim 64
    dict(qk_norm=True),                                  # Qwen3-style
    dict(attention_bias=True),                           # Qwen2-style
    dict(partial_rotary_factor=0.5),                     # partial rope
])
def test_gate_declines_unsupported_structure(fused_flag, kw):
    set_flags({"FLAGS_use_fused_decode_tail": True})
    layer, hidden, cache, cos = _decode_layer_and_cache(
        _fusable_config(**kw))
    assert not fused_decode_supported(layer, hidden, cache, cos)


def test_unsupported_model_still_generates(fused_flag):
    """Flag on + a declining structure = the discrete path, silently
    and correctly (exact-parity fallback)."""
    cfg = _fusable_config(attention_bias=True)
    ids = paddle.to_tensor(
        np.random.RandomState(3).randint(0, 128, (2, 6)))
    set_flags({"FLAGS_use_fused_decode_tail": False})
    ref = _gen(cfg, ids, max_new_tokens=8)
    decode_tail._announced.clear()
    set_flags({"FLAGS_use_fused_decode_tail": True})
    out = _gen(cfg, ids, max_new_tokens=8)
    assert not decode_tail._announced
    np.testing.assert_array_equal(ref, out)


def test_prefill_never_fused(fused_flag):
    set_flags({"FLAGS_use_fused_decode_tail": True})
    layer, _, cache, cos = _decode_layer_and_cache(_fusable_config())
    prompt = paddle.to_tensor(np.zeros((2, 4, 256), np.float32))  # S=4
    assert not fused_decode_supported(layer, prompt, cache, cos)


# ---------------------------------------------------------------------------
# audit surface
# ---------------------------------------------------------------------------

def test_fused_step_event_recorded(fused_flag):
    from paddle_tpu.observability import flightrecorder as frec

    rec = frec.get_recorder()
    rec.clear()
    rec.enabled = True  # not enable(): skip the compile-events hook
    try:
        set_flags({"FLAGS_use_fused_decode_tail": True})
        decode_tail._announced.clear()
        cfg = _fusable_config()
        ids = paddle.to_tensor(
            np.random.RandomState(4).randint(0, 128, (2, 6)))
        _gen(cfg, ids, max_new_tokens=4)
        evs = rec.events(kind="kernel.fused_step")
        assert evs and evs[0]["head_dim"] == 128
        assert evs[0]["layout"] == "dense"
        # announce dedupes per shape: one event, not one per layer/step
        assert len(evs) == 1
    finally:
        rec.enabled = False
        rec.clear()
