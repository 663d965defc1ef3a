"""Cohere2-MoE (``models/cohere2_moe.py``) at the benchmark's rehearsal
sizes on the CPU: the engine (prefill, then decode through the window
layers' ring past two wraps) against the plain float32 reference the
benchmark judges it by, the held share against the uncut layer, the
dropless expert layer, the pools by layer type and their refusals.

Everything is float32 here, so program and reference agree to rounding:
every tolerance below is 2e-4 nats or 2e-5 absolute, two orders above the
~5e-7 read and three below what any planted fault shows (0.002-0.04)."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.cohere2_moe import (Cohere2MoEConfig,
                                           Cohere2MoEForCausalLM)
from paddle_tpu.serving import ContinuousBatchEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4


def load_reference():
    from benchmarks.lib import common

    return common.load_module(
        os.path.join(ROOT, "benchmarks", "reference", "cohere2_moe.py"),
        "bench_reference_cohere2_moe")


def rehearsal_file() -> dict:
    """The benchmark's own configuration at its ``rehearse`` sizes."""
    from benchmarks.lib import common

    cfg = common.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "command-a-plus-ep8-d4.json"))
    return common.rehearsed(cfg, True)


@pytest.fixture(scope="module")
def served():
    """(file, model, reference module, spec, plain state): hidden 128,
    8 heads / 2 KV of 16, window 32, 4 of 8 experts held, top 2, 2 shared,
    4 layers (sliding x 3, full), max_len 128, pages of 8."""
    from benchmarks.lib import build

    cfg = rehearsal_file()
    model = build.build_model(cfg, 5)
    rng = np.random.RandomState(0)
    for name, p in model.named_parameters():
        if "norm" in name:   # all ones at init: make them matter
            p.set_value(paddle.to_tensor(
                (1.0 + 0.2 * rng.standard_normal(p.shape)).astype("float32")))
    ref = load_reference()
    return (cfg, model, ref, ref.Spec.from_config(cfg),
            build.plain_state(model))


def run_engine(model, cfg, jobs, **engine_args):
    """[(prompt ids, tokens, logprobs)] of ``jobs`` = [(ids, max_new)],
    all in flight at once."""
    args = dict(cfg["recipe"]["engine"], **engine_args)
    engine = ContinuousBatchEngine(model, **args)
    rids = [engine.add_request(np.asarray(ids), max_new_tokens=n,
                               logprobs=True) for ids, n in jobs]
    done = {}
    while len(done) < len(rids):
        done.update(engine.step())
    return engine, [(list(ids), [int(t) for t in done[r]],
                     np.asarray(engine._finished_logprobs[r]))
                    for (ids, _), r in zip(jobs, rids)]


def reference_logprobs(ref, spec, state, ids, toks):
    lp = np.asarray(ref.forward_logprobs(spec, state, ids + toks[:-1],
                                         last=len(toks)))
    return lp[np.arange(len(toks)), np.asarray(toks)]


# prompt, new tokens: below the window; over the window but inside the ring
# (5 pages x 8 = 40); over the ring (the scatter wraps); a short prompt that
# decodes through 2.9 rings
@pytest.mark.parametrize("n_prompt,n_new", [(20, 12), (37, 10), (70, 50),
                                            (9, 110)])
def test_engine_matches_reference_through_the_ring(served, n_prompt, n_new):
    cfg, model, ref, spec, state = served
    ids = np.random.RandomState(n_prompt).randint(1, cfg["vocab_size"],
                                                  n_prompt)
    engine, [(prompt, toks, lps)] = run_engine(model, cfg, [(ids, n_new)])
    assert engine._ring_pages == [5, 5, 5, None]
    assert len(toks) == n_new
    want = reference_logprobs(ref, spec, state, prompt, toks)
    assert np.abs(want - lps).max() < TOL


# what ``_bucket`` reads of an engine: pages of 16, 8192 positions
PAGED_8K = SimpleNamespace(
    page_size=16, max_len=8192,
    _HALF_OCTAVES_ABOVE=ContinuousBatchEngine._HALF_OCTAVES_ABOVE)


@pytest.mark.parametrize("n,bucket", [
    (1, 16), (100, 128), (1024, 1024),                   # powers of two
    (1500, 1536), (2048, 2048), (2049, 3072), (3072, 3072), (4054, 4096),
    (4245, 5120), (6144, 6144), (7000, 7168), (8000, 8192),
    (1057, 1536), (1454, 1536), (1652, 2048), (2000, 2048),  # docs grid
    (3529, 4096)])                                           # rag grid
def test_long_prompts_pad_to_a_step_not_to_a_power_of_two(n, bucket):
    """Up to 1024 tokens the next power of two (the chat cell's short
    programs are what they were), beyond it the next multiple of half the
    prompt's octave, at most 1024: the docs cell's prompts of 1057-1454
    tokens run in 1536, not in 2048; the rag cell's in its 3072-6144."""
    assert ContinuousBatchEngine._bucket(PAGED_8K, n) == bucket


def test_every_bucket_pads_a_long_prompt_under_a_third():
    """Every n of 1..8192 at pages of 16: the bucket holds n, is whole
    pages, stays under max_len; above the threshold it is a multiple of
    512 (splash tiles it) with under a third of pad; an octave has two
    buckets, or one every 1024 tokens; over 2048 tokens the bucket is the
    next multiple of 1024, as before this rule."""
    limit = ContinuousBatchEngine._HALF_OCTAVES_ABOVE
    octaves = {}
    for n in range(1, 8193):
        b = ContinuousBatchEngine._bucket(PAGED_8K, n)
        assert n <= b <= 8192 and b % 16 == 0
        if n > limit:
            assert b % 512 == 0 and (b - n) / b < 1 / 3
            octaves.setdefault(1 << ((n - 1).bit_length() - 1), set()).add(b)
        if n > 2 * limit:
            assert b == -(-n // limit) * limit
    assert {o: len(bs) for o, bs in octaves.items()} == {
        1024: 2, 2048: 2, 4096: 4}


@pytest.mark.parametrize("n_prompt,bucket", [(37, 48), (70, 80), (90, 96),
                                             (100, 112)])
def test_a_stepped_bucket_scatters_into_the_ring(served, monkeypatch,
                                                 n_prompt, bucket):
    """A bucket that is no power of two (the threshold shrunk to the
    rehearsal's sizes): 6, 10, 12 and 14 pages of prompt into rings of 5,
    and the decode that follows, against the reference."""
    cfg, model, ref, spec, state = served
    monkeypatch.setattr(ContinuousBatchEngine, "_HALF_OCTAVES_ABOVE", 16)
    ids = np.random.RandomState(n_prompt).randint(1, cfg["vocab_size"],
                                                  n_prompt)
    engine, [(prompt, toks, lps)] = run_engine(model, cfg, [(ids, 12)])
    assert engine._bucket(n_prompt) == bucket
    want = reference_logprobs(ref, spec, state, prompt, toks)
    assert np.abs(want - lps).max() < TOL


def test_a_row_is_the_same_alone_and_in_a_full_batch(served):
    """Dropless: no capacity, so a request's logits do not depend on its
    neighbours (the capacity dispatch dropped in batch order)."""
    cfg, model, ref, spec, state = served
    rng = np.random.RandomState(7)
    mine = rng.randint(1, cfg["vocab_size"], 33)
    others = [(rng.randint(1, cfg["vocab_size"], n), 20)
              for n in (60, 17, 45)]
    _, [(_, toks_alone, lps_alone)] = run_engine(model, cfg, [(mine, 20)])
    _, rows = run_engine(model, cfg, [(mine, 20)] + others)
    assert rows[0][1] == toks_alone
    assert np.abs(rows[0][2] - lps_alone).max() < 2e-5
    for prompt, toks, lps in rows:      # and every neighbour is right too
        want = reference_logprobs(ref, spec, state, prompt, toks)
        assert np.abs(want - lps).max() < TOL


@pytest.mark.parametrize("fault,least", [
    ({"rope_pairs": "halves"}, 5e-3),      # half-split instead of adjacent
    ({"global_rope": True}, 1e-3),         # a rotation on the global layer
    ({"sliding_window": 31}, 5e-3),        # a window one short
])
def test_reference_tells_a_planted_fault(served, fault, least):
    """The program's plain forward equals the reference as configured and
    differs from one with the fault: adjacent-pair rotary against
    half-split, no rotation on global layers, the window's edge."""
    import jax

    cfg, model, ref, spec, state = served
    ids = np.random.RandomState(1).randint(1, cfg["vocab_size"], 70)
    logits = model(paddle.to_tensor(ids[None]))._array[0]
    got = np.asarray(jax.nn.log_softmax(logits.astype("float32"), -1))
    right = np.asarray(ref.forward_logprobs(spec, state, ids, last=70))
    wrong = np.asarray(ref.forward_logprobs(spec._replace(**fault), state,
                                            ids, last=70))
    assert np.abs(got - right).max() < TOL
    assert np.abs(got - wrong).max() > least


def test_shares_sum_to_the_uncut_layer():
    """The guide's shares test: 8 shares' ``routed_H`` summed + the shared
    experts once + attention once = the uncut layer's output."""
    import jax.numpy as jnp

    from paddle_tpu.models.cohere2_moe import Cohere2MoEDecoderLayer

    paddle.seed(11)
    kw = dict(published_num_experts=8, num_experts_per_tok=3)
    whole_cfg = Cohere2MoEConfig.tiny(num_experts=8, **kw)
    whole = Cohere2MoEDecoderLayer(whole_cfg, 0)
    x = paddle.to_tensor(np.random.RandomState(2).standard_normal(
        (1, 24, 128)).astype("float32"))
    model_rope = Cohere2MoEForCausalLM(Cohere2MoEConfig.tiny(
        num_experts=8, num_hidden_layers=1,
        layer_types=("sliding_attention",), **kw)).llama
    cos, sin = model_rope._rope(24)
    uncut = whole(x, cos, sin)._array
    u32 = whole.input_layernorm(x)
    attn, _ = whole.self_attn(u32, cos, sin, {
        "k": jnp.zeros((1, 24, 2, 16)), "v": jnp.zeros((1, 24, 2, 16)),
        "pos": 0, "prefill": True})
    shared = whole.mlp.shared_expert(u32)._array / 2
    routed = jnp.zeros_like(uncut)
    for share in range(8):
        cut = Cohere2MoEDecoderLayer(Cohere2MoEConfig.tiny(
            num_experts=1, held_experts=(share, share + 1), **kw), 0)
        cut.mlp.gate_weight.set_value(whole.mlp.gate_weight)
        for name in ("w1", "b1", "w2", "b2"):
            getattr(cut.mlp.experts, name).set_value(paddle.to_tensor(
                getattr(whole.mlp.experts, name)._array[share:share + 1]))
        cut.mlp.shared_expert = None          # the share's routed part alone
        part, counts = cut.mlp.forward_counted(u32, router_input=u32)
        routed = routed + part._array
        assert int(counts._array[0]) == 24 and int(counts._array[1]) <= 24
    total = x._array + attn._array + shared + routed
    assert np.abs(np.asarray(total - uncut)).max() < 2e-5


def test_every_pair_is_computed_where_capacity_would_drop():
    """All tokens choose the same two experts: the capacity dispatch of
    factor 1.0 keeps 2 x 16 x 1.0 / 4 = 8 rows an expert and drops the
    rest; the dropless layer computes all 32 pairs."""
    import jax.numpy as jnp

    from paddle_tpu.distributed.moe import _expert_act
    from paddle_tpu.models.llama_moe import LlamaMoEConfig, MoEMLP

    paddle.seed(4)
    cfg = LlamaMoEConfig.tiny_moe(n_shared_experts=0, moe_capacity_factor=1.0)
    mlp = MoEMLP(cfg)
    gate = np.zeros((128, 4), "float32")
    gate[:, 1], gate[:, 3] = 0.3, 0.2         # every row: experts 1 then 3
    mlp.gate_weight.set_value(paddle.to_tensor(gate))
    x = np.abs(np.random.RandomState(0).standard_normal(
        (1, 16, 128))).astype("float32")
    out, counts = mlp.forward_counted(paddle.to_tensor(x))
    out = out._array[0]
    assert counts._array.tolist() == [16, 0, 16, 0, 16]
    logits = x[0] @ gate
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    w = p[:, [1, 3]] / p[:, [1, 3]].sum(-1, keepdims=True)
    want = np.zeros((16, 128), "float32")
    for j, e in enumerate((1, 3)):
        h = _expert_act(jnp.asarray(x[0]) @ mlp.experts.w1._array[e],
                        "swiglu")
        want += w[:, j:j + 1] * np.asarray(h @ mlp.experts.w2._array[e])
    assert np.abs(np.asarray(out) - want).max() < 2e-5


def test_pad_rows_route_nowhere():
    """A right-padded prefill's pads are not routed: the counts are those
    of the real rows, whatever the pads hold."""
    from paddle_tpu.models.llama_moe import LlamaMoEConfig, MoEMLP

    paddle.seed(4)
    mlp = MoEMLP(LlamaMoEConfig.tiny_moe(n_shared_experts=0))
    x = np.random.RandomState(0).standard_normal((1, 16, 128)).astype(
        "float32")
    valid = paddle.to_tensor(np.arange(16)[None] < 10)
    out, counts = mlp.forward_counted(paddle.to_tensor(x), valid=valid)
    out, counts = out._array, counts._array
    assert int(counts[0]) == 10 and int(counts[1:].sum()) == 20
    real, alone = mlp.forward_counted(paddle.to_tensor(x[:, :10]))
    real = real._array
    assert alone._array.tolist() == counts.tolist()
    assert np.abs(np.asarray(out[:, :10] - real)).max() < 2e-6
    assert np.abs(np.asarray(out[:, 10:])).max() == 0.0


def test_ring_attention_equals_the_full_row_with_a_window():
    """``_paged_ring_attention`` over a ring that wrapped against the
    windowed gather reference over a pool that holds the whole row."""
    import jax.numpy as jnp

    from paddle_tpu.generation import (_paged_attention_ref,
                                       _paged_ring_attention)

    rng = np.random.RandomState(3)
    B, H, hk, D, ps, window, ring, n_full = 3, 4, 2, 16, 4, 10, 4, 12
    lengths = np.asarray([7, 16, 41])
    k_full = rng.standard_normal((hk, B * n_full, ps, D)).astype("float32")
    v_full = rng.standard_normal((hk, B * n_full, ps, D)).astype("float32")
    k_ring = np.zeros((hk, B * ring, ps, D), "float32")
    v_ring = np.zeros_like(k_ring)
    for b in range(B):
        for p in range(lengths[b]):
            src = (b * n_full + p // ps, p % ps)
            dst = (b * ring + (p // ps) % ring, p % ps)
            k_ring[:, dst[0], dst[1]] = k_full[:, src[0], src[1]]
            v_ring[:, dst[0], dst[1]] = v_full[:, src[0], src[1]]
    q = jnp.asarray(rng.standard_normal((B, H, D)).astype("float32"))
    want = _paged_attention_ref(
        q, jnp.asarray(k_full), jnp.asarray(v_full), jnp.asarray(lengths),
        jnp.arange(B * n_full).reshape(B, n_full), window=window)
    got = _paged_ring_attention(
        q, jnp.asarray(k_ring), jnp.asarray(v_ring), jnp.asarray(lengths),
        jnp.arange(B * ring).reshape(B, ring), window)
    assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_pools_by_layer_type_and_their_bytes(served):
    cfg, model, *_ = served
    engine = ContinuousBatchEngine(model, **cfg["recipe"]["engine"])
    shapes = [c["k_pages"].shape for c in engine._caches]
    # 4 slots x (ceil(32 / 8) + 1) pages on window layers, x 16 on global
    assert shapes == [(2, 20, 8, 16)] * 3 + [(2, 64, 8, 16)]
    assert ["ring" in c for c in engine._caches] == [True] * 3 + [False]
    from paddle_tpu.observability import catalog

    def pool(kind):
        return catalog.SERVING_KV_POOL_BYTES.labels(
            engine="decoder", layer_type=kind).value

    assert pool("window") == 3 * 2 * 2 * 20 * 8 * 16 * 4
    assert pool("global") == 2 * 2 * 64 * 8 * 16 * 4


def test_a_uniform_windowed_model_keeps_whole_rows():
    """No ``layer_types``: whole rows, whatever the window, and with them
    the features that read a slot's pages as one run (which an engine
    with rings refuses by name, below)."""
    from paddle_tpu.models.mistral import MistralConfig, MistralForCausalLM

    paddle.seed(1)
    model = MistralForCausalLM(MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, sliding_window=16, dtype="float32"))
    engine = ContinuousBatchEngine(model, max_batch=2, max_len=128,
                                   page_size=8, enable_prefix_cache=True,
                                   prefill_chunk_tokens=16)
    assert engine._ring_pages == [None, None]
    assert all("ring" not in c for c in engine._caches)


@pytest.mark.parametrize("feature,how", [
    ("prefix caching", {"enable_prefix_cache": True}),
    ("chunked prefill", {"prefill_chunk_tokens": 16}),
    ("preemption", {"enable_preemption": True}),
    ("speculative decoding", {"speculative_k": 2}),
    ("KV handoff", "export_prefill"),
    ("migration", "export_slot"),
])
def test_rings_refuse_by_name_what_assumes_whole_rows(served, feature, how):
    cfg, model, *_ = served
    args = dict(cfg["recipe"]["engine"])
    with pytest.raises(NotImplementedError, match=feature):
        if isinstance(how, dict):
            ContinuousBatchEngine(model, **args, **how)
        elif how == "export_prefill":
            ContinuousBatchEngine(model, **args).export_prefill(
                np.arange(1, 9), max_new_tokens=4)
        else:
            ContinuousBatchEngine(model, **args).export_slot(0)


def test_counters_follow_the_programs(served):
    """Rows routed and held pairs, prefill and decode, arrive with the
    tokens: rows = (prompt + decoded) x 4 expert layers."""
    cfg, model, *_ = served
    from paddle_tpu.observability import catalog

    def read():
        return (catalog.SERVING_MOE_TOKENS.labels(engine="decoder").value,
                catalog.SERVING_MOE_HELD_PAIRS.labels(engine="decoder").value,
                catalog.SERVING_DECODE_ROWS_OVER_WINDOW.labels(
                    engine="decoder").value)

    before = read()
    ids = np.random.RandomState(3).randint(1, cfg["vocab_size"], 29)
    run_engine(model, cfg, [(ids, 10)])
    tokens, pairs, over = (a - b for a, b in zip(read(), before))
    # the step in flight when the last token retires is drained too, and
    # one row-step of it may count: 29 + 9 decoded rows, or one more
    assert tokens in (4 * 38, 4 * 39)
    assert 0 < pairs <= 2 * tokens
    assert over == 6           # contexts 33..38 are over the window of 32


def test_the_file_is_the_catalogs_row():
    """Every number of the catalog row's config is in the benchmark's file
    under the same key, but for what ``reduced`` names."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(path)
               if '"command-a-plus-05-2026"' in line)
    from benchmarks.lib import common

    cfg = common.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "command-a-plus-ep8-d4.json"))
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            continue
        assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == ["layer_types", "num_experts",
                                      "num_hidden_layers", "vocab_size"]
