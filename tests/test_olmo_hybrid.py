"""Olmo-Hybrid (``models/olmo_hybrid.py``) and its gated delta rule
(``ops/pallas/gated_delta.py``) on the CPU: the chunked kernel
(interpreted) and its composite against the token-by-token recurrence, the
decode kernel's gating, and the engine (prefill, then decoding through the
cache, slots reused) against the plain float32 reference the benchmark
judges it by, at the benchmark's rehearsal widths, with three planted
faults the comparison must catch.

Tolerances. The kernels run in float32 against a float32 recurrence over
at most 256 tokens: they differ by the order of summation alone, ~5e-6
read, so 1e-4 absolute. The engine runs the rehearsal in float32 against
the reference's float32: 2e-4 nats, five times the 4e-5 read (the head is
scaled so that the logits spread over nats, which scales rounding too),
where every planted fault reads 0.05 or more (bf16 state 0.057, the
others over 25)."""
import os

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import gated_delta as gd
from paddle_tpu.serving import ContinuousBatchEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNEL_TOL = 1e-4
TOL = 2e-4


# ---- the kernels against the recurrence -------------------------------------

def recurrence(q, k, v, g, beta, h0, n):
    """Token by token, per head: S = a (S - b (S k) k^T) + b v k^T, o = S q
    (q, k [T, H, dk], v [T, H, dv], S [H, dv, dk]), the first ``n``
    tokens."""
    S = np.asarray(h0, np.float64)
    out = []
    for t in range(n):
        a, b = np.exp(g[t])[:, None, None], beta[t][:, None, None]
        kt = k[t][:, None, :]
        sk = (S * kt).sum(-1, keepdims=True)
        S = a * (S - b * sk * kt) + b * v[t][:, :, None] * kt
        out.append((S * q[t][:, None, :]).sum(-1))
    return np.stack(out), S


def random_inputs(T, H=3, dk=16, dv=32, beta_lo=0.0, seed=0):
    rng = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.randn(1, T, H, dk)) * dk ** -0.5
    k = unit(rng.randn(1, T, H, dk))
    v = rng.randn(1, T, H, dv)
    g = -rng.uniform(0.001, 1.6, (1, T, H))       # decays ~0.2 to ~0.999
    beta = rng.uniform(beta_lo, 2.0, (1, T, H))
    h0 = 0.1 * rng.randn(1, H, dv, dk)
    return [np.asarray(x, np.float32) for x in (q, k, v, g, beta, h0)]


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernel", "composite"])
@pytest.mark.parametrize("n,bucket", [(1, 16), (63, 64), (64, 64),
                                      (65, 128), (200, 256)])
def test_chunked_form_matches_the_recurrence(n, bucket, interpret):
    """Right-padded to a bucket: the pads must not run into the state."""
    q, k, v, g, beta, h0 = random_inputs(bucket)
    o, S = gd.gated_delta_prefill(q, k, v, g, beta, h0, jnp.asarray([n]),
                                  interpret=interpret)
    ro, rS = recurrence(q[0], k[0], v[0], g[0], beta[0], h0[0], n)
    np.testing.assert_allclose(np.asarray(o)[0, :n], ro, atol=KERNEL_TOL)
    np.testing.assert_allclose(np.asarray(S)[0], rS, atol=KERNEL_TOL)


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernel", "composite"])
@pytest.mark.parametrize("heads", [3, 6])
def test_rows_of_a_batch_stop_at_their_own_lengths(heads, interpret):
    """Three rows of one 256 bucket, lengths 1, 64 and 200: each against
    the recurrence over its own tokens, o exactly 0 from its length on
    (whole chunks of pads are skipped), the state the one at its length.
    At 6 heads the kernel takes several heads a grid step."""
    lengths = (1, 64, 200)
    rows = [random_inputs(256, H=heads, seed=10 + r) for r in range(3)]
    q, k, v, g, beta, h0 = (np.concatenate(x) for x in zip(*rows))
    o, S = gd.gated_delta_prefill(q, k, v, g, beta, h0,
                                  jnp.asarray(lengths), interpret=interpret)
    o, S = np.asarray(o), np.asarray(S)
    assert np.isfinite(o).all()
    for r, n in enumerate(lengths):
        ro, rS = recurrence(q[r], k[r], v[r], g[r], beta[r], h0[r], n)
        np.testing.assert_allclose(o[r, :n], ro, atol=KERNEL_TOL)
        np.testing.assert_allclose(S[r], rS, atol=KERNEL_TOL)
        assert (o[r, n:] == 0.0).all()


@pytest.mark.parametrize("h,dk,dv,want", [(6, 16, 32, 6), (30, 96, 192, 10),
                                          (3, 16, 32, 3)])
def test_heads_per_block_divide_the_heads_within_the_budget(h, dk, dv, want):
    """The blocks a grid step holds, double-buffered and padded to whole
    lanes (96 -> 128), fit the budget: at the cell's widths 0.79 MB a
    head, so 10 of its 30 (15 would not fit in 8 MiB)."""
    hb = gd.prefill_heads_per_block(h, dk, dv)
    assert hb == want and h % hb == 0
    lanes = lambda d: -(-d // 128) * 128
    per_head = 8 * (gd.CHUNK * (2 * lanes(dk) + 2 * lanes(dv))
                    + 8 * lanes(gd.CHUNK) + 2 * dv * lanes(dk))
    assert hb * per_head <= gd._PREFILL_BLOCK_BYTES


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernel", "composite"])
def test_negative_eigenvalues(interpret):
    """beta near 2: (I - beta k k^T) reflects, 1 - beta near -1. Over 200
    tokens an error that grows with each reflection would show."""
    q, k, v, g, beta, h0 = random_inputs(256, beta_lo=1.9, seed=3)
    o, S = gd.gated_delta_prefill(q, k, v, g, beta, h0, jnp.asarray([200]),
                                  interpret=interpret)
    ro, rS = recurrence(q[0], k[0], v[0], g[0], beta[0], h0[0], 200)
    np.testing.assert_allclose(np.asarray(o)[0, :200], ro, atol=KERNEL_TOL)
    np.testing.assert_allclose(np.asarray(S)[0], rS, atol=KERNEL_TOL)


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernel", "composite"])
def test_alike_keys(interpret):
    """Keys a silu leaves mostly positive are alike (k.k ~ 0.8): the
    chunk's triangular inverse must come from substitution, not from the
    sum of powers, whose terms then grow like binomials and cancel (what
    PR 39 first wrote: errors of 2e-3 of the output)."""
    q, k, v, g, beta, h0 = random_inputs(256, beta_lo=1.0, seed=5)
    k = np.abs(k) + 0.3
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    o, S = gd.gated_delta_prefill(q, k, v, g, beta, h0, jnp.asarray([250]),
                                  interpret=interpret)
    ro, rS = recurrence(q[0], k[0], v[0], g[0], beta[0], h0[0], 250)
    np.testing.assert_allclose(np.asarray(o)[0, :250], ro, atol=KERNEL_TOL)
    np.testing.assert_allclose(np.asarray(S)[0], rS, atol=KERNEL_TOL)


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernel", "composite"])
def test_decode_step_advances_live_rows_only(interpret):
    """advance 1 steps the row; 0 (a free slot) and -1 (held mid-chunk)
    leave its state BIT-equal."""
    rng = np.random.RandomState(1)
    B, H, dk, dv = 4, 3, 16, 32
    S0 = rng.randn(B, H, dv, dk).astype(np.float32)
    q, k, v, g, beta, _ = random_inputs(B, H, dk, dv, seed=2)
    q, k, v, g, beta = (x[0] for x in (q, k, v, g, beta))
    live = jnp.asarray([1, 0, -1, 1], jnp.int32)
    o, S1 = gd.gated_delta_decode(jnp.asarray(S0), q, k, v, g, beta, live,
                                  interpret=interpret)
    S1 = np.asarray(S1)
    for b in (1, 2):
        assert (S1[b] == S0[b]).all()
    for b in (0, 3):
        ro, rS = recurrence(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                            g[b:b + 1], beta[b:b + 1], S0[b], 1)
        np.testing.assert_allclose(np.asarray(o)[b], ro[0], atol=KERNEL_TOL)
        np.testing.assert_allclose(S1[b], rS, atol=KERNEL_TOL)


# ---- the engine against the reference ---------------------------------------

def rehearsal_file() -> dict:
    from benchmarks.lib import common

    cfg = common.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "olmo-hybrid-7b-d16.json"))
    return common.rehearsed(cfg, True)


def load_reference():
    from benchmarks.lib import common

    return common.load_module(
        os.path.join(ROOT, "benchmarks", "reference", "olmo_hybrid.py"),
        "bench_reference_olmo_hybrid")


@pytest.fixture(scope="module")
def served():
    """(model, reference module, spec, plain state) at the rehearsal's
    widths: hidden 64, 4 + 4 linear heads of 8 / 16, conv 4, 4 full heads
    of 16, one period (linear x 3, full). The norms are drawn away from 1
    and the head scaled so that logits spread over several nats."""
    from benchmarks.lib import build

    cfg = rehearsal_file()
    model = build.build_model(cfg, 11)
    rng = np.random.RandomState(0)
    for name, p in model.named_parameters():
        if "norm" in name:
            p.set_value(paddle.to_tensor(
                (1.0 + 0.2 * rng.standard_normal(p.shape)).astype("float32")))
        elif name == "lm_head.weight":
            p.set_value(paddle.to_tensor(np.asarray(p._array) * 40.0))
    ref = load_reference()
    return model, ref, ref.Spec.from_config(cfg), build.plain_state(model)


def run_engine(model, prompts, max_new=6, max_batch=2):
    eng = ContinuousBatchEngine(model, max_batch=max_batch, max_len=128,
                                page_size=8)
    rids = [eng.add_request(np.asarray(p), max_new_tokens=max_new,
                            logprobs=True) for p in prompts]
    done = eng.run_until_done()
    return eng, [(done[r].tolist(), list(eng.logprobs(r))) for r in rids]


def worst_error(ref, spec, state, prompts, answers):
    """The largest |engine - reference| logprob of a chosen token,
    teacher-forced on the engine's own tokens."""
    worst = 0.0
    for p, (toks, lps) in zip(prompts, answers):
        lp = np.asarray(ref.forward_logprobs(spec, state, p + toks[:-1],
                                             last=len(toks)))
        want = lp[np.arange(len(toks)), toks]
        worst = max(worst, float(np.abs(want - np.asarray(lps)).max()))
    return worst


PROMPTS_SEED = 4


def prompts(lengths=(5, 37, 70, 100)):
    rng = np.random.RandomState(PROMPTS_SEED)
    return [rng.randint(1, 512, n).tolist() for n in lengths]


def test_engine_matches_the_reference(served):
    """Prefill (buckets 8 to 128, pads gated) then decode through the
    cache, four requests on two slots: two slots are reused."""
    model, ref, spec, state = served
    ps = prompts()
    _, answers = run_engine(model, ps)
    assert worst_error(ref, spec, state, ps, answers) < TOL
    spread = max(-min(lps) for _, lps in answers)
    assert spread > 1.0        # the logits are not flat: the check bites


def test_a_reused_slot_answers_as_a_fresh_engine(served):
    """Admission OVERWRITES the slot's state and conv window."""
    model, _, _, _ = served
    ps = prompts((70, 37))
    _, both = run_engine(model, ps, max_batch=1)        # 37 reuses the slot
    _, fresh = run_engine(model, ps[1:], max_batch=1)
    assert both[1][0] == fresh[0][0]
    np.testing.assert_allclose(both[1][1], fresh[0][1], atol=1e-6)


def test_the_gauge_and_counter(served):
    from paddle_tpu.observability import catalog

    model, _, _, _ = served
    before = catalog.SERVING_GDN_STATE_UPDATES.value(engine="decoder")
    run_engine(model, prompts((20,)), max_new=5)
    after = catalog.SERVING_GDN_STATE_UPDATES.value(engine="decoder")
    # 5 tokens, 5 decode steps: each samples from the logits it holds and
    # runs its token through the layers, advancing the row in each of the
    # 3 linear layers (the last step's forward is for no one)
    assert after - before == 5 * 3
    gauge = catalog.SERVING_KV_POOL_BYTES.value(engine="decoder",
                                               layer_type="linear_state")
    per_slot = 3 * (4 * 16 * 8 * 4 + 3 * (2 * 4 * 8 + 4 * 16) * 4)
    assert gauge == 2 * per_slot


def test_the_prefill_chunks_counter(served):
    """Admission counts the kernel's chunks per linear layer: a prompt of
    100 in the 128 bucket runs 2 and skips none, one of 130 in the 256
    bucket runs 3 and skips the chunk of pads after it (the rehearsal
    holds 256 positions)."""
    from paddle_tpu.observability import catalog

    model, _, _, _ = served
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=256,
                                page_size=8)
    assert [eng._bucket(n) for n in (100, 130)] == [128, 256]
    count = lambda kind: catalog.SERVING_GDN_PREFILL_CHUNKS.value(
        engine="decoder", kind=kind)
    before = count("run"), count("skipped")
    for p in prompts((100, 130)):
        eng.add_request(np.asarray(p), max_new_tokens=1)
    eng.run_until_done()
    assert (count("run") - before[0], count("skipped") - before[1]) == \
        ((2 + 3) * 3, (0 + 1) * 3)


def test_the_skipped_share_reader():
    """The benchmark's reader of that counter: the window's skipped over
    all, and nothing where the program has no such series."""
    import importlib

    reader = importlib.import_module(
        "benchmarks.layer_metrics.gated_delta_prefill_skipped_share")
    series = 'serving_gdn_prefill_chunks_total{engine="decoder",kind="%s"}'
    ctx = lambda before, after: {"before": {"metrics": before},
                                 "after": {"metrics": after}}
    got = reader.read(ctx({series % "run": 10.0, series % "skipped": 2.0},
                          {series % "run": 10.0 + 84,
                           series % "skipped": 2.0 + 16}))
    assert got == pytest.approx(16.0)
    assert reader.read(ctx({}, {})) is None


@pytest.mark.parametrize("feature", [
    dict(enable_prefix_cache=True), dict(prefill_chunk_tokens=16),
    dict(enable_preemption=True), dict(speculative_k=2)])
def test_features_that_read_pages_as_the_context_are_refused(served,
                                                             feature):
    model, _, _, _ = served
    with pytest.raises(NotImplementedError, match="recurrent state"):
        ContinuousBatchEngine(model, max_batch=2, max_len=128, page_size=8,
                              **feature)


def test_handoff_and_migration_are_refused(served):
    model, _, _, _ = served
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=128, page_size=8)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.export_prefill(np.arange(1, 9), max_new_tokens=2)


# ---- planted faults ----------------------------------------------------------

def _fault_beta_not_doubled(monkeypatch, model):
    for layer in model.llama.layers:
        attn = getattr(layer, "linear_attn", None)
        if attn is not None:
            monkeypatch.setattr(attn.config, "linear_allow_neg_eigval",
                                False)


def _fault_state_in_bf16(monkeypatch, model):
    rnd = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)
    pre, dec = gd.gated_delta_prefill, gd.gated_delta_decode

    def prefill(*a, **kw):
        o, s = pre(*a, **kw)
        return o, rnd(s)

    def decode(*a, **kw):
        o, s = dec(*a, **kw)
        return o, rnd(s)

    monkeypatch.setattr(gd, "gated_delta_prefill", prefill)
    monkeypatch.setattr(gd, "gated_delta_decode", decode)


def _fault_state_zeroed_at_a_chunk(monkeypatch, model):
    chunk = gd._chunk_math
    monkeypatch.setattr(gd, "_chunk_math", lambda q, k, v, gcc, gcr, bc, S,
                        c: chunk(q, k, v, gcc, gcr, bc, jnp.zeros_like(S), c))


@pytest.mark.parametrize("plant", [_fault_beta_not_doubled,
                                   _fault_state_in_bf16,
                                   _fault_state_zeroed_at_a_chunk],
                         ids=["beta_not_doubled", "state_in_bf16",
                              "state_zeroed_at_a_chunk"])
def test_the_comparison_catches_a_planted_fault(served, monkeypatch, plant):
    """Each fault on a fresh model of the same weights (the programs are
    memoised on the model), prompts over one chunk of 64."""
    from benchmarks.lib import build

    model, ref, spec, state = served
    twin = build.build_model(rehearsal_file(), 11)
    twin.set_state_dict({k: paddle.to_tensor(np.asarray(v))
                         for k, v in state.items()})
    plant(monkeypatch, twin)
    ps = prompts((70, 100))
    _, answers = run_engine(twin, ps)
    assert worst_error(ref, spec, state, ps, answers) > 25 * TOL


@pytest.mark.parametrize("family", ["olmo_hybrid", "cohere2_moe_rings"])
def test_byte_estimators_count_what_the_pools_hold(served, family):
    """Preflight's ``kv_cache_bytes`` is the pools the engine allocates,
    page-less recurrent layers and window rings included; the atlas's
    per-token coefficient counts the layers that keep pages."""
    from paddle_tpu.analysis.graph import cost
    from paddle_tpu.observability import kvatlas

    if family == "olmo_hybrid":
        model = served[0]
        per_token = 1 * 2 * 4 * 16 * 4        # one full layer, f32
    else:
        from paddle_tpu.models.cohere2_moe import (Cohere2MoEConfig,
                                                   Cohere2MoEForCausalLM)

        paddle.seed(0)
        model = Cohere2MoEForCausalLM(Cohere2MoEConfig.tiny())
        per_token = 4 * 2 * 2 * 16 * 4        # 4 paged layers, 2 KV heads
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=128, page_size=8)
    pools = sum(c[a].nbytes + c[b].nbytes
                for c, (a, b) in zip(eng._caches, eng._pool_keys))
    assert cost.kv_cache_bytes(model.config, 2, 128, 8) == pools
    assert kvatlas.kv_bytes_per_token(model.config) == per_token
    assert eng.kvatlas.preflight_bytes == pools
