"""The paged decode attention kernel (ops/pallas/paged_decode) in
interpret mode on the CPU, against ``generation._paged_attention_ref``:
group widths 1, 4 and 16, pages of 8 and 16, lengths at every edge a
block has, the engine's ``arange`` tables (consecutive pages: one copy a
block) and a permuted table (one copy a page); which copies each takes;
and the engine end to end with the kernel admitted. That the kernel
compiles for a v5e at the cells' widths, with no copy of a pool around
it, is tests/test_chip_compile.py's to show."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import generation
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas import backend, paged_decode

HK, D, PAGES_PER_ROW, BLOCK_PAGES = 2, 128, 8, 4


def _plan_blocks_of(monkeypatch, pages, page_size, dtype=jnp.bfloat16):
    """Shrink the block budget so that a block holds ``pages`` pages:
    rows at these tiny widths then span several blocks."""
    page = 2 * HK * page_size * D * jnp.dtype(dtype).itemsize
    monkeypatch.setattr(paged_decode, "_BLOCK_BYTES", pages * page)
    assert paged_decode.pages_per_block(HK, page_size, D,
                                        jnp.dtype(dtype).itemsize,
                                        PAGES_PER_ROW) == pages


def _lengths(page_size):
    """Empty, one token, a page's edge, a block's edge and one past it, a
    row in its last block, a full row."""
    block = BLOCK_PAGES * page_size
    return [0, 1, page_size, block, block + 1, 2 * block - 3,
            PAGES_PER_ROW * page_size]


def _table(rows, permuted, seed=0):
    n = rows * PAGES_PER_ROW
    ids = (np.random.RandomState(seed).permutation(n) if permuted
           else np.arange(n))
    return jnp.asarray(ids.reshape(rows, PAGES_PER_ROW), jnp.int32)


def _inputs(g, page_size, rows, dtype, seed=0):
    rng = np.random.RandomState(seed)
    n = rows * PAGES_PER_ROW
    kp = jnp.asarray(rng.randn(HK, n, page_size, D), dtype)
    vp = jnp.asarray(rng.randn(HK, n, page_size, D), dtype)
    q = jnp.asarray(rng.randn(rows, HK * g, D), dtype)
    return q, kp, vp


@pytest.mark.parametrize("table", ["arange", "permuted"])
@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("g", [1, 4, 16])
def test_kernel_matches_the_reference(monkeypatch, g, page_size, table):
    _plan_blocks_of(monkeypatch, BLOCK_PAGES, page_size)
    lengths = _lengths(page_size)
    rows = len(lengths)
    q, kp, vp = _inputs(g, page_size, rows, jnp.bfloat16, seed=g)
    pages = _table(rows, table == "permuted")
    lengths = jnp.asarray(lengths, jnp.int32)
    got = paged_decode.paged_decode_attention(q, kp, vp, lengths, pages,
                                              interpret=True)
    want = generation._paged_attention_ref(q, kp, vp, lengths, pages)
    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # an empty row reads zeros (the reference's softmax of nothing is NaN)
    np.testing.assert_array_equal(got[0], 0.0)
    # bf16 outputs: within one rounding of the float32 reference's
    np.testing.assert_allclose(got[1:], want[1:], rtol=4e-3, atol=4e-3)


@pytest.mark.parametrize("g", [1, 16])
def test_float32_pools(monkeypatch, g):
    _plan_blocks_of(monkeypatch, BLOCK_PAGES, 16, jnp.float32)
    lengths = jnp.asarray(_lengths(16)[1:], jnp.int32)
    q, kp, vp = _inputs(g, 16, len(lengths), jnp.float32, seed=3)
    pages = _table(len(lengths), False)
    got = paged_decode.paged_decode_attention(q, kp, vp, lengths, pages,
                                              interpret=True)
    want = generation._paged_attention_ref(q, kp, vp, lengths, pages)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_p_stays_float32_through_pv():
    """P.V on the MXU takes P as three bf16 parts: the product is P's
    float32 one to float32 rounding, where P rounded to bf16 is off by
    its 8 bits."""
    rng = np.random.RandomState(5)
    p = jnp.asarray(rng.rand(16, 256), jnp.float32)
    v = jnp.asarray(rng.randn(256, D), jnp.bfloat16)
    v64 = np.asarray(v, np.float64)
    want = np.asarray(p, np.float64) @ v64
    err = np.abs(np.asarray(paged_decode._pv_mxu(p, v)) - want).max()
    rounded = np.asarray(p.astype(jnp.bfloat16), np.float64) @ v64
    assert err < 1e-4
    assert np.abs(rounded - want).max() > 100 * err


@pytest.fixture
def copies_moved(monkeypatch):
    """The pages each started copy moves, recorded as the kernel runs (a
    copy whose branch is not taken records nothing). The kernel call is
    jitted: its traces are dropped before and after, so that the spy is
    in this test's trace and in no other test's."""
    moved = []
    real = paged_decode.pltpu.make_async_copy

    class Spy:
        def __init__(self, src, dst, sem):
            self._copy = real(src, dst, sem)
            self._pages = src.shape[1]

        def start(self):
            jax.debug.callback(lambda n=self._pages: moved.append(n))
            self._copy.start()

        def wait(self):
            self._copy.wait()

    monkeypatch.setattr(paged_decode.pltpu, "make_async_copy", Spy)
    paged_decode._paged_decode.clear_cache()
    yield moved
    paged_decode._paged_decode.clear_cache()


def _copies_expected(lengths, page_size, consecutive):
    """Per pool (K, V): one copy per set bit of the pages a block holds
    where its pages are consecutive, else one copy a page."""
    out = []
    for n in lengths:
        held = -(-n // page_size)
        for first in range(0, held, BLOCK_PAGES):
            n_block = min(BLOCK_PAGES, held - first)
            pieces = ([1 << k for k in reversed(range(4)) if n_block >> k & 1]
                      if consecutive else [1] * n_block)
            out += pieces
    return sorted(out * 2)


@pytest.mark.parametrize("table", ["arange", "permuted"])
def test_copies_a_block_takes(monkeypatch, copies_moved, table):
    """On a consecutive table a whole block moves in ONE copy per pool, a
    last partial block in one copy per set bit of its page count; on a
    permuted one, one copy a page (over every head)."""
    _plan_blocks_of(monkeypatch, BLOCK_PAGES, 16)
    lengths = _lengths(16)
    q, kp, vp = _inputs(1, 16, len(lengths), jnp.bfloat16)
    out = paged_decode.paged_decode_attention(
        q, kp, vp, jnp.asarray(lengths, jnp.int32),
        _table(len(lengths), table == "permuted"), interpret=True)
    out.block_until_ready()
    assert sorted(copies_moved) == _copies_expected(lengths, 16,
                                                    table == "arange")


def test_engine_tables_take_one_copy_a_block(monkeypatch, copies_moved):
    """The page tables ContinuousBatchEngine hands the decode step: every
    block of every row moves in one copy per pool (the consecutive path),
    at the block plan the engine's own pool widths give."""
    from paddle_tpu.serving import ContinuousBatchEngine

    cfg = LlamaConfig(vocab_size=128, hidden_size=4 * D,
                      intermediate_size=512, num_hidden_layers=1,
                      num_attention_heads=4, num_key_value_heads=HK,
                      max_position_embeddings=256, dtype="bfloat16")
    eng = ContinuousBatchEngine(LlamaForCausalLM(cfg), max_batch=3,
                                max_len=PAGES_PER_ROW * 16, page_size=16)
    cache = eng._caches[0]
    table = cache["page_indices"]
    assert table.shape == (3, PAGES_PER_ROW)
    assert cache["k_pages"].shape == (HK, 3 * PAGES_PER_ROW, 16, D)
    _plan_blocks_of(monkeypatch, BLOCK_PAGES, 16)
    lengths = [BLOCK_PAGES * 16, PAGES_PER_ROW * 16, 16 * 5]
    q, _, _ = _inputs(2, 16, 3, jnp.bfloat16)
    paged_decode.paged_decode_attention(
        q, cache["k_pages"], cache["v_pages"],
        jnp.asarray(lengths, jnp.int32), table,
        interpret=True).block_until_ready()
    assert sorted(copies_moved) == _copies_expected(lengths, 16, True)
    assert copies_moved.count(BLOCK_PAGES) == 2 * 4


def test_gate_admits_whole_lanes_on_a_tpu_only():
    q = jnp.zeros((2, 4, D), jnp.bfloat16)
    pool = jnp.zeros((HK, 8, 16, D), jnp.bfloat16)
    narrow = jnp.zeros((HK, 8, 16, 64), jnp.bfloat16)
    backend.reset_paths()
    assert not paged_decode.supported(q, pool)          # the CPU, no ask
    with backend.lowering_target("tpu"):
        assert paged_decode.supported(q, pool)
        assert not paged_decode.supported(q[..., :64], narrow)
    assert backend.paths()["paged_attention"] == {backend.PALLAS: 1,
                                                  backend.XLA: 2}
    assert any("128 lanes" in r
               for r in backend.refusals()["paged_attention"])


def _admit_interpreted(monkeypatch):
    real = paged_decode.supported
    monkeypatch.setattr(paged_decode, "supported",
                        lambda q, k_pages, interpret=False:
                        real(q, k_pages, True))


def test_engine_tokens_and_logprobs_with_the_kernel(monkeypatch):
    """ContinuousBatchEngine on a tiny model (head width 128): greedy
    tokens with the kernel interpreted at every decode step equal those of
    the XLA reference, and every chosen-token logprob agrees to float32
    rounding."""
    from paddle_tpu.serving import ContinuousBatchEngine

    cfg = LlamaConfig(vocab_size=128, hidden_size=256, intermediate_size=512,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=256,
                      use_flash_attention=False, dtype="float32")

    def run():
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        eng = ContinuousBatchEngine(model, max_batch=4, max_len=64,
                                    page_size=16)
        rng = np.random.RandomState(1)
        backend.reset_paths()
        # six requests on four slots: ragged lengths across page edges,
        # slots reused, idle rows in the last steps
        rids = [eng.add_request(rng.randint(0, 128, (4 + 5 * i,)), 6 + i,
                                logprobs=True) for i in range(6)]
        done = eng.run_until_done()
        return ({r: done[r].tolist() for r in rids},
                {r: eng.logprobs(r) for r in rids},
                backend.paths().get("paged_attention", {}))

    ref_tokens, ref_logprobs, ref_paths = run()
    _admit_interpreted(monkeypatch)
    tokens, logprobs, paths = run()
    assert set(ref_paths) == {backend.XLA}
    assert set(paths) == {backend.INTERPRET}
    assert tokens == ref_tokens
    for r, want in ref_logprobs.items():
        assert len(want) > 0
        np.testing.assert_allclose(logprobs[r], want, rtol=0, atol=1e-5)
