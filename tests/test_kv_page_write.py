"""The decode step's page write (ops/pallas/kv_page_write) against the
XLA scatter it replaces on a TPU: the WHOLE pool, bit for bit, in
interpret mode on the CPU; the gate; and the engine end to end with the
gate admitted and refused. That the compiled program holds no copy of a
pool is tests/test_chip_compile.py's to show."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import generation
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas import backend, kv_page_write

HK, PAGE, D, B, PAGES_PER_ROW = 2, 16, 128, 4, 3
N_PAGES = B * PAGES_PER_ROW
MAX_LEN = PAGES_PER_ROW * PAGE


def _scatter(pages, rows, slot, new):
    return pages.at[:, rows, slot].set(
        jnp.moveaxis(new, 0, 1).astype(pages.dtype))


def _engine_rows(lengths, pages=()):
    """(rows, slot) as ``paged_cached_attention`` derives them from the
    engine's page table (every row owns PAGES_PER_ROW pages in order);
    ``pages`` overrides a row's page where it is not None."""
    page_indices = jnp.arange(N_PAGES, dtype=jnp.int32).reshape(
        B, PAGES_PER_ROW)
    lengths = jnp.asarray(lengths, jnp.int32)
    rows = page_indices[jnp.arange(B), lengths // PAGE]
    for b, page in enumerate(pages):
        if page is not None:
            rows = rows.at[b].set(page)
    return rows, lengths % PAGE


# name -> (lengths [B], pages overridden); a page outside the pool drops
# its row, a negative one counts from the end: the scatter's own reading
CASES = {
    "ragged_lengths": ([5, 17, 30, 44], ()),
    "first_and_last_row_of_a_page": ([0, 15, 16, 31], ()),
    "idle_rows_write_their_own_first_row": ([0, 0, 0, 0], ()),
    "last_position_of_a_row": ([MAX_LEN - 1, 3, 3, 3], ()),
    # lengths == MAX_LEN: the page table is read one past its end, which
    # jnp clamps, so the scatter lands in row 0 of the slot's LAST page
    "a_row_at_capacity": ([3, MAX_LEN, 20, 7], ()),
    "a_dropped_row_between_writers": ([5, 17, 30, 44],
                                      (None, N_PAGES + 3, None, None)),
    "the_first_row_dropped": ([5, 17, 30, 44],
                              (N_PAGES, None, -3 * N_PAGES, None)),
    "a_negative_page_counts_from_the_end": ([5, 17, 30, 44],
                                            (None, None, 8 - N_PAGES, None)),
    "every_row_dropped": ([5, 17, 30, 44], (N_PAGES + 1,) * B),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_page_write_equals_the_scatter_on_the_whole_pool(case, dtype):
    rows, slot = _engine_rows(*CASES[case])
    rng = np.random.RandomState(sorted(CASES).index(case))
    pool = jnp.asarray(rng.randn(HK, N_PAGES, PAGE, D), dtype)
    new = jnp.asarray(rng.randn(B, HK, D), jnp.float32)
    want = _scatter(pool, rows, slot, new)
    got = jax.jit(kv_page_write.kv_page_write)(pool, rows, slot, new)
    assert got.dtype == pool.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    changed = int((np.asarray(want) != np.asarray(pool)).any(-1).sum())
    if case == "every_row_dropped":
        assert changed == 0
    elif case == "a_row_at_capacity":
        # the row at capacity overwrote the first row of its last page
        assert changed == B * HK
        assert (np.asarray(got[:, 2 * PAGES_PER_ROW - 1, 0])
                == np.asarray(new[1].astype(dtype))).all()
    else:
        assert 0 < changed <= B * HK


def test_gate_takes_whole_lanes_on_a_tpu_and_nothing_elsewhere():
    pool = jnp.zeros((HK, N_PAGES, PAGE, D), jnp.bfloat16)
    new = jnp.zeros((B, HK, D), jnp.bfloat16)
    narrow = jnp.zeros((HK, N_PAGES, PAGE, 64), jnp.bfloat16)
    backend.reset_paths()
    assert not kv_page_write.supported(pool, new)       # the CPU, no ask
    assert kv_page_write.supported(pool, new, interpret=True)
    with backend.lowering_target("tpu"):
        assert kv_page_write.supported(pool, new)
        assert kv_page_write.supported(pool.astype(jnp.float32), new)
        assert not kv_page_write.supported(narrow, new[..., :64])
        with backend.composites():
            assert not kv_page_write.supported(pool, new)
    assert backend.paths()["kv_page_write"] == {
        backend.PALLAS: 2, backend.INTERPRET: 1, backend.XLA: 3}
    assert any("128 lanes" in r
               for r in backend.refusals()["kv_page_write"])


def _admit_interpreted(monkeypatch):
    real = kv_page_write.supported
    monkeypatch.setattr(
        kv_page_write, "supported",
        lambda pages, new, interpret=False: real(pages, new, True))


def test_decode_step_takes_the_kernel_where_the_gate_admits(monkeypatch):
    """``paged_cached_attention`` at S == 1: same output and same pools
    through the kernel as through the scatter; the S > 1 verify chunk
    stays on the scatter."""
    rng = np.random.RandomState(7)
    H = 4
    lengths = jnp.asarray([5, 16, 31, 0], jnp.int32)
    page_indices = jnp.arange(N_PAGES, dtype=jnp.int32).reshape(
        B, PAGES_PER_ROW)
    kp = jnp.asarray(rng.randn(HK, N_PAGES, PAGE, D), jnp.float32)
    vp = jnp.asarray(rng.randn(HK, N_PAGES, PAGE, D), jnp.float32)
    cos = jnp.asarray(rng.randn(MAX_LEN, D), jnp.float32)
    sin = jnp.asarray(rng.randn(MAX_LEN, D), jnp.float32)

    def run(S):
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
        args = (q, k, v, cos, sin, kp, vp, page_indices, lengths, PAGE)
        backend.reset_paths()
        ref = generation.paged_cached_attention(*args)
        ref_paths = backend.paths().get("kv_page_write", {})
        with monkeypatch.context() as m:
            _admit_interpreted(m)
            backend.reset_paths()
            got = generation.paged_cached_attention(*args)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return ref_paths, backend.paths().get("kv_page_write", {})

    assert run(1) == ({backend.XLA: 2}, {backend.INTERPRET: 2})
    assert run(3) == ({}, {})


def test_engine_tokens_and_logprobs_identical_with_the_kernel(monkeypatch):
    """ContinuousBatchEngine on a tiny model (head width 128, the width
    the gate admits): greedy tokens AND chosen-token logprobs with the
    page write interpreted equal those with the scatter."""
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
        # six requests on four slots: ragged lengths, slots reused, idle
        # rows in the last steps
        rids = [eng.add_request(rng.randint(0, 128, (4 + 3 * i,)), 6 + i,
                                logprobs=True) for i in range(6)]
        done = eng.run_until_done()
        return ({r: done[r].tolist() for r in rids},
                {r: eng.logprobs(r) for r in rids},
                backend.paths().get("kv_page_write", {}))

    ref_tokens, ref_logprobs, ref_paths = run()
    _admit_interpreted(monkeypatch)
    tokens, logprobs, paths = run()
    assert set(ref_paths) == {backend.XLA}
    assert set(paths) == {backend.INTERPRET}
    assert tokens == ref_tokens
    assert all(len(v) > 0 for v in logprobs.values())
    assert logprobs == ref_logprobs
