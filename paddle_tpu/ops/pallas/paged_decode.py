"""Pallas paged decode attention (one query token a row) over the
engine's page pools ``[hk, n_pages, page_size, D]``.

Role anchor: the attention half of the reference's
block_multi_head_attention serving kernel
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu);
``kv_page_write`` is its cache-write half.

Why not JAX's bundled ``paged_attention``: its grid is (row, KV head) and
it moves one head's page per copy (16 x 128 bf16 = 4 KB), so a row of
~500 tokens at 30 KV heads costs ~1000 copies and 30 grid steps; it then
multiplies float32 K and V on the MXU. On a v5e it read ~12% of its
bytes roofline in the serving cells (PERF.md, S12). Here:

- **Grid over rows**, one step a row over ALL its KV heads (the loop over
  heads unrolled); an inner loop over the blocks the row holds
  (``ceil(len / block)``), double-buffered across blocks and into the
  next row's first block.
- **Copies:** a block is ``pages_per_block`` pages of every head. Where
  the block's page ids are consecutive (the engine's tables are
  ``arange``, so always today) its pages move in ONE copy per pool
  (``[hk, n, page_size, D]``, up to 2 MiB), or for a row's last, partial
  block in one copy per set bit of the pages it holds; otherwise one copy
  a page over every head. The scalar core checks the ids from the
  prefetched table, so any page table is read correctly. No copy reaches
  past the pages a row holds.
- **Block plan from shapes:** the largest power of two of pages, at most
  a row's table, whose K and V over every head fit ``_BLOCK_BYTES``
  (8 pages at 30 heads of 128, 32 at 8 heads: bf16, page 16).
- **Contraction:** q.k^T from the pool's own dtype with float32
  accumulation (products of bf16 values are exact), the softmax scale
  1/sqrt(D) applied in float32 to the scores, an online softmax in
  float32, and P kept float32 through P.V. From ``_MXU_PV_GROUP`` query
  heads a KV head both products run on the MXU, P.V as one product of
  P's three bf16 parts stacked (``hi + mid + lo`` holds P's 24 bits; V is
  bf16, so every product is exact); below it the scores come out as a
  column per query head and P.V is a broadcast-multiply-reduce on the
  VPU in float32.

q enters in the pool's dtype (the engine's are the model's own); the
output is in q's dtype; a row of length 0 reads zeros. Off the chip the
caller runs its XLA reference; tests run this kernel interpreted.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

SITE = "paged_attention"
#: K + V of one block over every KV head, double-buffered in VMEM
_BLOCK_BYTES = 2 << 20
#: from this many query heads a KV head, P.V runs on the MXU (the VPU's
#: form was faster at 1, the MXU's at 4 and 16 on one v5e chip: PERF.md)
_MXU_PV_GROUP = 3
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _dot(a, b, dims):
    """A float32-accumulated product: one MXU pass for bf16 operands
    (their products are exact), full f32 precision for a float32 pool's.
    Stated here, so that a caller's default matmul precision (Mosaic
    refuses an f32 contraction of bf16 operands) does not reach it."""
    exact = (lax.Precision.HIGHEST if b.dtype == jnp.float32
             else lax.Precision.DEFAULT)
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32,
                           precision=exact)


def pages_per_block(hk, page_size, d, itemsize, pages_per_row):
    """The block plan: a power of two of pages, at most ``pages_per_row``,
    whose K and V over all ``hk`` heads stay within ``_BLOCK_BYTES``."""
    page = 2 * hk * page_size * d * itemsize
    bp = 1
    while 2 * bp * page <= _BLOCK_BYTES and 2 * bp <= pages_per_row:
        bp *= 2
    return bp


def _shape_refusal(q, k_pages):
    hk, _n, page_size, d = k_pages.shape
    if d % 128 != 0:
        return f"head width {d} is not a multiple of 128 lanes"
    if q.shape[1] % hk != 0:
        return f"{q.shape[1]} query heads are not a multiple of {hk}"
    if 2 * hk * page_size * d * k_pages.dtype.itemsize > _BLOCK_BYTES:
        return f"one page over {hk} heads exceeds {_BLOCK_BYTES} bytes"
    return None


def supported(q, k_pages, interpret: bool = False) -> bool:
    """Gate: a TPU (``interpret`` admits the interpreter off-TPU: tests), a
    program GSPMD will not partition, whole-lane heads."""
    return backend.gate(SITE, _shape_refusal(q, k_pages), interpret)


def _pv_mxu(p, v):
    """P [g, t] float32 times V [t, D] with P's full precision: for a bf16
    V, one bf16 product of P's three bf16 parts stacked."""
    g = p.shape[0]
    if v.dtype == jnp.float32:
        return _dot(p, v, _NN)
    hi = p.astype(v.dtype)
    rest = p - hi.astype(jnp.float32)
    mid = rest.astype(v.dtype)
    lo = (rest - mid.astype(jnp.float32)).astype(v.dtype)
    pv = _dot(jnp.concatenate([hi, mid, lo], 0), v, _NN)
    return pv[:g] + pv[g:2 * g] + pv[2 * g:]


def _make_kernel(B, hk, g, d, page_size, pps, bp, scale):
    f32 = jnp.float32
    bk = bp * page_size
    rows_form = g >= _MXU_PV_GROUP

    def kernel(len_ref, pages_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
               sems, m_ref, l_ref, acc_ref, st_ref):
        # st_ref: [0] the slot the row's first block lands in, [1] whether
        # the previous row already started that copy, [2 + slot] whether
        # the block in ``slot`` moved in consecutive-page copies
        b = pl.program_id(0)

        def held(r, i):                  # pages of row r's block i
            return jnp.minimum(bp, pl.cdiv(len_ref[r], page_size) - i * bp)

        def each_copy(r, i, slot, consecutive, act):
            base = r * pps + i * bp
            n = held(r, i)
            for k in reversed(range(bp.bit_length())):
                size = 1 << k
                off = n & -(2 * size)    # the set bits of n above this one

                @pl.when(consecutive & ((n & size) != 0))
                def _():
                    first = pages_ref[base] + off
                    for pool, buf, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                        act(pltpu.make_async_copy(
                            pool.at[:, pl.ds(first, size)],
                            buf.at[slot, :, pl.ds(off, size)],
                            sems.at[s, slot]))

            def one_page(j, carry):
                page = pages_ref[base + j]
                for pool, buf, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                    act(pltpu.make_async_copy(
                        pool.at[:, pl.ds(page, 1)],
                        buf.at[slot, :, pl.ds(j, 1)], sems.at[s, slot]))
                return carry

            lax.fori_loop(0, jnp.where(consecutive, 0, n), one_page, 0)

        def start(r, i, slot):
            base = r * pps + i * bp
            first = pages_ref[base]
            consecutive = lax.fori_loop(
                1, held(r, i),
                lambda j, ok: ok & (pages_ref[base + j] == first + j), True)
            st_ref[2 + slot] = consecutive.astype(jnp.int32)
            each_copy(r, i, slot, consecutive, lambda c: c.start())

        def wait(r, i, slot):
            each_copy(r, i, slot, st_ref[2 + slot] == 1, lambda c: c.wait())

        @pl.when(b == 0)
        def _():
            st_ref[0] = 0
            st_ref[1] = 0
            # a partial block leaves the rest of its slot as it was: keep
            # that finite, since P.V multiplies it by zero
            kbuf[...] = jnp.zeros_like(kbuf)
            vbuf[...] = jnp.zeros_like(vbuf)

        length = len_ref[b]
        nblk = pl.cdiv(length, bk)
        slot0 = st_ref[0]
        nxt = jnp.minimum(b + 1, B - 1)
        next_row = (b + 1 < B) & (len_ref[nxt] > 0)

        @pl.when((nblk > 0) & (st_ref[1] == 0))
        def _():
            start(b, 0, slot0)

        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def head_rows(h, slot, valid):
            # scores [g, t]; P.V on the MXU
            k = kbuf[slot, h].reshape(bk, d)
            v = vbuf[slot, h].reshape(bk, d)
            s = _dot(q_ref[0, h], k, _NT) * scale
            t = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(t < valid, s, -jnp.inf)
            m_prev = m_ref[h]                                # [g, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + _pv_mxu(p, v)
            m_ref[h] = m_new

        def head_cols(h, slot, valid):
            # scores [t, g]; P.V on the VPU in float32
            k = kbuf[slot, h].reshape(bk, d)
            v = vbuf[slot, h].reshape(bk, d).astype(f32)
            q = q_ref[0, h]                                  # [g, d]
            if g == 1:   # Mosaic takes a one-column product to the VPU
                s = jnp.sum(k.astype(f32) * q.astype(f32), axis=1,
                            keepdims=True)
            else:
                s = _dot(k, q, _NT)
            s = s * scale
            t = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(t < valid, s, -jnp.inf)            # [bk, g]
            m_prev = m_ref[h]                                # [1, g]
            m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=0, keepdims=True)
            for j in range(g):
                pv = jnp.sum(p[:, j:j + 1] * v, axis=0, keepdims=True)
                acc_ref[h, j:j + 1, :] = (alpha[:, j:j + 1]
                                          * acc_ref[h, j:j + 1, :] + pv)
            m_ref[h] = m_new

        head = head_rows if rows_form else head_cols

        def block(i, carry):
            slot = (slot0 + i) % 2

            @pl.when(i + 1 < nblk)
            def _():
                start(b, i + 1, 1 - slot)

            @pl.when((i + 1 == nblk) & next_row)
            def _():
                start(nxt, 0, 1 - slot)

            wait(b, i, slot)
            valid = length - i * bk
            for h in range(hk):
                head(h, slot, valid)
            return carry

        lax.fori_loop(0, nblk, block, 0)
        st_ref[0] = (slot0 + nblk) % 2
        st_ref[1] = ((nblk > 0) & next_row).astype(jnp.int32)
        if rows_form:
            l = l_ref[...]
            o_ref[0] = jnp.where(l > 0, acc_ref[...] / l, 0.0).astype(
                o_ref.dtype)
        else:
            for j in range(g):
                l = l_ref[:, :, j:j + 1]                     # [hk, 1, 1]
                o_ref[0, :, j:j + 1, :] = jnp.where(
                    l > 0, acc_ref[:, j:j + 1, :] / l, 0.0).astype(
                        o_ref.dtype)

    return kernel, rows_form


def paged_decode_attention(q, k_pages, v_pages, lengths, page_indices,
                           interpret: bool = False):
    """softmax(q k^T / sqrt(D)) v over each row's first ``lengths[b]``
    cached positions. q [B, H, D]; k_pages/v_pages [hk, n_pages,
    page_size, D]; lengths [B] int32; page_indices [B, pages_per_row]
    (row b's position p lives in page ``page_indices[b, p // page_size]``).
    Returns [B, H, D] in q's dtype."""
    hk, _n, page_size, d = k_pages.shape
    bp = pages_per_block(hk, page_size, d, k_pages.dtype.itemsize,
                         page_indices.shape[1])
    return _paged_decode(q, k_pages, v_pages, lengths, page_indices, bp=bp,
                         interpret=bool(interpret) or backend.interpret_mode())


# jitted so that a program's layers share one lowering of the kernel (each
# call lowered on its own cost the chip's host ~1.5 s of set-up a layer)
@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def _paged_decode(q, k_pages, v_pages, lengths, page_indices, bp, interpret):
    B, H, d = q.shape
    hk, _n, page_size, _ = k_pages.shape
    g = H // hk
    pps = page_indices.shape[1]
    kernel, rows_form = _make_kernel(B, hk, g, d, page_size, pps, bp,
                                     1.0 / math.sqrt(d))
    stat = (hk, g, 1) if rows_form else (hk, 1, g)
    row = pl.BlockSpec((1, hk, g, d), lambda b, *_: (b, 0, 0, 0))
    kv_block = 2 * hk * bp * page_size * d * k_pages.dtype.itemsize
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, hk, bp, page_size, d), k_pages.dtype),
                pltpu.VMEM((2, hk, bp, page_size, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM(stat, jnp.float32), pltpu.VMEM(stat, jnp.float32),
                pltpu.VMEM((hk, g, d), jnp.float32),
                pltpu.SMEM((4,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, hk, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * kv_block + (16 << 20)),
        interpret=interpret,
        name=SITE,
    )(jnp.clip(jnp.asarray(lengths, jnp.int32), 0, pps * page_size),
      page_indices.reshape(-1),
      q.reshape(B, hk, g, d).astype(k_pages.dtype), k_pages, v_pages)
    return out.reshape(B, H, d)
