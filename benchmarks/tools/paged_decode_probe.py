#!/usr/bin/env python
"""Scratch probe, not the harness: the paged decode attention (S == 1)
at the shapes three serving cells give it, to say where its time goes.

Shapes (the engine's pools ``[hk, n_pages, 16, 128]`` bf16, ``arange``
page tables, lengths drawn around each cell's mean context):

- ``hybrid``: 32 rows, 30 KV heads, 1 query head each, 128 pages a row,
  ~510 tokens (``olmohybrid_chat_batch``'s full layers);
- ``docs``: 16 rows, 8 KV heads, 4 query heads each, ~1300 tokens
  (``mistral7b_docs_batch``);
- ``rag``: 32 rows, 8 KV heads, 16 query heads each, 512 pages a row,
  ~4500 tokens (``commandaplus_rag_batch``'s global layer).

Variants, each timed on the host clock around ``block_until_ready`` (a
burst of calls, best of several bursts, one process):

- ``bundled@N``: JAX's bundled ``paged_attention`` as the engine called
  it (``pages_per_compute_block`` N, q pre-scaled);
- cut-down copies of ONE kernel body whose grid is over rows, one copy a
  block of pages over every KV head: ``copies`` (the copies alone, no
  contraction), ``math-rows`` / ``math-cols`` (the contraction alone over
  whatever VMEM holds, no copies) and ``full-rows`` / ``full-cols``
  (both; ``-unroll``: the loop over heads unrolled). ``rows``: scores
  ``[g, t]``, P.V on the MXU with P split into three bf16 parts (P stays
  float32); ``cols``: scores ``[t, g]``, P.V a broadcast-multiply-reduce
  on the VPU in float32. Both take q.k^T from bf16 operands with float32
  accumulation, on the MXU (``cols`` at one query head a KV head on the
  VPU);
- ``kernel``: ``ops/pallas/paged_decode.py`` as the engine calls it;
- ``full-rows-permuted``, ``kernel-permuted``: over a permuted page table
  (one copy a page over every head).

One JSON line a variant (``ms`` a call, ``kv_gb_per_s`` the K/V the rows
hold over that time, ``max_abs_err`` against the float32 XLA reference).

    python benchmarks/tools/paged_decode_probe.py   # on one TPU chip
"""
from __future__ import annotations

import functools
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SHAPES = {
    # name: (rows, kv heads, q heads a kv head, pages a row, mean tokens)
    "hybrid": (32, 30, 1, 128, 510),
    "docs": (16, 8, 4, 128, 1300),
    "rag": (32, 8, 16, 512, 4500),
}
PAGE, D = 16, 128
BLOCK_BYTES = 2 << 20


def pages_per_block(hk, page_bytes, pages_per_row):
    """Pages a block, a power of two: K + V of every head within
    ``BLOCK_BYTES``."""
    bp = 1
    while 2 * bp * 2 * hk * page_bytes <= BLOCK_BYTES and 2 * bp <= \
            pages_per_row:
        bp *= 2
    return bp


def build(B, hk, g, pps, bp, form, copies=True, math_=True, unroll=1,
          interpret=False):
    """One kernel body, cut down by ``copies`` / ``math_``; ``unroll``
    heads a step of the loop over heads."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bk = bp * PAGE
    scale = 1.0 / math.sqrt(D)
    nt = (((1,), (1,)), ((), ()))
    bits = bp.bit_length()

    def kernel(len_ref, pages_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
               sems, m_ref, l_ref, acc_ref, st_ref):
        b = pl.program_id(0)

        def held(r, i):
            return jnp.minimum(bp, pl.cdiv(len_ref[r], PAGE) - i * bp)

        def each_copy(r, i, slot, contig, act):
            base = r * pps + i * bp
            n = held(r, i)
            for k in reversed(range(bits)):
                size = 1 << k
                off = n & -(2 * size)

                @pl.when(contig & ((n & size) != 0))
                def _():
                    first = pages_ref[base] + off
                    for src, dst, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                        act(pltpu.make_async_copy(
                            src.at[:, pl.ds(first, size)],
                            dst.at[slot, :, pl.ds(off, size)],
                            sems.at[s, slot]))
            for j in range(bp):
                @pl.when(jnp.logical_not(contig) & (j < n))
                def _():
                    page = pages_ref[base + j]
                    for src, dst, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                        act(pltpu.make_async_copy(
                            src.at[:, pl.ds(page, 1)],
                            dst.at[slot, :, pl.ds(j, 1)],
                            sems.at[s, slot]))

        def start(r, i, slot):
            base = r * pps + i * bp
            first = pages_ref[base]
            contig = lax.fori_loop(
                1, held(r, i),
                lambda j, ok: ok & (pages_ref[base + j] == first + j), True)
            st_ref[2 + slot] = contig.astype(jnp.int32)
            each_copy(r, i, slot, contig, lambda c: c.start())

        def wait(r, i, slot):
            each_copy(r, i, slot, st_ref[2 + slot] == 1, lambda c: c.wait())

        @pl.when(b == 0)
        def _():
            st_ref[0] = 0
            st_ref[1] = 0
            kbuf[...] = jnp.zeros_like(kbuf)
            vbuf[...] = jnp.zeros_like(vbuf)

        length = len_ref[b]
        nblk = pl.cdiv(length, bk)
        slot0 = st_ref[0]
        nxt = jnp.minimum(b + 1, B - 1)
        next_row = (b + 1 < B) & (len_ref[nxt] > 0)
        if copies:
            @pl.when((nblk > 0) & (st_ref[1] == 0))
            def _():
                start(b, 0, slot0)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def head_rows(h, slot, valid):
            q = q_ref[0, h]                                  # [g, D]
            k = kbuf[slot, h].reshape(bk, D)
            v = vbuf[slot, h].reshape(bk, D)
            s = lax.dot_general(q, k, nt, preferred_element_type=f32) * scale
            t = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(t < valid, s, -jnp.inf)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=1, keepdims=True)
            p1 = p.astype(v.dtype)
            r1 = p - p1.astype(f32)
            p2 = r1.astype(v.dtype)
            p3 = (r1 - p2.astype(f32)).astype(v.dtype)
            pv = lax.dot_general(jnp.concatenate([p1, p2, p3], 0), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=f32)
            acc_ref[h] = alpha * acc_ref[h] + (pv[:g] + pv[g:2 * g]
                                               + pv[2 * g:])
            m_ref[h] = m_new

        def head_cols(h, slot, valid):
            q = q_ref[0, h]                                  # [g, D]
            k = kbuf[slot, h].reshape(bk, D)
            v = vbuf[slot, h].reshape(bk, D).astype(f32)
            if g == 1:    # Mosaic takes a one-column product to the VPU
                s = jnp.sum(k.astype(f32) * q.astype(f32), axis=1,
                            keepdims=True) * scale
            else:
                s = lax.dot_general(k, q, nt,
                                    preferred_element_type=f32) * scale
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

        head = head_rows if form == "rows" else head_cols

        def block(i, carry):
            slot = (slot0 + i) % 2
            if copies:
                @pl.when(i + 1 < nblk)
                def _():
                    start(b, i + 1, 1 - slot)

                @pl.when((i + 1 == nblk) & next_row)
                def _():
                    start(nxt, 0, 1 - slot)

                wait(b, i, slot)
            if math_:
                valid = length - i * bk
                lax.fori_loop(0, hk, lambda h, c: (head(h, slot, valid), c)[1],
                              0, unroll=unroll)
            return carry

        lax.fori_loop(0, nblk, block, 0)
        st_ref[0] = (slot0 + nblk) % 2
        st_ref[1] = ((nblk > 0) & next_row).astype(jnp.int32)
        if form == "rows":
            l = l_ref[...]
            o_ref[0] = jnp.where(l > 0, acc_ref[...] / l, 0.0).astype(
                o_ref.dtype)
        else:
            for j in range(g):
                l = l_ref[:, :, j:j + 1]                     # [hk, 1, 1]
                o_ref[0, :, j:j + 1, :] = jnp.where(
                    l > 0, acc_ref[:, j:j + 1, :] / l, 0.0).astype(
                        o_ref.dtype)

    def call(q, k_pages, v_pages, lengths, page_indices):
        stat = (hk, g, 1) if form == "rows" else (hk, 1, g)
        blk = pl.BlockSpec((1, hk, g, D), lambda b, *_: (b, 0, 0, 0))
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(B,),
                in_specs=[blk, pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=blk,
                scratch_shapes=[
                    pltpu.VMEM((2, hk, bp, PAGE, D), k_pages.dtype),
                    pltpu.VMEM((2, hk, bp, PAGE, D), v_pages.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM(stat, f32), pltpu.VMEM(stat, f32),
                    pltpu.VMEM((hk, g, D), f32),
                    pltpu.SMEM((4,), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((B, hk, g, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=48 << 20),
            interpret=interpret,
            name="paged_attention_probe",
        )(lengths, page_indices.reshape(-1),
          q.reshape(B, hk, g, D).astype(k_pages.dtype), k_pages, v_pages)
        return out.reshape(B, hk * g, D)

    return call


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention)

    from paddle_tpu.generation import _paged_attention_ref
    from paddle_tpu.ops.pallas import paged_decode

    out_path = os.path.join(ROOT, "chiprun_out", "paged_decode_probe.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def say(**fields):
        line = json.dumps(fields)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    say(device=str(jax.devices()[0].device_kind))
    rng = np.random.RandomState(0)
    for name, (B, hk, g, pps, mean) in SHAPES.items():
        n_pages = B * pps
        key = jax.random.PRNGKey(1)
        kp = jax.random.normal(key, (hk, n_pages, PAGE, D), jnp.bfloat16)
        vp = jax.random.normal(jax.random.fold_in(key, 1),
                               (hk, n_pages, PAGE, D), jnp.bfloat16)
        q = jax.random.normal(jax.random.fold_in(key, 2), (B, hk * g, D),
                              jnp.bfloat16)
        lengths = jnp.asarray(rng.randint(mean // 2, 3 * mean // 2, B),
                              jnp.int32)
        table = jnp.arange(n_pages, dtype=jnp.int32).reshape(B, pps)
        perm = jnp.asarray(rng.permutation(n_pages).reshape(B, pps),
                           jnp.int32)
        kv_bytes = float(jnp.sum(lengths)) * hk * D * 2 * 2
        want = jax.jit(_paged_attention_ref)(q, kp, vp, lengths, table)
        want = np.asarray(want.astype(jnp.float32))
        bp = pages_per_block(hk, PAGE * D * 2, pps)
        scale = jnp.asarray(1.0 / math.sqrt(D), q.dtype)
        variants = [(f"bundled@{n}", functools.partial(
            lambda q_, *a, n: paged_attention(q_ * scale, *a,
                                              pages_per_compute_block=n),
            n=n)) for n in (8, 16, 32)]
        for label, kw in (("copies", dict(form="rows", math_=False)),
                          ("math-rows", dict(form="rows", copies=False)),
                          ("math-cols", dict(form="cols", copies=False)),
                          ("full-rows", dict(form="rows")),
                          ("full-cols", dict(form="cols")),
                          ("full-rows-unroll", dict(form="rows", unroll=hk)),
                          ("full-cols-unroll", dict(form="cols", unroll=hk))):
            variants.append((label, build(B, hk, g, pps, bp, **kw)))
        variants.append(("kernel", paged_decode.paged_decode_attention))
        for label, fn in variants + [
                ("full-rows-permuted", build(B, hk, g, pps, bp, "rows")),
                ("kernel-permuted", paged_decode.paged_decode_attention)]:
            pages = perm if label.endswith("permuted") else table
            args = (q, kp, vp, lengths, pages)
            try:
                jitted = jax.jit(fn)
                t0 = time.time()
                out = jitted(*args)
                out.block_until_ready()
                compile_s = time.time() - t0
                best = 1e9
                for _ in range(5):
                    t0 = time.time()
                    for _ in range(10):
                        out = jitted(*args)
                    out.block_until_ready()
                    best = min(best, (time.time() - t0) / 10)
                got = np.asarray(out.astype(jnp.float32))
                if label.endswith("permuted"):
                    ref = np.asarray(jax.jit(_paged_attention_ref)(
                        *args).astype(jnp.float32))
                else:
                    ref = want
                say(shape=name, variant=label, pages_per_block=bp,
                    ms=round(best * 1e3, 4),
                    kv_gb_per_s=round(kv_bytes / best / 1e9, 1),
                    compile_s=round(compile_s, 2),
                    max_abs_err=float(np.abs(got - ref).max()))
            except Exception as e:  # a variant the compiler refuses
                say(shape=name, variant=label, error=repr(e)[:400])
        del kp, vp
    return 0


if __name__ == "__main__":
    sys.exit(main())
