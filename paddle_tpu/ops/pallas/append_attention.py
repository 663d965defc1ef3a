"""Pallas append attention: a CHUNK of queries against a long dense KV
buffer with a causal offset — the serving fast path for chunked prefill
(generation._ChunkedPrefillStep), multi-token cache appends, and the
speculative-decode verify chunk.

Role anchor: the multi-token branch of the reference's
block_multi_head_attention serving kernel family
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu);
the single-token case rides ``paged_decode`` and the
pos=0 full prefill rides splash flash — this kernel covers the middle:
0 < pos, S > 1.

Kernel shape (per (batch, kv_head) grid cell):
- q block [S, g, D] (g = query heads per KV head, GQA in-kernel like the
  splash path — KV moves through VMEM once per group, not per Q head);
- whole-buffer k/v [T, D] resident in VMEM (gate caps T·D·dtype at a VMEM
  budget; beyond that the caller falls back to the dense XLA path);
- fori over T blocks with streaming softmax (running max / sum / acc in
  f32), masking columns  t > pos + s  (and an optional [T] column-validity
  mask for ragged prompts); blocks entirely beyond pos+S are skipped via
  @pl.when, so compute scales with the VALID prefix, not the buffer.

``pos`` arrives as a scalar-prefetch operand so the same compiled kernel
serves every chunk position (it is a traced value inside scans).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend


_VMEM_BUDGET = 10 * 1024 * 1024  # bytes for k+v residency per grid cell


def _shape_refusal(q, k_buf):
    if q.ndim != 4 or k_buf.ndim != 4:
        return "q and the KV buffer must be 4-D"
    B, S, H, D = q.shape
    T, hk = k_buf.shape[1], k_buf.shape[2]
    if D % 128 != 0 or T % 128 != 0:
        return f"head_dim {D} / buffer length {T} is not a multiple of 128"
    if H % hk != 0:
        return f"{H} query heads do not group over {hk} KV heads"
    g = H // hk
    if (g * S) % 8 != 0:  # f32 sublane tile for the scores block
        return f"{g} x {S} score rows are not a sublane multiple"
    kv_bytes = 2 * T * D * jnp.dtype(k_buf.dtype).itemsize
    if kv_bytes > _VMEM_BUDGET:
        return f"one head's KV ({kv_bytes} B) exceeds the VMEM budget"
    # streaming block: [g*S, bkv] f32 scores must stay modest
    if g * S > 2048:
        return f"{g} x {S} score rows exceed 2048"
    return None


def supported(q, k_buf, interpret: bool = False) -> bool:
    """Gate: TPU (or interpret-mode test), MXU-tileable dims, whole-buffer
    KV fits the VMEM budget, and GQA groups divide evenly."""
    return backend.gate("append_attention", _shape_refusal(q, k_buf),
                        interpret)


def _kernel(pos_ref, q_ref, k_ref, v_ref, allowed_ref, o_ref, *,
            S, g, D, T, bkv, scale, have_allowed):
    q = q_ref[0, :, 0].astype(jnp.float32)     # [S, g, D]
    qf = q.transpose(1, 0, 2).reshape(g * S, D) * scale
    pos = pos_ref[0]
    # row r of qf is query position  s = r % S  (group-major layout)
    row_s = jax.lax.broadcasted_iota(jnp.int32, (g * S, 1), 0) % S
    limit = pos + row_s                        # [gS, 1] last visible column
    nb = T // bkv

    def body(i, carry):
        m, l, acc = carry

        def compute(carry):
            m, l, acc = carry
            kblk = k_ref[0, pl.ds(i * bkv, bkv), :].astype(jnp.float32)
            vblk = v_ref[0, pl.ds(i * bkv, bkv), :].astype(jnp.float32)
            s_blk = qf @ kblk.T                # [gS, bkv]
            col = (i * bkv
                   + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1))
            mask = col <= limit
            if have_allowed:
                ab = allowed_ref[0, :, pl.ds(i * bkv, bkv)]   # [1, bkv]
                mask = mask & (ab != 0)
            s_blk = jnp.where(mask, s_blk, -1e30)
            m_new = jnp.maximum(m, s_blk.max(axis=1, keepdims=True))
            p = jnp.exp(s_blk - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=1, keepdims=True)
            acc = acc * alpha + p @ vblk
            return m_new, l, acc

        # skip blocks fully beyond the last valid column (pos + S - 1)
        return jax.lax.cond(i * bkv <= pos + S - 1, compute,
                            lambda c: c, carry)

    m0 = jnp.full((g * S, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((g * S, 1), jnp.float32)
    a0 = jnp.zeros((g * S, D), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)          # [gS, D]
    o_ref[0, :, 0] = out.reshape(g, S, D).transpose(1, 0, 2).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _append_jit(q, k_buf, v_buf, pos, allowed, interpret):
    B, S, H, D = q.shape
    T, hk = k_buf.shape[1], k_buf.shape[2]
    g = H // hk
    bkv = next(b for b in (512, 256, 128) if T % b == 0)
    scale = 1.0 / math.sqrt(D)
    have_allowed = allowed is not None
    # [B, 1, T]: a (1, T) block over [B, T] would put a block of 1 on a
    # second-to-last dim of B, which Mosaic refuses
    allowed = (jnp.ones((B, 1, T), jnp.int32) if not have_allowed
               else allowed.astype(jnp.int32).reshape(B, 1, T))
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    q5 = q.reshape(B, S, hk, g, D)
    # heads folded into the lane dim: one KV head is then a (T, D) block
    # whose last two dims tile, where (1, T, 1, D) over [B, T, hk, D]
    # put a block of 1 on the hk dim
    k3 = k_buf.reshape(B, T, hk * D)
    v3 = v_buf.reshape(B, T, hk * D)

    kern = functools.partial(
        _kernel, S=S, g=g, D=D, T=T, bkv=bkv, scale=scale,
        have_allowed=have_allowed)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, hk),
            in_specs=[
                pl.BlockSpec((1, S, 1, g, D),
                             lambda b, k, pos: (b, 0, k, 0, 0)),
                pl.BlockSpec((1, T, D), lambda b, k, pos: (b, 0, k)),
                pl.BlockSpec((1, T, D), lambda b, k, pos: (b, 0, k)),
                pl.BlockSpec((1, 1, T), lambda b, k, pos: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, S, 1, g, D),
                                   lambda b, k, pos: (b, 0, k, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, hk, g, D), q.dtype),
        interpret=interpret,
        name="append_attention",
    )(pos_arr, q5, k3, v3, allowed)
    return out.reshape(B, S, H, D)


def append_attention(q, k_buf, v_buf, pos, allowed=None, interpret=False):
    """q [B,S,H,D] (already RoPE'd), k_buf/v_buf [B,T,hk,D] (chunk already
    written at ``pos``), pos scalar, allowed optional [B,T] column mask.
    Returns [B,S,H,D] — same math as generation.cached_attention's dense
    branch. ``interpret`` is honoured only off-TPU."""
    return _append_jit(q, k_buf, v_buf, pos, allowed,
                       interpret and backend.interpret_mode())
