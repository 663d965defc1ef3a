"""Pallas MLA decode attention: one token per row against the COMPRESSED
latent cache (DeepSeek multi-head latent attention, models/deepseek.py).

Role anchor: the single-token decode branch of the reference's
block_multi_head_attention serving kernel family
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu) for
the MLA cache layout this build adds; the GQA layout rides JAX's bundled
paged_attention kernel instead.

Why a kernel: the absorbed decode step reads the latent buffer TWICE in
the XLA einsum formulation — once for scores (``q_lat · c_kv``) and once
for the context read-back (``probs · c_kv``) — and decode is
HBM-bandwidth-bound. This kernel streams each ``c_kv`` block through VMEM
ONCE, computing scores and accumulating the context from the same load
with a flash-style running softmax: the latent cache's bytes/token
advantage (576 vs 2048 floats) arrives at full effect.

Kernel shape (per batch-row grid cell):
- q_lat [H, r] (q_nope pre-absorbed through W_uk and PRE-SCALED) and
  q_pe [H, dr_pad] (pre-scaled, RoPE applied; dr zero-padded to a lane
  multiple — zero lanes add nothing to the dots);
- whole-buffer c_kv [T, r] + k_pe [T, dr_pad] resident in VMEM (gate caps
  residency at a VMEM budget — at DeepSeek shapes r+dr is 3.5x smaller
  than one GQA head fleet, so the SAME budget holds ~3.5x more tokens);
- fori over T blocks: scores = q_lat·c_kvᵀ + q_pe·k_peᵀ, mask t > pos
  (+ optional [T] column-validity mask), streaming max/sum/context in
  f32; blocks fully beyond ``pos`` are skipped via lax.cond.

``pos`` arrives as a scalar-prefetch operand so one compiled kernel
serves every decode position. Output is the latent-space context
[B, H, r]; the caller projects through W_uv outside (one small matmul).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend


_VMEM_BUDGET = 10 * 1024 * 1024  # bytes for c_kv + k_pe residency per row


def _shape_refusal(q_lat, ckv_buf, kpe_buf):
    if q_lat.ndim != 3 or ckv_buf.ndim != 3 or kpe_buf.ndim != 3:
        return "q_lat and the latent buffers must be 3-D"
    B, H, r = q_lat.shape
    T = ckv_buf.shape[1]
    if r % 128 != 0 or T % 128 != 0 or H % 8 != 0:
        return (f"latent width {r} / buffer length {T} / {H} heads do "
                "not tile")
    dr_pad = -(-kpe_buf.shape[-1] // 128) * 128
    resident = T * (r + dr_pad) * jnp.dtype(ckv_buf.dtype).itemsize
    if resident > _VMEM_BUDGET:
        return f"one row's latents ({resident} B) exceed the VMEM budget"
    return None


def supported(q_lat, ckv_buf, kpe_buf, interpret: bool = False) -> bool:
    """Gate: TPU (or interpret-mode test), lane-tileable latent width,
    tileable buffer length, sublane-tileable head count, and whole-buffer
    latent residency under the VMEM budget."""
    return backend.gate("mla_decode",
                        _shape_refusal(q_lat, ckv_buf, kpe_buf), interpret)


def _kernel(pos_ref, qlat_ref, qpe_ref, ckv_ref, kpe_ref, allowed_ref,
            o_ref, *, H, r, dp, T, bkv, have_allowed):
    qlat = qlat_ref[0].astype(jnp.float32)         # [H, r] (pre-scaled)
    qpe = qpe_ref[0].astype(jnp.float32)           # [H, dp] (pre-scaled)
    pos = pos_ref[pl.program_id(0)]                # per-row visible limit
    nb = T // bkv

    def body(i, carry):
        m, l, acc = carry

        def compute(carry):
            m, l, acc = carry
            ckv = ckv_ref[0, pl.ds(i * bkv, bkv), :].astype(jnp.float32)
            kpe = kpe_ref[0, pl.ds(i * bkv, bkv), :].astype(jnp.float32)
            s_blk = qlat @ ckv.T + qpe @ kpe.T     # [H, bkv]
            col = (i * bkv
                   + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1))
            mask = col <= pos                      # S=1: limit is pos
            if have_allowed:
                ab = allowed_ref[0, :, pl.ds(i * bkv, bkv)]   # [1, bkv]
                mask = mask & (ab != 0)
            s_blk = jnp.where(mask, s_blk, -1e30)
            m_new = jnp.maximum(m, s_blk.max(axis=1, keepdims=True))
            p = jnp.exp(s_blk - m_new)
            # a row with NO visible column keeps the -1e30 sentinel max,
            # where exp(s - m) would be exp(0)=1 for every masked column
            # — zero those so dead rows accumulate nothing (output 0, not
            # the mean of disallowed latents)
            p = jnp.where(s_blk > -1e29, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=1, keepdims=True)
            # context from the SAME ckv load the scores used — the point
            acc = acc * alpha + p @ ckv
            return m_new, l, acc

        return jax.lax.cond(i * bkv <= pos, compute, lambda c: c, carry)

    m0 = jnp.full((H, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    a0 = jnp.zeros((H, r), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, a0))
    # fully-masked rows: l == 0 and acc == 0 → output 0 (the einsum
    # softmax would NaN; zeros are the useful answer for dead rows)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "bkv"))
def _decode_jit(q_lat, q_pe, ckv_buf, kpe_buf, pos, allowed, interpret,
                bkv=None):
    B, H, r = q_lat.shape
    T = ckv_buf.shape[1]
    dr = q_pe.shape[-1]
    dp = -(-dr // 128) * 128
    if q_pe.shape[-1] != dp:
        q_pe = jnp.pad(q_pe, ((0, 0), (0, 0), (0, dp - dr)))
    if kpe_buf.shape[-1] != dp:
        # per-step buffer copy — only on paths that did NOT allocate the
        # cache lane-padded (models.deepseek.empty_cache_layer pads on
        # TPU so the hot decode loop never pays this)
        kpe_buf = jnp.pad(
            kpe_buf, ((0, 0), (0, 0), (0, dp - kpe_buf.shape[-1])))
    if bkv is None:
        bkv = next(b for b in (512, 256, 128) if T % b == 0)
    have_allowed = allowed is not None
    # [B, 1, T]: a (1, T) block over [B, T] would put a block of 1 on a
    # second-to-last dim of B, which Mosaic refuses
    allowed = (jnp.ones((B, 1, T), jnp.int32) if not have_allowed
               else allowed.astype(jnp.int32).reshape(B, 1, T))
    # pos: scalar (shared decode offset) or [B] (per-row serving slots) —
    # the kernel always reads pos_ref[row]
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                               (B,))

    kern = functools.partial(_kernel, H=H, r=r, dp=dp, T=T, bkv=bkv,
                             have_allowed=have_allowed)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, r), lambda b, pos: (b, 0, 0)),
                pl.BlockSpec((1, H, dp), lambda b, pos: (b, 0, 0)),
                pl.BlockSpec((1, T, r), lambda b, pos: (b, 0, 0)),
                pl.BlockSpec((1, T, dp), lambda b, pos: (b, 0, 0)),
                pl.BlockSpec((1, 1, T), lambda b, pos: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, r), lambda b, pos: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_lat.dtype),
        interpret=interpret,
        name="mla_decode",
    )(pos_arr, q_lat, q_pe, ckv_buf, kpe_buf, allowed)


def mla_decode_attention(q_lat, q_pe, ckv_buf, kpe_buf, pos, allowed=None,
                         interpret: bool = False):
    """q_lat [B,H,r] (absorbed + PRE-SCALED), q_pe [B,H,dr] (RoPE'd +
    pre-scaled), ckv_buf [B,T,r], kpe_buf [B,T,dr] (current token already
    written at ``pos``), pos scalar OR [B] per-row limits (serving slots
    at different lengths), allowed optional [B,T] column mask.
    Returns the latent-space context [B,H,r] — same math as the absorbed
    einsum branch of models.deepseek.mla_cached_attention at S=1.
    ``interpret`` is honoured only off-TPU."""
    T = ckv_buf.shape[1]
    bkv = next(b for b in (512, 256, 128) if T % b == 0)
    interpret = interpret and backend.interpret_mode()
    if not interpret:
        # FLAGS_use_autotune: eager TPU calls measure the T-block grid
        # once per (shape, dtype, device) and persist the winner; traced
        # calls (scan decode / engine step) read the cache only
        from . import autotune

        key = (f"B{q_lat.shape[0]}xH{q_lat.shape[1]}xr{q_lat.shape[2]}"
               f"xT{T} {ckv_buf.dtype}")
        cands = [(b,) for b in (1024, 512, 256, 128) if T % b == 0]
        can = backend.on_tpu() and autotune.is_concrete(q_lat, ckv_buf, pos)

        def runner(cfg):
            return lambda: _decode_jit(q_lat, q_pe, ckv_buf, kpe_buf, pos,
                                       allowed, interpret, bkv=cfg[0])

        (bkv,) = autotune.pick("mla_decode", key, (bkv,), cands, runner,
                               can)
    return _decode_jit(q_lat, q_pe, ckv_buf, kpe_buf, pos, allowed,
                       interpret, bkv=bkv)
