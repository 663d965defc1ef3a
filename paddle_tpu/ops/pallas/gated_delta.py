"""Pallas kernels of the gated delta rule (Gated DeltaNet, Yang, Kautz,
Hatamizadeh, arXiv:2412.06464): the recurrence of a linear-attention
layer (``models/olmo_hybrid.py``), with each kernel's XLA composite.

Per head, with ``S`` a ``[dv, dk]`` float32 state, and per token ``t``
the decay ``a_t = exp(g_t)`` (``g`` the log-decay, <= 0), the write
strength ``b_t`` and unit-norm ``k_t``:

    S_t = a_t (S_{t-1} - b_t (S_{t-1} k_t) k_t^T) + b_t v_t k_t^T
    o_t = S_t q_t

A token with ``g = 0`` and ``b = 0`` leaves ``S`` exactly as it was: that
is how a pad, or a row that does not advance, is gated.

- ``gated_delta_prefill``: the chunked form, 64 tokens a chunk. Within a
  chunk the WY/UT transform turns the chunk's 64 rank-one updates into
  matrix products: ``T = (I + tril(diag(b) K K^T * decay, -1))^-1``, its
  8-row diagonal blocks by forward substitution on the VPU and the rest
  by three doublings of two MXU products each; ``U = T diag(b) V`` and
  ``W = T diag(b exp(G)) K``. Across chunks the state is carried in VMEM
  in float32: ``V' = U - W S^T``,
  ``O = (Q exp(G)) S^T + (Q K^T * decay) V'``,
  ``S <- S exp(G_last) + V'^T (K exp(G_last - G))``. Every product is
  float32 at ``Precision.HIGHEST``. Grid (batch, head block, chunk), the
  chunk axis sequential: a block holds as many whole heads as fit
  ``_PREFILL_BLOCK_BYTES`` (``prefill_heads_per_block``: 10 of 30 at
  dk 96 / dv 192), whose independent chains of products interleave on
  the MXU. The rows' lengths are a scalar-prefetch operand: a chunk past
  its row's length fetches nothing new and does no math, and o is zero
  at every pad.
- ``gated_delta_decode``: one token for every row, the state updated IN
  PLACE (``input_output_aliases``), gated by the row's ``live`` flag
  (a scalar-prefetch operand): a row that does not advance stores its
  state block as it read it, bit for bit.

**Layout.** Widths 96 (dk) and 192 (dv) are not whole lanes. A block
holds whole heads with trailing dims equal to the array's (``[hb, 192,
96]`` in decode, ``[hb, 64, 96]`` / ``[hb, 64, 192]`` and ``[hb, 192,
96]`` in prefill): Mosaic pads 96 lanes to 128 in VMEM and the state
pool is laid out ``T(8,128)`` in HBM too, so the custom call asks for
the pool's own layout and XLA copies nothing around it (described v5e
compile, PR 39).
The price is the pad: a 192 x 96 f32 head state occupies 192 x 128, a
third more than its bytes (PERF.md, section 5).

Off the chip the composites run (``backend.gate(..., interpret=False)``
at the model's call sites); tests call the kernels with ``interpret``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

PREFILL_SITE = "gated_delta_prefill"
DECODE_SITE = "gated_delta_decode"
CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST
#: the decode kernel's state block (double-buffered in and out): whole
#: heads, as many as divide the head count within this many bytes
_DECODE_BLOCK_BYTES = 1 << 20
#: the prefill kernel's blocks a grid step (double-buffered): whole heads,
#: as many as divide the head count within this many bytes
_PREFILL_BLOCK_BYTES = 8 << 20
#: rows of the chunk's diagonal blocks that the triangular inverse solves
#: by substitution (a power of two; above it, doubling on the MXU)
_SOLVE_BLOCK = 8


def _dot(a, b, contract):
    """Per head, an f32 product on the MXU at full f32 precision: both
    operands carry a leading head axis, and ``contract`` names the
    contracted dim of each head's matrix (``(1, 0)`` A B, ``(1, 1)`` A
    B^T, ``(0, 0)`` A^T B)."""
    return jax.lax.dot_general(
        a, b, (((contract[0] + 1,), (contract[1] + 1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=_HIGHEST)


def _pad_tokens(x, t_pad, axis=1):
    t = x.shape[axis]
    if t == t_pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, t_pad - t)
    return jnp.pad(x, widths)


# ---- the chunked form ------------------------------------------------------
def _unit_lower_inverse(low, i, j, c):
    """``(I + low)^-1`` for a strictly lower-triangular ``low`` [hb, c, c]
    (c a power of two), one inverse a head. The diagonal blocks of
    ``_SOLVE_BLOCK`` rows are solved by forward substitution on the VPU,
    all of them and every head at once: step s takes row s of each block,
    final by then, times column s of ``low`` within the block (``X <- X -
    L[:, s] X[s, :]``). Above that block, doubling: each 2b-block's
    lower-left quadrant C becomes ``-X_D C X_A``, i.e. ``X <- X - X C X``,
    two MXU products a level. Both are as stable as the row-by-row
    substitution (the sum of the powers of ``-low`` is not: where the keys
    of a chunk are alike its terms grow like binomials and cancel)."""
    n = min(_SOLVE_BLOCK, c)
    x = jnp.broadcast_to(jnp.where(i == j, 1.0, 0.0).astype(jnp.float32),
                         low.shape)
    if n > 1:
        block = ((i ^ j) & ~(n - 1)) == 0
        diag = jnp.where(block, low, 0.0)

        def step(s, x):
            col = jnp.sum(jnp.where((j & (n - 1)) == s, diag, 0.0), axis=2,
                          keepdims=True)                    # [hb, c, 1]
            row = jnp.sum(jnp.where((i & (n - 1)) == s, x, 0.0), axis=1,
                          keepdims=True)                    # [hb, 1, c]
            return x - jnp.where(block, col * row, 0.0)

        x = jax.lax.fori_loop(0, n, step, x)
    b = n
    while b < c:
        quad = (((i ^ j) & ~(2 * b - 1)) == 0) & ((i & b) != 0) & \
            ((j & b) == 0)
        x = x - _dot(_dot(x, jnp.where(quad, low, 0.0), (1, 0)), x, (1, 0))
        b *= 2
    return x


def _chunk_math(q, k, v, gc_col, gc_row, beta_col, S, c):
    """One chunk of hb heads: q/k [hb, c, dk], v [hb, c, dv], the chunk's
    cumulative log-decay as columns [hb, c, 1] and rows [hb, 1, c], beta
    as columns, S [hb, dv, dk]. Returns (o [hb, c, dv], S_next). Shared by
    the kernel body and the composite, so the two differ only in where
    they run. Products that share an operand take it once: ``K_b K^T``
    over ``Q K^T``, and ``W S^T`` over ``(Q e^G) S^T``, rows stacked."""
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    decay = jnp.exp(jnp.where(i >= j, gc_col - gc_row, -jnp.inf))
    eg = jnp.exp(gc_col)
    kb = k * beta_col
    kk = _dot(jnp.concatenate([kb, q], axis=1), k, (1, 1))  # [hb, 2c, c]
    low = jnp.where(i > j, kk[:, :c] * decay, 0.0)
    qk = jnp.where(i >= j, kk[:, c:] * decay, 0.0)
    t_inv = _unit_lower_inverse(low, i, j, c)
    u = _dot(t_inv, v * beta_col, (1, 0))                   # [hb, c, dv]
    w = _dot(t_inv, kb * eg, (1, 0))                        # [hb, c, dk]
    ws = _dot(jnp.concatenate([w, q * eg], axis=1), S, (1, 1))  # [hb,2c,dv]
    v_new = u - ws[:, :c]                                   # [hb, c, dv]
    o = ws[:, c:] + _dot(qk, v_new, (1, 0))
    # the chunk's last cumulative log-decay: g <= 0, so it is the least
    g_last = jnp.min(gc_row, axis=2, keepdims=True)         # [hb, 1, 1]
    S = S * jnp.exp(g_last) + _dot(v_new, k * jnp.exp(g_last - gc_col),
                                   (0, 0))
    return o, S


def _prefill_kernel(len_ref, q_ref, k_ref, v_ref, gb_ref, h0_ref, o_ref,
                    ht_ref):
    """One grid step: row b, hb heads, chunk n. The state rides in the
    ``ht`` block, whose index holds across the chunk axis. A chunk that
    starts at or after the row's length does no math: its inputs are the
    last real chunk's blocks again (no DMA), its output is zeros, as is
    every pad's in the last real chunk."""
    c = q_ref.shape[2]
    n = pl.program_id(2)
    length = len_ref[pl.program_id(0)]
    real = n * c < length

    @pl.when(n == 0)
    def _():
        ht_ref[...] = h0_ref[...]

    @pl.when(real)
    def _():
        gb = gb_ref[0, :, 0]                                # [hb, 2, c]
        gc_row, beta_row = gb[:, 0:1], gb[:, 1:2]
        o, s_new = _chunk_math(q_ref[0], k_ref[0], v_ref[0],
                               jnp.swapaxes(gc_row, 1, 2), gc_row,
                               jnp.swapaxes(beta_row, 1, 2), ht_ref[0], c)
        t = n * c + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        o_ref[0] = jnp.where(t < length, o, 0.0)
        ht_ref[0] = s_new

    @pl.when(jnp.logical_not(real))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def prefill_heads_per_block(h, dk, dv):
    """Whole heads a grid step of the prefill kernel: the largest divisor
    of ``h`` whose blocks, double-buffered (q, k, v in and o out a chunk,
    the decay and strength rows, h0 in and the state out), fit
    ``_PREFILL_BLOCK_BYTES`` with lanes rounded up to 128 and rows to 8."""
    lanes = lambda d: -(-d // 128) * 128
    per_head = 2 * 4 * (CHUNK * (2 * lanes(dk) + 2 * lanes(dv))
                        + 8 * lanes(CHUNK) + 2 * dv * lanes(dk))
    fits = [n for n in range(1, h + 1)
            if h % n == 0 and n * per_head <= _PREFILL_BLOCK_BYTES]
    return max(fits or [1])


def _prefill_inputs(q, k, v, g, beta, lengths, chunk):
    """[B, T, H, d] inputs -> head-major, chunk-padded f32 arrays, pads
    (``t >= lengths``) gated to g = 0, beta = 0, and the within-chunk
    cumulative log-decay."""
    B, T, H, _ = q.shape
    t_pad = -(-T // chunk) * chunk
    real = jnp.arange(t_pad)[None, :] < lengths[:, None]    # [B, t_pad]
    g = jnp.where(real[:, :, None], _pad_tokens(g, t_pad), 0.0)
    beta = jnp.where(real[:, :, None], _pad_tokens(beta, t_pad), 0.0)
    heads = [jnp.moveaxis(_pad_tokens(x.astype(jnp.float32), t_pad), 2, 1)
             for x in (q, k, v)]                            # [B, H, t, d]
    nc = t_pad // chunk
    gc = jnp.cumsum(jnp.moveaxis(g, 2, 1).reshape(B, H, nc, chunk), -1)
    beta = jnp.moveaxis(beta, 2, 1).reshape(B, H, nc, chunk)
    return heads, gc.astype(jnp.float32), beta.astype(jnp.float32), t_pad


def prefill_supported(q, v, interpret: bool = False) -> bool:
    dk, dv = q.shape[-1], v.shape[-1]
    reason = (None if dk % 8 == 0 and dv % 8 == 0 else
              f"head widths {dk}/{dv} are not multiples of 8 sublanes")
    return backend.gate(PREFILL_SITE, reason, interpret)


def gated_delta_prefill(q, k, v, g, beta, h0, lengths, interpret=None):
    """The chunked gated delta rule over a right-padded batch.

    q, k [B, T, H, dk] (q scaled, both unit-norm per head), v [B, T, H,
    dv], g (log-decay) and beta [B, T, H], h0 [B, H, dv, dk] float32,
    lengths [B] the real tokens of each row. Returns (o [B, T, H, dv]
    float32, the state after each row's last real token [B, H, dv, dk]).
    ``o`` is exactly 0 at every position at or after its row's length.
    ``interpret`` None: the Pallas kernel where ``prefill_supported`` lets
    it run (a TPU), else the XLA composite; True/False force the kernel
    interpreted / the composite."""
    lengths = jnp.asarray(lengths, jnp.int32)
    h0 = h0.astype(jnp.float32)
    use_kernel = (prefill_supported(q, v, interpret=True) if interpret
                  else interpret is None and prefill_supported(q, v))
    if use_kernel:
        H, dk, dv = q.shape[2], q.shape[3], v.shape[3]
        return _prefill_pallas(
            q, k, v, g, beta, h0, lengths,
            hb=prefill_heads_per_block(H, dk, dv),
            interpret=bool(interpret) or backend.interpret_mode())
    T = q.shape[1]
    (qh, kh, vh), gc, bt, t_pad = _prefill_inputs(q, k, v, g, beta, lengths,
                                                  CHUNK)
    o, ht = _prefill_composite(qh, kh, vh, gc, bt, h0, lengths, CHUNK)
    return jnp.moveaxis(o[:, :, :T], 1, 2), ht


# jitted so that a program's linear layers share one trace and one lowering
# of the kernel: each call traced and lowered on its own is twelve a prefill
# program, and set-up pays for each in every process, cache or no cache
@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _prefill_pallas(q, k, v, g, beta, h0, lengths, hb, interpret):
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    chunk = CHUNK
    (qh, kh, vh), gc, bt, t_pad = _prefill_inputs(q, k, v, g, beta, lengths,
                                                  chunk)
    nc = t_pad // chunk
    gb = jnp.stack([gc, bt], axis=3)                        # [B,H,nc,2,c]

    def at(b, n, lens):
        # past the row's last real chunk: that chunk's block again
        last = jnp.maximum((lens[b] + chunk - 1) // chunk - 1, 0)
        return jnp.minimum(n, last)

    tok = lambda d: pl.BlockSpec(
        (1, hb, chunk, d), lambda b, h, n, lens: (b, h, at(b, n, lens), 0))
    head = pl.BlockSpec((1, hb, dv, dk), lambda b, h, n, lens: (b, h, 0, 0))
    o, ht = pl.pallas_call(
        _prefill_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // hb, nc),
            in_specs=[tok(dk), tok(dk), tok(dv),
                      pl.BlockSpec((1, hb, 1, 2, chunk),
                                   lambda b, h, n, lens:
                                   (b, h, at(b, n, lens), 0, 0)),
                      head],
            out_specs=[pl.BlockSpec((1, hb, chunk, dv),
                                    lambda b, h, n, lens: (b, h, n, 0)),
                       head]),
        out_shape=[jax.ShapeDtypeStruct((B, H, t_pad, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=PREFILL_SITE,
    )(lengths, qh, kh, vh, gb, h0)
    return jnp.moveaxis(o[:, :, :T], 1, 2), ht


def _prefill_composite(qh, kh, vh, gc, bt, h0, lengths, chunk):
    """The same chunked math in XLA: vmapped over the batch, a scan over
    chunks of every head at once; o zeroed from the row's length on."""
    B, H, t_pad, dk = qh.shape
    nc = t_pad // chunk

    def one_row(q, k, v, gc, bt, s0):
        def body(s, xs):
            qc, kc, vc, gcc, btc = xs
            o, s = _chunk_math(qc, kc, vc, gcc[:, :, None], gcc[:, None, :],
                               btc[:, :, None], s, chunk)
            return s, o

        chunks = lambda x: jnp.moveaxis(x.reshape(H, nc, chunk, -1), 1, 0)
        xs = (chunks(q), chunks(k), chunks(v), jnp.moveaxis(gc, 1, 0),
              jnp.moveaxis(bt, 1, 0))
        s, o = jax.lax.scan(body, s0, xs)                   # o [nc, H, c, dv]
        return jnp.moveaxis(o, 0, 1).reshape(H, t_pad, -1), s

    o, ht = jax.vmap(one_row)(qh, kh, vh, gc, bt, h0)
    real = jnp.arange(t_pad)[None, :] < lengths[:, None]    # [B, t_pad]
    return jnp.where(real[:, None, :, None], o, 0.0), ht


# ---- one token a row -------------------------------------------------------
def _step_math(S, q, k, v_col, a, b):
    """S [hb, dv, dk]; q, k [hb, 1, dk]; v_col [hb, dv, 1]; a, b [hb, 1, 1].
    Returns (S_next, o as a column [hb, dv, 1])."""
    sk = jnp.sum(S * k, axis=-1, keepdims=True)             # [hb, dv, 1]
    S = a * S + (b * (v_col - a * sk)) * k
    return S, jnp.sum(S * q, axis=-1, keepdims=True)


def _decode_kernel(live_ref, s_ref, q_ref, k_ref, v_ref, ab_ref, o_ref,
                   so_ref):
    S = s_ref[0]
    ab = ab_ref[0]                                          # [hb, 1, 2]
    s_new, o = _step_math(S, q_ref[0], k_ref[0],
                          jnp.swapaxes(v_ref[0], 1, 2), ab[:, :, 0:1],
                          ab[:, :, 1:2])
    so_ref[0] = jnp.where(live_ref[pl.program_id(0)] > 0, s_new, S)
    o_ref[0] = jnp.swapaxes(o, 1, 2)


def _heads_per_block(h, dv, dk):
    lanes = -(-dk // 128) * 128
    fits = [n for n in range(1, h + 1)
            if h % n == 0 and n * dv * lanes * 4 <= _DECODE_BLOCK_BYTES]
    return max(fits or [1])


def decode_supported(state, interpret: bool = False) -> bool:
    dv = state.shape[-2]
    reason = (None if dv % 8 == 0 else
              f"value width {dv} is not a multiple of 8 sublanes")
    return backend.gate(DECODE_SITE, reason, interpret)


def gated_delta_decode(state, q, k, v, g, beta, live, interpret=None):
    """One token for every row. state [B, H, dv, dk] float32 (updated in
    place on the kernel path: donate it), q, k [B, H, dk], v [B, H, dv],
    g and beta [B, H], live [B] (> 0: the row advances; else its state is
    left bit-equal). Returns (o [B, H, dv] float32, the new state).
    ``interpret`` as in ``gated_delta_prefill``."""
    B, H, dv, dk = state.shape
    f32 = jnp.float32
    a = jnp.exp(g.astype(f32))
    b = beta.astype(f32)
    live = jnp.asarray(live, jnp.int32)
    use_kernel = (decode_supported(state, interpret=True) if interpret
                  else interpret is None and decode_supported(state))
    if not use_kernel:
        s_new, o = _step_math(state, q.astype(f32)[:, :, None],
                              k.astype(f32)[:, :, None],
                              v.astype(f32)[..., None], a[..., None, None],
                              b[..., None, None])
        s_new = jnp.where(live[:, None, None, None] > 0, s_new, state)
        return o[..., 0], s_new
    hb = _heads_per_block(H, dv, dk)
    blk = lambda *s: pl.BlockSpec((1, hb) + s, lambda i, j, live: (i, j, 0, 0))
    o, s_new = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // hb),
            in_specs=[blk(dv, dk), blk(1, dk), blk(1, dk), blk(1, dv),
                      blk(1, 2)],
            out_specs=[blk(1, dv), blk(dv, dk)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands count the scalar-prefetch array: the state is the 2nd
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=bool(interpret) or backend.interpret_mode(),
        name=DECODE_SITE,
    )(live, state, q.astype(f32)[:, :, None], k.astype(f32)[:, :, None],
      v.astype(f32)[:, :, None], jnp.stack([a, b], -1)[:, :, None])
    return o[:, :, 0], s_new
