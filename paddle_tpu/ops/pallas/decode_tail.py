"""Fused decode-tail Pallas kernels: the S=1 hot path with VMEM-resident
intermediates (MPK-style mega-kernelization, PAPERS.md "MPK").

A decode step at S=1 is latency- and dispatch-bound: every activation is
tiny ([B, hidden] is a few hundred KB) while the step issues ~7 discrete
ops per layer (norm, three projections, two ropes, epilogue norm), each
a separate XLA/Pallas dispatch whose intermediates round-trip through
HBM. These two kernels collapse the non-attention tail of a decode layer
to TWO dispatches:

- :func:`fused_qkv_rope` — ``rms_norm(x) → q/k/v projection → rotary``
  in ONE ``pallas_call``: the grid walks the CONTRACTION (hidden) axis,
  streaming weight row-blocks through VMEM while the whole (tiny) ``x``
  row block stays resident; q/k/v accumulate in f32 VMEM scratch and the
  final grid cell applies rotate-half RoPE to q and k in-register before
  the single cast-and-write. Each weight byte is read exactly once — the
  theoretical minimum for the step — and the normed hidden and pre-rope
  q/k/v never exist in HBM.
- :func:`fused_epilogue` — ``attention-out → o_proj → residual-add →
  rms_norm`` in one ``pallas_call`` with the same contraction-walk
  shape; emits the next sublayer input AND the new residual stream
  (``add_rms_norm``'s contract) without materializing the o_proj output.

Numerical parity with the discrete path is exact by construction: every
cast sits where the discrete ops cast (norm math in f32 → cast to the
compute dtype → matmul with f32 accumulation → cast → rope in f32 →
cast), so the fused decode step is token-identical to the discrete one
(tier-1 asserts this in interpret mode; tests/test_decode_tail.py).

The contraction block size is an autotune-search dimension
(ops/pallas/autotune.py): a registered analytical cost model prunes
VMEM-infeasible geometries and ranks the rest by roofline before
anything is timed. The flag lives in utils/flags.py
(``FLAGS_use_fused_decode_tail``, default off — the discrete path is
the reference); models/llama.py gates per-layer on :func:`supported`
and falls back exactly when any structural assumption (full-width rope,
no qk-norm, no projection bias, VMEM feasibility) does not hold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

#: VMEM ceiling for the per-cell working set at the smallest block —
#: beyond this the discrete path is the right call anyway
_VMEM_BUDGET = 12 * 1024 * 1024

_MIN_BLOCK_K = 128


def enabled() -> bool:
    from ...utils.flags import get_flags

    return bool(get_flags("FLAGS_use_fused_decode_tail")
                ["FLAGS_use_fused_decode_tail"])


# ---------------------------------------------------------------------------
# analytical cost models (autotune pruning + graph-cost-table lint replay)
# ---------------------------------------------------------------------------

def _qkv_cost(params: dict, choice: tuple) -> dict:
    b = int(params["batch"])
    hidden = int(params["hidden"])
    wtot = int(params["wtot"])          # (H + 2*hk) * head_dim
    it = jnp.dtype(params["dtype"]).itemsize
    (bk,) = choice
    return {
        "bytes": hidden * wtot * it + b * hidden * it + b * wtot * it,
        "flops": 2 * b * hidden * wtot,
        # x resident + double-buffered weight block + f32 accumulators
        "vmem_bytes": (b * hidden * it + 2 * bk * wtot * it
                       + b * wtot * (4 + it)),
        "grid": hidden // max(bk, 1),
    }


def _epilogue_cost(params: dict, choice: tuple) -> dict:
    b = int(params["batch"])
    width = int(params["width"])        # H * head_dim
    hidden = int(params["hidden"])
    it = jnp.dtype(params["dtype"]).itemsize
    (bk,) = choice
    return {
        "bytes": (width * hidden * it + b * width * it
                  + 3 * b * hidden * it),
        "flops": 2 * b * width * hidden,
        "vmem_bytes": (b * width * it + 2 * bk * hidden * it
                       + b * hidden * (4 + 3 * it)),
        "grid": width // max(bk, 1),
    }


def _spec_verify_cost(params: dict, choice: tuple) -> dict:
    """Whole-dispatch cost of one speculative verify at chunk width k
    (the engine's multi-token decode step): the weight stream is read
    ONCE per dispatch regardless of k — exactly why wider chunks raise
    arithmetic intensity on the HBM-bound decode tail — while FLOPs and
    activation traffic scale with b*k. Registered so the engine's
    spec-k autotune sweep prunes/ranks like any kernel geometry and the
    graph-cost-table lint can replay persisted entries."""
    (k,) = choice
    b = int(params["batch"])
    hidden = int(params["hidden"])
    layers = int(params["layers"])
    inter = int(params["intermediate"])
    wtot = int(params["wtot"])          # (H + 2*hk) * head_dim per layer
    vocab = int(params["vocab"])
    it = jnp.dtype(params["dtype"]).itemsize
    # weights: qkv + o_proj + 3 MLP mats per layer + the lm head
    w_elems = layers * (hidden * wtot + hidden * hidden
                        + 3 * hidden * inter) + hidden * vocab
    act_elems = b * k * (layers * (4 * hidden + 2 * inter) + vocab)
    return {
        "bytes": (w_elems + act_elems) * it,
        "flops": 2 * b * k * w_elems,
        "vmem_bytes": 0,                 # XLA-scheduled; never infeasible
        "grid": 0,
    }


def _register_cost_models():
    from . import autotune

    autotune.register_cost_model("fused_qkv_rope", _qkv_cost)
    autotune.register_cost_model("fused_epilogue", _epilogue_cost)
    autotune.register_cost_model("spec_verify", _spec_verify_cost)


_register_cost_models()


def _block_k(kernel: str, contraction: int, params: dict, runner,
             *arrays) -> int:
    """Contraction block: the largest divisor ≤ 512 by default, or the
    autotune search's cost-table answer (eager TPU callers measure; the
    traced decode step reads the cache only)."""
    from . import autotune

    cands = [(b,) for b in (1024, 512, 256, 128) if contraction % b == 0]
    default = next((b for (b,) in cands if b <= 512), (cands[-1][0]
                                                       if cands else 128))
    can = backend.on_tpu() and autotune.is_concrete(*arrays)
    sig = " ".join(f"{k}{v}" for k, v in sorted(params.items()))
    (bk,) = autotune.search(
        kernel, sig, (default,), cands, runner, can, params=params,
        cost_model=lambda cfg: autotune.analytical_cost(kernel, params,
                                                        cfg))
    return bk


# ---------------------------------------------------------------------------
# kernel 1: rms_norm -> q/k/v projection -> rope
# ---------------------------------------------------------------------------

def _rope_rotate(flat, cs, n_heads, d):
    """Rotate-half RoPE on a [B, n_heads*d] compute-dtype block with
    per-row f32 cos|sin [B, 2d]; matches rope_ref's cast order (f32
    accumulate, cast once at the end)."""
    b = flat.shape[0]
    x = flat.reshape(b * n_heads, d) if n_heads > 1 else flat
    cos = cs[:, :d]
    sin = cs[:, d:]
    if n_heads > 1:
        # reshape-then-broadcast: ``cs[:, None, :d]`` lowers to a gather,
        # which Mosaic refuses
        cos = jnp.broadcast_to(cos.reshape(b, 1, d),
                               (b, n_heads, d)).reshape(b * n_heads, d)
        sin = jnp.broadcast_to(sin.reshape(b, 1, d),
                               (b, n_heads, d)).reshape(b * n_heads, d)
    x1, x2 = x[:, : d // 2], x[:, d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    out = (x.astype(jnp.float32) * cos + rot.astype(jnp.float32) * sin
           ).astype(flat.dtype)
    return out.reshape(b, n_heads * d)


def _qkv_kernel(x_ref, wn_ref, wq_ref, wk_ref, wv_ref, cs_ref,
                oq_ref, ok_ref, ov_ref, aq, ak, av, *,
                bk, nblocks, eps, n_heads, n_kv, d):
    i = pl.program_id(0)
    x32 = x_ref[:].astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    xs = x_ref[:, pl.ds(i * bk, bk)].astype(jnp.float32)
    ws = wn_ref[0, pl.ds(i * bk, bk)]
    # exactly the discrete rms_norm's slice: f32 normalize, cast to the
    # compute dtype, THEN the (dtype) weight multiply
    normed = (xs * rms).astype(oq_ref.dtype) * ws

    pq = jnp.dot(normed, wq_ref[:], preferred_element_type=jnp.float32)
    pk = jnp.dot(normed, wk_ref[:], preferred_element_type=jnp.float32)
    pv = jnp.dot(normed, wv_ref[:], preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _init():
        aq[:], ak[:], av[:] = pq, pk, pv

    @pl.when(i > 0)
    def _acc():
        aq[:] += pq
        ak[:] += pk
        av[:] += pv

    @pl.when(i == nblocks - 1)
    def _finalize():
        cs = cs_ref[:]
        oq_ref[:] = _rope_rotate(aq[:].astype(oq_ref.dtype), cs, n_heads, d)
        ok_ref[:] = _rope_rotate(ak[:].astype(ok_ref.dtype), cs, n_kv, d)
        ov_ref[:] = av[:].astype(ov_ref.dtype)


def fused_qkv_rope(x, w_norm, wq, wk, wv, cos_row, sin_row, eps,
                   n_heads: int, n_kv: int, d: int):
    """x [B, hidden] → (q [B, H*D], k [B, hk*D], v [B, hk*D]), q/k
    roped at each row's position (``cos_row``/``sin_row`` [B, D] f32
    gathered by the caller — scalar pos broadcasts, per-row positions
    gather)."""
    b, hidden = x.shape
    cs = jnp.concatenate([cos_row.astype(jnp.float32),
                          sin_row.astype(jnp.float32)], axis=-1)
    params = {"batch": b, "hidden": hidden,
              "wtot": (n_heads + 2 * n_kv) * d, "dtype": str(x.dtype)}

    interpret = backend.interpret_mode()

    def runner(cfg):
        return lambda: _qkv_call(x, w_norm, wq, wk, wv, cs, eps, n_heads,
                                 n_kv, d, interpret, cfg[0])

    bk = (128 if interpret
          else _block_k("fused_qkv_rope", hidden, params, runner,
                        x, wq, cos_row))
    return _qkv_call(x, w_norm, wq, wk, wv, cs, eps, n_heads, n_kv, d,
                     interpret, bk)


@functools.partial(jax.jit, static_argnames=("eps", "n_heads", "n_kv",
                                             "d", "interpret", "bk"))
def _qkv_call(x, w_norm, wq, wk, wv, cs, eps, n_heads, n_kv, d,
              interpret, bk):
    b, hidden = x.shape
    nblocks = hidden // bk
    kern = functools.partial(_qkv_kernel, bk=bk, nblocks=nblocks, eps=eps,
                             n_heads=n_heads, n_kv=n_kv, d=d)
    wid_q, wid_kv = n_heads * d, n_kv * d
    return pl.pallas_call(
        kern,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((b, hidden), lambda i: (0, 0)),      # x resident
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),      # norm weight
            pl.BlockSpec((bk, wid_q), lambda i: (i, 0)),      # wq stream
            pl.BlockSpec((bk, wid_kv), lambda i: (i, 0)),     # wk stream
            pl.BlockSpec((bk, wid_kv), lambda i: (i, 0)),     # wv stream
            pl.BlockSpec((b, 2 * d), lambda i: (0, 0)),       # cos|sin
        ],
        out_specs=(
            pl.BlockSpec((b, wid_q), lambda i: (0, 0)),
            pl.BlockSpec((b, wid_kv), lambda i: (0, 0)),
            pl.BlockSpec((b, wid_kv), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, wid_q), x.dtype),
            jax.ShapeDtypeStruct((b, wid_kv), x.dtype),
            jax.ShapeDtypeStruct((b, wid_kv), x.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((b, wid_q), jnp.float32),
            pltpu.VMEM((b, wid_kv), jnp.float32),
            pltpu.VMEM((b, wid_kv), jnp.float32),
        ],
        interpret=interpret,
        name="decode_tail_qkv_rope",
    )(x, w_norm.reshape(1, hidden), wq, wk, wv, cs)


# ---------------------------------------------------------------------------
# kernel 2: o_proj -> residual add -> rms_norm
# ---------------------------------------------------------------------------

def _epilogue_kernel(a_ref, wo_ref, r_ref, wn_ref, on_ref, os_ref, acc, *,
                     bk, nblocks, eps):
    i = pl.program_id(0)
    a_slice = a_ref[:, pl.ds(i * bk, bk)]
    part = jnp.dot(a_slice, wo_ref[:], preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _init():
        acc[:] = part

    @pl.when(i > 0)
    def _acc():
        acc[:] += part

    @pl.when(i == nblocks - 1)
    def _finalize():
        # cast exactly where the discrete path casts: o_proj's output is
        # a compute-dtype array BEFORE add_rms_norm lifts it back to f32
        od = acc[:].astype(on_ref.dtype)
        h = od.astype(jnp.float32) + r_ref[:].astype(jnp.float32)
        os_ref[:] = h.astype(os_ref.dtype)
        rms = jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
        on_ref[:] = (h * rms).astype(on_ref.dtype) * wn_ref[:]


def fused_epilogue(attn, wo, residual, w_norm, eps):
    """attn [B, H*D] (pre-o_proj attention output), wo [H*D, hidden],
    residual [B, hidden] → (normed [B, hidden], new_residual
    [B, hidden]) — ``add_rms_norm(o_proj(attn), residual, w)`` in one
    dispatch."""
    b, width = attn.shape
    hidden = wo.shape[1]
    params = {"batch": b, "width": width, "hidden": hidden,
              "dtype": str(attn.dtype)}

    interpret = backend.interpret_mode()

    def runner(cfg):
        return lambda: _epilogue_call(attn, wo, residual, w_norm, eps,
                                      interpret, cfg[0])

    bk = (128 if interpret
          else _block_k("fused_epilogue", width, params, runner,
                        attn, wo, residual))
    return _epilogue_call(attn, wo, residual, w_norm, eps, interpret, bk)


@functools.partial(jax.jit, static_argnames=("eps", "interpret", "bk"))
def _epilogue_call(attn, wo, residual, w_norm, eps, interpret, bk):
    b, width = attn.shape
    hidden = wo.shape[1]
    nblocks = width // bk
    kern = functools.partial(_epilogue_kernel, bk=bk, nblocks=nblocks,
                             eps=eps)
    return pl.pallas_call(
        kern,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((b, width), lambda i: (0, 0)),       # attn resident
            pl.BlockSpec((bk, hidden), lambda i: (i, 0)),     # wo stream
            pl.BlockSpec((b, hidden), lambda i: (0, 0)),      # residual
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),      # norm weight
        ],
        out_specs=(
            pl.BlockSpec((b, hidden), lambda i: (0, 0)),
            pl.BlockSpec((b, hidden), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, hidden), attn.dtype),
            jax.ShapeDtypeStruct((b, hidden), attn.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((b, hidden), jnp.float32)],
        interpret=interpret,
        name="decode_tail_epilogue",
    )(attn, wo, residual.astype(attn.dtype), w_norm.reshape(1, hidden))


# ---------------------------------------------------------------------------
# gates + audit
# ---------------------------------------------------------------------------

def supported(b: int, hidden: int, n_heads: int, n_kv: int, d: int,
              rope_width: int, itemsize: int) -> bool:
    """Structural + VMEM gate for the fused S=1 tail. The caller (the
    llama decoder layer) additionally checks the model-level assumptions
    (no qk-norm, no q pre-multiplier, no projection bias). Off-TPU the
    kernels run interpret mode like every Pallas op here — the flag
    (default off) is the opt-in, the gate is about shapes."""
    if d % 128 != 0 or hidden % _MIN_BLOCK_K != 0:
        return False
    if rope_width != d:
        return False  # partial-rotary families keep the discrete path
    if (n_heads * d) % _MIN_BLOCK_K != 0:
        return False
    wtot = (n_heads + 2 * n_kv) * d
    qkv_vmem = _qkv_cost({"batch": b, "hidden": hidden, "wtot": wtot,
                          "dtype": "float32" if itemsize == 4
                          else "bfloat16"},
                         (_MIN_BLOCK_K,))["vmem_bytes"]
    epi_vmem = _epilogue_cost({"batch": b, "width": n_heads * d,
                               "hidden": hidden,
                               "dtype": "float32" if itemsize == 4
                               else "bfloat16"},
                              (_MIN_BLOCK_K,))["vmem_bytes"]
    return max(qkv_vmem, epi_vmem) <= _VMEM_BUDGET


_announced = set()


def announce(layout: str, b: int, hidden: int, n_heads: int, n_kv: int,
             d: int):
    """One kernel.fused_step flight-recorder event per activated shape
    (emitted at trace/selection time — O(compiles), never O(steps))."""
    sig = (layout, b, hidden, n_heads, n_kv, d)
    if sig in _announced:
        return
    _announced.add(sig)
    from ...observability import flightrecorder as _frec

    rec = _frec.get_recorder()
    if rec.enabled:
        rec.record(_frec.EV_FUSED_STEP, kernel="decode_tail", batch=b,
                   hidden=hidden, heads=n_heads, kv_heads=n_kv,
                   head_dim=d, layout=layout)
