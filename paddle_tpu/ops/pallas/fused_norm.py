"""Custom Pallas kernels: fused RMSNorm (+residual) and fused RoPE.

Reference parity: paddle/phi/kernels/fusion/gpu/rms_norm* and
fused_rope (paddle/phi/infermeta/spmd_rules/fused_rope.cc for the dist rule).
These are HBM-bandwidth-bound elementwise+reduce ops — one VMEM round trip
instead of several. Custom VJPs keep them differentiable; off-TPU they run
in interpret mode (tests), and shapes that do not tile take the XLA
composite (recorded by ``backend.gate``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import backend


def _pick_block_rows(rows: int, d: int) -> int:
    """Largest row-block with block*d ≤ 512K elements (≈2 MB f32 per ref) —
    the f32 intermediates of 4-5 refs must fit the ~16 MB scoped-VMEM stack
    (observed OOM at d=4096 with a fixed 256-row block)."""
    block = 256
    while block > 8 and (block * d > 512 * 1024 or rows % block):
        block //= 2
    return block


def _block_candidates(rows: int, d: int):
    """Row blocks that divide the grid, for the autotune search space —
    wider than the VMEM bound alone admits: the roofline cost model
    prunes infeasible geometries before they are ever launched."""
    return [(b,) for b in (1024, 512, 256, 128, 64, 32, 16, 8)
            if rows % b == 0]


def _norm_cost(params: dict, choice: tuple, n_io: int = 2) -> dict:
    """Analytical cost of a row-blocked norm kernel: ``n_io`` dtype-wide
    HBM streams of [rows, d] (x+out for rms_norm; x+residual+out+sum for
    add_rms_norm) plus the weight row; VPU flops ~ a few per element.
    Registered with autotune so the graph-cost-table lint can replay it
    against persisted entries."""
    rows, d = int(params["rows"]), int(params["d"])
    it = jnp.dtype(params["dtype"]).itemsize
    (block,) = choice
    return {
        "bytes": n_io * rows * d * it + d * it,
        "flops": (n_io + 2) * rows * d,
        # per-cell working set: n_io dtype blocks + one f32 intermediate
        "vmem_bytes": block * d * (n_io * it + 4),
        "grid": rows // max(block, 1),
    }


def _tuned_block_rows(kernel: str, rows: int, d: int, dtype, runner,
                      *arrays) -> int:
    """Heuristic block unless the autotune cost table
    (ops/pallas/autotune.py, the phi/kernels/autotune analog) knows — or
    can search out — better. ``arrays`` are the kernel operands: a timed
    sweep is only legal when they are concrete (not tracers) on a real
    TPU."""
    from . import autotune

    default = _pick_block_rows(rows, d)
    can_measure = backend.on_tpu() and autotune.is_concrete(*arrays)
    params = {"rows": rows, "d": d, "dtype": str(jnp.dtype(dtype))}
    (block,) = autotune.search(
        kernel, f"rows{rows} d{d} {jnp.dtype(dtype)}", (default,),
        _block_candidates(rows, d), runner, can_measure, params=params,
        cost_model=lambda cfg: autotune.analytical_cost(kernel, params,
                                                        cfg))
    return block


def _row_gate(site: str, rows: int, d: int) -> bool:
    """Row-blocked kernels need a lane-multiple width and a sublane
    multiple of rows; off-TPU they run interpreted (tests)."""
    return backend.gate(site, None if d % 128 == 0 and rows % 8 == 0
                        else f"[{rows}, {d}] is not (8, 128)-tileable")


# ---------------- fused RMSNorm ----------------------------------------------

def _rmsnorm_fwd_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[:] = (x * rms).astype(o_ref.dtype) * w_ref[:]


def _rmsnorm_pallas(x2d, w, eps, block_rows):
    n, d = x2d.shape
    kernel = functools.partial(_rmsnorm_fwd_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        interpret=backend.interpret_mode(),
        name="rms_norm",
    )(x2d, w.reshape(1, d))


def _rmsnorm_ref(x, w, eps):
    x32 = x.astype(jnp.float32)
    out = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return out.astype(x.dtype) * w


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, weight, eps=1e-6):
    """Fused RMSNorm over the last axis; weight shape [hidden]."""
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    if _row_gate("rms_norm", rows, d):
        # runner jits each candidate so the sweep times the KERNEL, not
        # eager pallas_call dispatch/retrace overhead
        jit_norm = jax.jit(_rmsnorm_pallas, static_argnums=(2, 3))
        block = _tuned_block_rows(
            "rms_norm", rows, d, x.dtype,
            lambda cfg: functools.partial(jit_norm, x.reshape(rows, d),
                                          weight, eps, cfg[0]),
            x, weight)
        out2d = _rmsnorm_pallas(x.reshape(rows, d), weight, eps, block)
        return out2d.reshape(x.shape)
    return _rmsnorm_ref(x, weight, eps)


def _rms_fwd(x, weight, eps):
    return rms_norm(x, weight, eps), (x, weight)


def _rms_bwd(eps, res, g):
    x, w = res
    # recompute-based VJP of the reference formulation (cheap, fused by XLA)
    _, vjp = jax.vjp(lambda xx, ww: _rmsnorm_ref(xx, ww, eps), x, w)
    return vjp(g)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


# ---------------- fused residual-add + RMSNorm --------------------------------

def _add_rmsnorm_kernel(x_ref, r_ref, w_ref, o_ref, s_ref, *, eps):
    h = (x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32))
    s_ref[:] = h.astype(s_ref.dtype)
    rms = jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    o_ref[:] = (h * rms).astype(o_ref.dtype) * w_ref[:]


def _add_rms_ref(x, r, w, eps):
    h = x + r
    return _rmsnorm_ref(h, w, eps), h


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def add_rms_norm(x, residual, weight, eps=1e-6):
    """out, new_residual = rmsnorm(x + residual) — the transformer block's
    hottest memory pattern, one HBM pass."""
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    if _row_gate("add_rms_norm", rows, d):
        jit_norm = jax.jit(_add_rms_pallas, static_argnums=(3, 4))
        block = _tuned_block_rows(
            "add_rms_norm", rows, d, x.dtype,
            lambda cfg: functools.partial(jit_norm, x.reshape(rows, d),
                                          residual.reshape(rows, d),
                                          weight, eps, cfg[0]),
            x, residual, weight)
        out2d, h2d = _add_rms_pallas(x.reshape(rows, d),
                                     residual.reshape(rows, d),
                                     weight, eps, block)
        return out2d.reshape(x.shape), h2d.reshape(x.shape)
    return _add_rms_ref(x, residual, weight, eps)


def _add_rms_pallas(x2d, r2d, w, eps, block):
    rows, d = x2d.shape
    kernel = functools.partial(_add_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, d), x2d.dtype),
            jax.ShapeDtypeStruct((rows, d), x2d.dtype),
        ),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((block, d), lambda i: (i, 0)),
        ),
        interpret=backend.interpret_mode(),
        name="add_rms_norm",
    )(x2d, r2d, w.reshape(1, d))


def _add_rms_fwd(x, r, w, eps):
    out = add_rms_norm(x, r, w, eps)
    return out, (x, r, w)


def _add_rms_bwd(eps, res, gs):
    x, r, w = res
    _, vjp = jax.vjp(lambda a, b, c: _add_rms_ref(a, b, c, eps), x, r, w)
    return vjp(gs)


add_rms_norm.defvjp(_add_rms_fwd, _add_rms_bwd)


# ---------------- fused RoPE --------------------------------------------------

def partial_rope(full_fn, x, cos, sin, *args):
    """THE width-aware rotary wrapper (partial_rotary_factor —
    GLM/StableLM/Phi-3-small class): tables narrower than the head rotate
    only the leading slice through ``full_fn``; the tail passes through.
    Every rope application path (eager fused, dense reference, ragged
    per-row) routes here so the slicing rule lives in one place.
    A partial width must be a rope_dim_of product: even and < head_dim
    (a width-1 "broadcastable" table is NOT a partial width — it would
    silently rotate one lane)."""
    r = cos.shape[-1]
    if r == x.shape[-1]:
        return full_fn(x, cos, sin, *args)
    if r > x.shape[-1] or r % 2 or r < 2:
        raise ValueError(
            f"rope table width {r} is not a valid partial width for "
            f"head_dim {x.shape[-1]} (must be even and smaller)")
    return jnp.concatenate([full_fn(x[..., :r], cos, sin, *args),
                            x[..., r:]], axis=-1)


def _rope_ref_full(x, cos, sin):
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    c = cos.reshape(1, cos.shape[-2], 1, cos.shape[-1])
    s = sin.reshape(1, sin.shape[-2], 1, sin.shape[-1])
    return (x.astype(jnp.float32) * c + rotated.astype(jnp.float32) * s).astype(x.dtype)


def rope_ref(x, cos, sin):
    """Rotate-half RoPE on [B, S, H, D]; cos/sin [S, D] (full width, or an
    EVEN partial width — see partial_rope)."""
    return partial_rope(_rope_ref_full, x, cos, sin)


def _rope_kernel(x_ref, cs_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)  # [block, d]
    d = x.shape[-1]
    cos = cs_ref[:, :d]
    sin = cs_ref[:, d:]
    x1, x2 = x[:, : d // 2], x[:, d // 2 :]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    o_ref[:] = (x * cos + rot * sin).astype(o_ref.dtype)


def apply_rope(x, cos, sin):
    """Width-aware rotary over the fused kernel (see partial_rope)."""
    return partial_rope(fused_rope, x, cos, sin)


@jax.custom_vjp
def fused_rope(x, cos, sin):
    """Fused rotary embedding: x [B,S,H,D], cos/sin [S,D]."""
    b, s, h, d = x.shape
    if not backend.gate("fused_rope", None if d % 128 == 0 else
                        f"head_dim {d} is not a lane multiple"):
        return rope_ref(x, cos, sin)
    cs = jnp.concatenate([cos.astype(jnp.float32), sin.astype(jnp.float32)], axis=-1)  # [S, 2D]
    xt = jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)  # rows grouped by sequence

    def run(x3):
        return pl.pallas_call(
            _rope_kernel,
            out_shape=jax.ShapeDtypeStruct((s, d), x.dtype),
            grid=(1,),
            in_specs=[
                pl.BlockSpec((s, d), lambda i: (0, 0)),
                pl.BlockSpec((s, 2 * d), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((s, d), lambda i: (0, 0)),
            interpret=backend.interpret_mode(),
            name="fused_rope",
        )(x3, cs)

    out = jax.vmap(run)(xt)
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)


def _rope_fwd(x, cos, sin):
    return fused_rope(x, cos, sin), (x, cos, sin)


def _rope_bwd(res, g):
    x, cos, sin = res
    _, vjp = jax.vjp(rope_ref, x, cos, sin)
    return vjp(g)


fused_rope.defvjp(_rope_fwd, _rope_bwd)


# cost models registered for the autotune search's roofline pruning and
# the graph-cost-table lint's replay (see ops/pallas/autotune.py)
def _register_cost_models():
    from . import autotune

    autotune.register_cost_model(
        "rms_norm", functools.partial(_norm_cost, n_io=2))
    autotune.register_cost_model(
        "add_rms_norm", functools.partial(_norm_cost, n_io=4))


_register_cost_models()
