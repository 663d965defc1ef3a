"""Mixture-of-Experts with expert parallelism (EP).

Reference parity (SURVEY.md §2.7 "EP"):
- ``MoELayer``: python/paddle/incubate/distributed/models/moe/moe_layer.py:263
- gates: python/paddle/incubate/distributed/models/moe/gate/
  {naive_gate,gshard_gate,switch_gate}.py
- count/capacity ops: python/paddle/incubate/distributed/models/moe/utils.py
  (count_by_gate, limit_by_capacity, prune_gate_by_capacity)
- global_scatter/global_gather: python/paddle/distributed/utils/moe_utils.py:20,153
- SPMD rule: paddle/phi/infermeta/spmd_rules/moe_gate_dispatch.cc
- fused grouped-GEMM path: paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu

TPU-native design (SURVEY.md §7 step 8). The reference routes tokens with a
sort + variable-length NCCL alltoall (``global_scatter``). That shape-dynamic
pattern defeats XLA, so dispatch here is the dense GShard formulation:
a capacity-``C`` one-hot dispatch tensor ``[S, E, C]`` and combine tensor of
the same shape, applied with einsums — static shapes, MXU-friendly grouped
matmuls, and when the expert dim is sharded over mesh axes (``moe_group``)
GSPMD materialises exactly the expert-parallel all_to_all the reference
issues by hand. Experts are authored in the GLOBAL view (all ``E`` experts
constructed once, sharded by annotation) rather than per-rank construction.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..nn.layer import Layer
from ..nn.initializer_core import XavierUniform, Constant
from ..tensor_class import wrap, unwrap
from .collective import Group
from .topology import get_hybrid_communicate_group


# --------------------------------------------------------------------------
# capacity / counting primitives (parity: moe/utils.py ops, as pure jnp fns)
# --------------------------------------------------------------------------

def expert_count(gate_idx, n_expert: int):
    """Tokens assigned per expert. Parity: number_count op
    (moe/utils.py count_by_gate)."""
    gate_idx = unwrap(gate_idx)
    return jnp.sum(jax.nn.one_hot(gate_idx.reshape(-1), n_expert, dtype=jnp.int32), axis=0)


def limit_by_capacity(expert_counts, capacity: int):
    """Clamp per-expert counts to capacity (moe/utils.py limit_by_capacity)."""
    return jnp.minimum(unwrap(expert_counts), capacity)


def prune_gate_by_capacity(gate_idx, n_expert: int, capacity: int):
    """Replace over-capacity assignments with -1
    (moe/utils.py prune_gate_by_capacity)."""
    gate_idx = unwrap(gate_idx)
    flat = gate_idx.reshape(-1)
    onehot = jax.nn.one_hot(flat, n_expert, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1  # position of each token within its expert
    mypos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    pruned = jnp.where(mypos < capacity, flat, -1)
    return pruned.reshape(gate_idx.shape)


def compute_capacity(num_tokens: int, num_experts: int, top_k: int,
                     capacity_factor: float) -> int:
    cap = int(math.ceil(num_tokens * top_k * capacity_factor / num_experts))
    return max(1, min(cap, num_tokens))


def one_hot_dispatch(probs, topk_idx, capacity: int):
    """Dense GShard dispatch from top-k routing.

    probs: [S, E] softmax router probabilities.
    topk_idx: [S, K] chosen experts per token (priority = batch order,
      matching the reference's cumsum-position semantics in
      prune_gate_by_capacity).
    Returns (combine [S, E, C] float, dispatch [S, E, C] bool).
    """
    S, E = probs.shape
    K = topk_idx.shape[1]
    # vectorized over K (VERDICT r2 item 9): routes ordered k-major —
    # all k=0 routes take expert slots before any k=1 route, matching the
    # loop-with-base-offset (and the reference's cumsum-position semantics)
    mask = jax.nn.one_hot(topk_idx.T, E, dtype=jnp.int32)  # [K, S, E]
    flat = mask.reshape(K * S, E)
    pos = jnp.cumsum(flat, axis=0) - 1                      # [K*S, E]
    keep = flat * (pos < capacity)
    pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity,
                            dtype=probs.dtype)              # [K*S, E, C]
    weights = (keep.astype(probs.dtype).reshape(K, S, E)
               * probs[None])                               # [K, S, E]
    combine = jnp.einsum("kse,ksec->sec", weights,
                         pos_oh.reshape(K, S, E, capacity))
    dispatch = combine > 0
    return combine, dispatch


def load_balance_loss(probs, topk_idx):
    """Switch/GShard auxiliary loss: E * sum_e(mean_prob_e * frac_tokens_e),
    using the top-1 assignment fraction. =1 at perfect balance."""
    E = probs.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(topk_idx[:, 0], E, dtype=probs.dtype), axis=0)
    return E * jnp.sum(me * ce)


# --------------------------------------------------------------------------
# gates
# --------------------------------------------------------------------------

class BaseGate(Layer):
    """Router base (gate/base_gate.py). ``num_expert`` is the per-rank count
    in the reference; total experts = num_expert * world_size. Here experts
    are global, so tot_expert is the routing width."""

    def __init__(self, num_expert: int, world_size: int = 1):
        super().__init__()
        self.world_size = world_size
        self.num_expert = num_expert
        self.tot_expert = num_expert * world_size
        self.loss = None

    def set_loss(self, loss):
        self.loss = loss

    def get_loss(self, clear: bool = True):
        loss = self.loss
        if clear:
            self.loss = None
        return loss

    def dispatch(self, x_flat):  # pragma: no cover - abstract
        raise NotImplementedError


class NaiveGate(BaseGate):
    """Plain top-k softmax router, no capacity drop (gate/naive_gate.py).

    Routing runs through :func:`~paddle_tpu.ops.registry.apply` as one pure
    stage so the eager tape differentiates through the combine weights."""

    def __init__(self, d_model: int, num_expert: int, world_size: int = 1, topk: int = 2,
                 capacity_factor: Optional[float] = 2.0):
        super().__init__(num_expert, world_size)
        self.d_model = d_model
        self.top_k = topk
        # Default 2.0 bounds the dispatch tensors at O(S*K*factor*M)
        # (VERDICT r2 item 9: C = S by default is quadratic in tokens).
        # Pass capacity_factor=None to opt IN to the reference's strict
        # no-drop semantics (C = S) for small-S correctness work.
        self.capacity_factor = capacity_factor
        self.gate_weight = self.create_parameter(
            [d_model, self.tot_expert], default_initializer=XavierUniform())
        self.gate_bias = self.create_parameter(
            [self.tot_expert], default_initializer=Constant(0.0), is_bias=True)

    # -- pure routing stage (x, w, b, key are raw arrays) ------------------
    def _route(self, x, w, b, key, training):
        probs = jax.nn.softmax((x @ w + b).astype(jnp.float32), axis=-1)
        _, topk_idx = jax.lax.top_k(probs, self.top_k)
        if self.capacity_factor is None:
            cap = x.shape[0]  # no drop
        else:
            cap = compute_capacity(x.shape[0], self.tot_expert, self.top_k,
                                   self.capacity_factor)
        combine, disp = one_hot_dispatch(probs, topk_idx, cap)
        aux = jnp.zeros((), jnp.float32)
        return (combine.astype(x.dtype),
                jax.lax.stop_gradient(disp.astype(x.dtype)), aux)

    def dispatch(self, x_flat):
        """x_flat: Tensor [S, M] → (combine [S,E,C], dispatch_f [S,E,C])."""
        from ..ops.registry import apply

        key = self._routing_key()
        combine, disp, aux = apply(
            "moe_gate", self._route, x_flat, self.gate_weight, self.gate_bias,
            key, training=self.training)
        self.set_loss(aux)
        return combine, disp

    def _routing_key(self):
        return None


class SwitchGate(NaiveGate):
    """Top-1 router with capacity + training jitter (gate/switch_gate.py)."""

    def __init__(self, d_model, num_expert, world_size: int = 1, topk: int = 1,
                 switch_eps: float = 0.1, capacity: Sequence[float] = (1.2, 2.4)):
        assert topk == 1, "switch gate is top-1"
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.switch_eps = switch_eps
        self.capacity = capacity  # (train_factor, eval_factor)

    def _routing_key(self):
        if self.training and self.switch_eps > 0:
            from ..framework.random import next_key

            return next_key()
        return None

    def _route(self, x, w, b, key, training):
        logits = (x @ w + b).astype(jnp.float32)
        if key is not None:
            noise = jax.random.uniform(
                key, logits.shape,
                minval=1.0 - self.switch_eps, maxval=1.0 + self.switch_eps)
            logits = logits + jnp.log(noise)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_idx = jnp.argmax(probs, axis=-1)[:, None]
        factor = self.capacity[0] if training else self.capacity[1]
        cap = compute_capacity(x.shape[0], self.tot_expert, 1, factor)
        combine, disp = one_hot_dispatch(probs, topk_idx, cap)
        aux = load_balance_loss(probs, topk_idx)
        return (combine.astype(x.dtype),
                jax.lax.stop_gradient(disp.astype(x.dtype)), aux)


class GShardGate(NaiveGate):
    """Top-2 router with capacity + balance loss (gate/gshard_gate.py)."""

    def __init__(self, d_model, num_expert, world_size: int = 1, topk: int = 2,
                 capacity: Sequence[float] = (1.2, 2.4), random_routing: bool = True):
        assert topk == 2, "gshard gate is top-2"
        super().__init__(d_model, num_expert, world_size, topk=2)
        self.capacity = capacity
        self.random_routing = random_routing

    def _routing_key(self):
        if self.random_routing and self.training:
            from ..framework.random import next_key

            return next_key()
        return None

    def _route(self, x, w, b, key, training):
        probs = jax.nn.softmax((x @ w + b).astype(jnp.float32), axis=-1)
        topk_val, topk_idx = jax.lax.top_k(probs, 2)
        if key is not None:
            # keep 2nd expert with prob 2*gate2 (gshard_gate.py random routing);
            # -1 is the drop sentinel: one_hot(-1) is all-zero, so the route
            # simply vanishes (matches the reference's _random_routing)
            r = jax.random.uniform(key, topk_val[:, 1].shape)
            drop = r >= 2.0 * jax.lax.stop_gradient(topk_val[:, 1])
            topk_idx = topk_idx.at[:, 1].set(
                jnp.where(drop, -1, topk_idx[:, 1]))
        factor = self.capacity[0] if training else self.capacity[1]
        cap = compute_capacity(x.shape[0], self.tot_expert, 2, factor)
        combine, disp = one_hot_dispatch(probs, topk_idx, cap)
        aux = load_balance_loss(probs, topk_idx)
        return (combine.astype(x.dtype),
                jax.lax.stop_gradient(disp.astype(x.dtype)), aux)


# --------------------------------------------------------------------------
# experts
# --------------------------------------------------------------------------

def _act_fn(activation: str):
    if activation == "gelu":  # exact erf gelu (paddle F.gelu default)
        return lambda v: jax.nn.gelu(v, approximate=False)
    return getattr(jax.nn, activation)


def _expert_act(z, activation: str):
    """Hidden activation of the expert FFN. ``"swiglu"`` reads z as the
    FUSED gate‖up projection output ([..., 2*hid] — the LLM-expert form:
    DeepSeekMoE/Qwen2-MoE/ERNIE experts are silu(x@Wg) * (x@Wu) @ Wd);
    the one definition serves the padded ([E, C, M]) and ragged paths."""
    if activation == "swiglu":
        g, u = jnp.split(z, 2, axis=-1)
        return jax.nn.silu(g) * u
    return _act_fn(activation)(z)


def _grouped_ffn(xe, w1, b1, w2, b2, activation: str):
    """[E, C, M] grouped FFN on raw arrays — shared by the Layer forward
    and the tape-recorded apply() path."""
    h = _expert_act(jnp.einsum("ecm,emh->ech", xe, w1) + b1, activation)
    return jnp.einsum("ech,ehm->ecm", h, w2) + b2


def dropless_expert_ffn(tokens, topk_idx, topk_w, w1, b1, w2, b2,
                        activation: str, held=None, valid=None):
    """The routed experts' output for EVERY (token, expert) pair whose
    expert this device holds: nothing is dropped and no capacity exists.

    tokens [T, M]; topk_idx / topk_w [T, K] (weights in f32, already
    normalised over ALL K choices); w1 [H, M, F1], w2 [H, F, M] are the
    ``H`` held experts' stacked weights, ``held = (lo, hi)`` their range in
    the routing width (None: all of ``w1``'s). ``valid`` [T] marks the rows
    whose output is read (a right-padded prefill passes its real rows):
    the others route nowhere.

    The T x K pairs are sorted by expert, absent and invalid pairs last,
    and the held ones run through one grouped matmul per projection
    (``jax.lax.ragged_dot``: XLA's own on every backend; measured against
    the bundled megablox ``gmm`` on the v5e at 8192 and 32 tokens, PERF.md
    PR 35) in chunks of a STATIC number of rows, a chunk past the last held pair
    taking an empty branch: the work and the live memory
    follow the pairs this device owns (T x K x held / experts in
    expectation), never the T x K bound, and a pair of an absent expert
    costs its place in the sort. Returns (out [T, M] in tokens' dtype,
    counts [H] int32: pairs per held expert)."""
    T, K = topk_idx.shape
    M = tokens.shape[-1]
    n_held = w1.shape[0]
    lo = 0 if held is None else int(held[0])
    local = topk_idx.astype(jnp.int32) - lo
    is_held = (local >= 0) & (local < n_held)
    if valid is not None:
        is_held = is_held & valid[:, None]
    P = T * K
    key = jnp.where(is_held, local, n_held).reshape(P)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)   # sorted -> pair
    place = jnp.argsort(order).astype(jnp.int32)              # pair -> sorted
    counts = jnp.sum(key[:, None] == jnp.arange(n_held, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    n_pairs = ends[-1]
    # a chunk holds every pair of a decode step; a long prefill's pairs
    # (about one a real token here) take a chunk or two of T rows
    R = min(P, max(T, 256))
    n_chunks = -(-P // R)
    b1r, b2r = b1[:, 0], b2[:, 0]
    # padded so that the last chunk's slice never clamps
    order = jnp.pad(order, (0, n_chunks * R - P))

    def chunk(i, ys):
        base = i * R
        rows = base + jnp.arange(R, dtype=jnp.int32)
        pair = jax.lax.dynamic_slice_in_dim(order, base, R)
        xs = tokens[pair // K]
        gs = (jnp.clip(ends, base, base + R)
              - jnp.clip(starts, base, base + R)).astype(jnp.int32)
        expert = jnp.clip(jnp.searchsorted(ends, rows, side="right"),
                          0, n_held - 1)
        h = _expert_act(jax.lax.ragged_dot(xs, w1, gs) + b1r[expert],
                        activation)
        y = jax.lax.ragged_dot(h.astype(xs.dtype), w2, gs) + b2r[expert]
        return jax.lax.dynamic_update_slice_in_dim(
            ys, y.astype(ys.dtype), base, 0)

    # static bounds (a scan: training differentiates through it); a chunk
    # past the last held pair runs the empty branch
    ys = jax.lax.fori_loop(
        0, n_chunks,
        lambda i, ys: jax.lax.cond(i * R < n_pairs, chunk,
                                   lambda _, ys: ys, i, ys),
        jnp.zeros((n_chunks * R, M), tokens.dtype))
    place = place.reshape(T, K)
    out = None
    for j in range(K):
        y = ys[place[:, j]].astype(jnp.float32)
        y = jnp.where(is_held[:, j, None], topk_w[:, j, None] * y, 0.0)
        out = y if out is None else out + y
    return out.astype(tokens.dtype), counts


class GroupedMLP(Layer):
    """All E experts' FFN weights stacked on a leading expert dim — the
    grouped-GEMM formulation (parity: fused_moe cutlass grouped GEMM,
    paddle/phi/kernels/fusion/cutlass/cutlass_kernels/moe_gemm/). One einsum
    per projection keeps the MXU busy across experts and lets the expert dim
    be sharded for EP."""

    def __init__(self, num_experts: int, d_model: int, d_hidden: int,
                 activation: str = "gelu"):
        super().__init__()
        self.num_experts = num_experts
        self.d_model, self.d_hidden = d_model, d_hidden
        self.activation = activation
        # swiglu experts fuse gate‖up into one [E, M, 2*hid] projection
        # (one grouped GEMM instead of two)
        fan1 = d_hidden * (2 if activation == "swiglu" else 1)
        # per-expert fans: the stacked [E, in, out] layout would otherwise be
        # read as conv-style (E*out receptive) by Initializer._fan
        self.w1 = self.create_parameter(
            [num_experts, d_model, fan1],
            default_initializer=XavierUniform(fan_in=d_model, fan_out=d_hidden))
        self.b1 = self.create_parameter(
            [num_experts, 1, fan1], default_initializer=Constant(0.0), is_bias=True)
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model],
            default_initializer=XavierUniform(fan_in=d_hidden, fan_out=d_model))
        self.b2 = self.create_parameter(
            [num_experts, 1, d_model], default_initializer=Constant(0.0), is_bias=True)

    def forward_expert_batch(self, xe):
        """xe: [E, C, M] → [E, C, M]."""
        return _grouped_ffn(xe, unwrap(self.w1), unwrap(self.b1),
                            unwrap(self.w2), unwrap(self.b2), self.activation)

    def forward(self, x):
        return wrap(self.forward_expert_batch(unwrap(x)))


def default_ep_axes(num_experts: int):
    """The hybrid topology's data axes (dp, sharding) whose joint degree
    divides ``num_experts`` — the default expert-parallel placement (the
    reference's moe group defaults to the data-parallel communicator)."""
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return ()
    axes = tuple(a for a in ("dp", "sharding")
                 if hcg.mesh.get_dim_size(a) > 1)
    if axes and num_experts % np.prod(
            [hcg.mesh.get_dim_size(a) for a in axes]) == 0:
        return axes
    return ()


def ep_constrain(arr, axes, expert_sharded: bool = True):
    """Sharding constraint on a dispatched [E, C, M]-style block so GSPMD
    inserts the EP all_to_all at the dispatch/combine boundary. No-op in
    eager mode (the constraint only means something under tracing) or when
    no hybrid mesh / axes are active."""
    hcg = get_hybrid_communicate_group()
    axes = tuple(axes or ())
    if hcg is None or not axes or not isinstance(arr, jax.core.Tracer):
        return arr
    spec = [None] * arr.ndim
    if expert_sharded:
        spec[0] = axes if len(axes) > 1 else axes[0]
    return jax.lax.with_sharding_constraint(
        arr, NamedSharding(hcg.mesh.jax_mesh(), PartitionSpec(*spec)))


def shard_grouped_experts(experts: "GroupedMLP", axes) -> tuple:
    """EP placement: shard a GroupedMLP's expert dim over mesh ``axes``
    (a multi-axis Shard when several axes fold together). Returns the axes
    applied (() when no hybrid mesh / empty axes)."""
    hcg = get_hybrid_communicate_group()
    axes = tuple(axes or ())
    if hcg is None or not axes:
        return ()
    mesh = hcg.mesh
    for name in ("w1", "b1", "w2", "b2"):
        p = getattr(experts, name)
        spec = [None] * len(p.shape)
        spec[0] = axes if len(axes) > 1 else axes[0]
        p._array = jax.device_put(
            unwrap(p), NamedSharding(mesh.jax_mesh(), PartitionSpec(*spec)))
    return axes


class MoELayer(Layer):
    """Mixture-of-experts layer (moe_layer.py:263).

    Args mirror the reference: ``experts`` is either a :class:`GroupedMLP`
    (preferred — grouped GEMM + EP sharding) or a list of per-expert Layers
    (looped; kept for API parity with arbitrary expert modules).
    ``moe_group`` names the mesh axes the expert dim is sharded over (the
    reference's NCCL moe group); default: the hybrid topology's data axes.
    """

    def __init__(self, d_model: int, experts, gate=None,
                 moe_group: Optional[Group] = None, mp_group=None,
                 recompute_interval: int = 0, top_k: int = 2):
        super().__init__()
        self.d_model = d_model
        if isinstance(experts, GroupedMLP):
            self.experts = experts
            num_experts = experts.num_experts
        else:
            from ..nn.container import LayerList

            if not isinstance(experts, Layer):
                experts = LayerList(list(experts))  # materialize iterables once
            self.experts = experts
            num_experts = len(list(experts))
        self.num_experts = num_experts
        if gate is None:
            gate = NaiveGate(d_model, num_experts, topk=top_k)
        elif isinstance(gate, dict):
            kind = gate.get("type", "naive")
            cls = {"naive": NaiveGate, "gshard": GShardGate, "switch": SwitchGate}[kind]
            kwargs = {k: v for k, v in gate.items() if k != "type"}
            kwargs.setdefault("topk", 1 if kind == "switch" else 2)
            gate = cls(d_model, num_experts, **kwargs)
        self.gate = gate
        self.recompute_interval = recompute_interval
        self.activation_name = (experts.activation
                                if isinstance(experts, GroupedMLP) else "gelu")
        self._ep_axes = self._resolve_ep_axes(moe_group)
        if self._ep_axes and isinstance(self.experts, GroupedMLP):
            self._shard_experts()

    # -- EP sharding -------------------------------------------------------
    def _resolve_ep_axes(self, moe_group):
        if isinstance(moe_group, (Group, tuple, list)):
            if isinstance(moe_group, Group):
                axes, mesh = tuple(moe_group.axis_names), moe_group.mesh
            else:
                axes = tuple(moe_group)
                hcg = get_hybrid_communicate_group()
                mesh = hcg.mesh if hcg is not None else None
            if mesh is not None and axes:
                ep = int(np.prod([mesh.get_dim_size(a) for a in axes]))
                if self.num_experts % ep != 0:
                    raise ValueError(
                        f"num_experts={self.num_experts} must be divisible by "
                        f"EP degree {ep} (moe_group axes {axes})")
            return axes
        if moe_group is None:
            axes = default_ep_axes(self.num_experts)
            if axes:
                return axes
        return ()

    def _shard_experts(self):
        shard_grouped_experts(self.experts, self._ep_axes)

    def _constrain(self, arr, expert_sharded: bool):
        return ep_constrain(arr, self._ep_axes, expert_sharded)

    # -- forward -----------------------------------------------------------
    def _dispatch_fn(self, x_flat, dispatch):
        # [S,M] x [S,E,C] -> [E,C,M]  (the reference's MoEScatter+global_scatter)
        xe = jnp.einsum("sm,sec->ecm", x_flat, dispatch.astype(x_flat.dtype))
        return self._constrain(xe, expert_sharded=True)

    def _expert_ffn_fn(self, xe, w1, b1, w2, b2):
        ffn = lambda v: _grouped_ffn(v, w1, b1, w2, b2, self.activation_name)
        if self.recompute_interval > 0:
            ffn = jax.checkpoint(ffn)
        return self._constrain(ffn(xe), expert_sharded=True)

    def _combine_fn(self, ye, combine):
        # [E,C,M] x [S,E,C] -> [S,M]  (MoEGather+global_gather)
        return jnp.einsum("ecm,sec->sm", ye, combine.astype(ye.dtype))

    def forward(self, x):
        from ..ops.registry import apply

        orig_shape = tuple(x.shape)
        x_flat = apply("reshape", lambda a: a.reshape(-1, self.d_model), x)
        combine, dispatch = self.gate.dispatch(x_flat)
        xe = apply("moe_dispatch", self._dispatch_fn, x_flat, dispatch)
        if isinstance(self.experts, GroupedMLP):
            g = self.experts
            ye = apply("moe_expert_ffn", self._expert_ffn_fn, xe,
                       g.w1, g.b1, g.w2, g.b2)
        else:
            outs = [expert(xe[e]) for e, expert in enumerate(self.experts)]
            ye = apply("stack", lambda *a: jnp.stack(a, axis=0), *outs)
        y = apply("moe_combine", self._combine_fn, ye, combine)
        return apply("reshape", lambda a: a.reshape(orig_shape), y)


# --------------------------------------------------------------------------
# eager global_scatter / global_gather (moe_utils.py:20,153)
# --------------------------------------------------------------------------

def _counts_to_np(c):
    return np.asarray(unwrap(c)).astype(np.int64)


def global_scatter(x, local_count, global_count, group=None, use_calc_stream=True):
    """Reference-semantics expert exchange (moe_utils.py:20) in the global
    view. ``x``: [world, local_batch, M] stacked per-rank token buffers, each
    rank's tokens ordered by destination index i = dest_rank * n_expert +
    expert; ``local_count``: [world, world * n_expert]; ``global_count``:
    [world, world * n_expert] (i = src_rank * n_expert + expert). Output:
    [world, out_batch, M] where each rank's buffer is ordered expert-major
    then source-rank (the layout the reference's recv loop produces),
    zero-padded to the max recv count.

    This is an EAGER data-movement utility for API parity/testing; the
    jit/production path is MoELayer's dense dispatch (see module docstring).
    """
    xg = np.asarray(unwrap(x))
    lc, gc = _counts_to_np(local_count), _counts_to_np(global_count)
    world, _, M = xg.shape
    n_expert = lc.shape[1] // world
    # start offset of segment i in each source rank's buffer
    starts = np.concatenate([np.zeros((world, 1), np.int64), np.cumsum(lc, axis=1)], axis=1)
    out_batch = int(gc.sum(axis=1).max()) if gc.size else 0
    out = np.zeros((world, out_batch, M), xg.dtype)
    for dst in range(world):
        off = 0
        for e in range(n_expert):
            for src in range(world):
                cnt = int(lc[src, dst * n_expert + e])
                s = int(starts[src, dst * n_expert + e])
                out[dst, off:off + cnt] = xg[src, s:s + cnt]
                off += cnt
    return wrap(jnp.asarray(out))


def global_gather(x, local_count, global_count, group=None, use_calc_stream=True):
    """Inverse of :func:`global_scatter` (moe_utils.py:153): routes expert
    outputs back to the token owners, restoring each rank's original
    local-buffer order."""
    xg = np.asarray(unwrap(x))
    lc, gc = _counts_to_np(local_count), _counts_to_np(global_count)
    world, _, M = xg.shape
    n_expert = lc.shape[1] // world
    starts = np.concatenate([np.zeros((world, 1), np.int64), np.cumsum(lc, axis=1)], axis=1)
    out_batch = int(lc.sum(axis=1).max()) if lc.size else 0
    out = np.zeros((world, out_batch, M), xg.dtype)
    # walk the scattered layout in the same order global_scatter wrote it
    for dst in range(world):
        off = 0
        for e in range(n_expert):
            for src in range(world):
                cnt = int(lc[src, dst * n_expert + e])
                s = int(starts[src, dst * n_expert + e])
                out[src, s:s + cnt] = xg[dst, off:off + cnt]
                off += cnt
    return wrap(jnp.asarray(out))
