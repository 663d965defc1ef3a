"""Megatron-style tensor/sequence-parallel layers.

Reference parity: fleet/layers/mpu/mp_layers.py (VocabParallelEmbedding :49,
ColumnParallelLinear :336, RowParallelLinear :543, ParallelCrossEntropy :744)
and fleet/utils/sequence_parallel_utils.py (Column/RowSequenceParallelLinear
:429,564).

TPU-native: instead of manual collectives, each layer (a) creates its weight
pre-sharded on the mp axis of the hybrid mesh and (b) constrains its
activations' shardings. GSPMD then inserts exactly the Megatron
communication pattern: column-parallel = no comm fwd / allreduce bwd,
row-parallel = allreduce fwd, sequence-parallel boundaries = allgather /
reduce_scatter — this is the whole point of the architecture mapping
(SURVEY.md §7: "HybridCommunicateGroup → one Mesh with named axes").
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..nn.layer import Layer
from ..nn.initializer_core import XavierNormal, Constant
from ..ops.registry import apply
from ..tensor_class import Tensor, unwrap, wrap
from .process_mesh import ProcessMesh
from .placements import Shard, Replicate
from .api import shard_tensor
from .topology import get_hybrid_communicate_group


def _mp_mesh():
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return None
    return hcg.mesh


def _constraint(arr, mesh: ProcessMesh, **dims):
    """Constrain a traced activation on the dims a tensor-parallel layer
    owns — ``last=`` the feature dim, ``seq=`` dim 1 — to a mesh axis
    name or ``None`` (gathered). Every other dim stays UNCONSTRAINED:
    the batch dim belongs to the dp/sharding axes, and a ``None`` there
    would all-gather it on every linear. Eager arrays pass through
    (their layout is whatever the eager ops produced)."""
    if not isinstance(arr, jax.core.Tracer):
        return arr
    spec = [PartitionSpec.UNCONSTRAINED] * arr.ndim
    if "last" in dims:
        spec[-1] = dims["last"]
    if "seq" in dims:
        spec[1] = dims["seq"]
    return jax.lax.with_sharding_constraint(
        arr, NamedSharding(mesh.jax_mesh(), PartitionSpec(*spec)))


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dim sharded over mp (mp_layers.py:49)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None, mp_group=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=XavierNormal())
        mesh = _mp_mesh()
        if mesh is not None and num_embeddings % mesh.get_dim_size("mp") == 0:
            placements = [Replicate()] * mesh.ndim
            placements[mesh.dim_names.index("mp")] = Shard(0)
            self.weight = shard_tensor(self.weight, mesh, placements)

    def forward(self, x):
        from ..nn.functional.common import embedding

        return embedding(x, self.weight)


class ColumnParallelLinear(Layer):
    """Linear with the OUT dim sharded over mp (mp_layers.py:336). Weight
    layout [in, out] (paddle convention); gather_output re-replicates."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 gather_output=True, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features, self.out_features = in_features, out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter([in_features, out_features], attr=weight_attr,
                                            default_initializer=XavierNormal())
        self.bias = self.create_parameter([out_features], is_bias=True) if has_bias else None
        self._mesh = _mp_mesh()
        if self._mesh is not None:
            mp_dim = self._mesh.dim_names.index("mp")
            wp = [Replicate()] * self._mesh.ndim
            wp[mp_dim] = Shard(1)
            self.weight = shard_tensor(self.weight, self._mesh, wp)
            if self.bias is not None:
                bp = [Replicate()] * self._mesh.ndim
                bp[mp_dim] = Shard(0)
                self.bias = shard_tensor(self.bias, self._mesh, bp)

    def forward(self, x):
        mesh = self._mesh

        def fn(a, w, *b):
            out = a @ w
            if b:
                out = out + b[0]
            if mesh is not None:
                out = _constraint(out, mesh, last="mp")
                if self.gather_output:
                    out = _constraint(out, mesh, last=None)
            return out

        args = [x, self.weight] + ([self.bias] if self.bias is not None else [])
        return apply("column_parallel_linear", fn, *args)


class RowParallelLinear(Layer):
    """Linear with the IN dim sharded over mp (mp_layers.py:543): local matmul
    over the input shard, then (GSPMD-inserted) allreduce."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 input_is_parallel=False, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features, self.out_features = in_features, out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter([in_features, out_features], attr=weight_attr,
                                            default_initializer=XavierNormal())
        self.bias = self.create_parameter([out_features], is_bias=True) if has_bias else None
        self._mesh = _mp_mesh()
        if self._mesh is not None:
            mp_dim = self._mesh.dim_names.index("mp")
            wp = [Replicate()] * self._mesh.ndim
            wp[mp_dim] = Shard(0)
            self.weight = shard_tensor(self.weight, self._mesh, wp)

    def forward(self, x):
        mesh = self._mesh

        def fn(a, w, *b):
            if mesh is not None:
                a = _constraint(a, mesh, last="mp")
            out = a @ w
            if mesh is not None:
                out = _constraint(out, mesh, last=None)
            if b:
                out = out + b[0]
            return out

        args = [x, self.weight] + ([self.bias] if self.bias is not None else [])
        return apply("row_parallel_linear", fn, *args)


class ColumnSequenceParallelLinear(ColumnParallelLinear):
    """Megatron-SP column linear (sequence_parallel_utils.py:429): input
    arrives sequence-sharded on mp; an allgather precedes the matmul.
    Expressed as sharding constraints: in [B, S/mp, H] → gather → matmul →
    out [B, S, H/mp]."""

    def forward(self, x):
        mesh = self._mesh

        def fn(a, w, *b):
            if mesh is not None:
                # sequence-sharded input → gather to full sequence
                a = _constraint(a, mesh, seq="mp", last=None)
                a = _constraint(a, mesh, seq=None, last=None)
            out = a @ w
            if b:
                out = out + b[0]
            if mesh is not None:
                out = _constraint(out, mesh, last="mp")
            return out

        args = [x, self.weight] + ([self.bias] if self.bias is not None else [])
        return apply("column_seq_parallel_linear", fn, *args)


class RowSequenceParallelLinear(RowParallelLinear):
    """Megatron-SP row linear (sequence_parallel_utils.py:564): output leaves
    sequence-sharded (reduce_scatter instead of allreduce)."""

    def forward(self, x):
        mesh = self._mesh

        def fn(a, w, *b):
            if mesh is not None:
                a = _constraint(a, mesh, last="mp")
            out = a @ w
            if mesh is not None:
                # reduce_scatter onto the sequence dim
                out = _constraint(out, mesh, seq="mp", last=None)
            if b:
                out = out + b[0]
            return out

        args = [x, self.weight] + ([self.bias] if self.bias is not None else [])
        return apply("row_seq_parallel_linear", fn, *args)


class ParallelCrossEntropy(Layer):
    """Vocab-parallel softmax CE (mp_layers.py:744 wrapping
    c_softmax_with_cross_entropy): logits arrive vocab-sharded; under GSPMD
    the standard CE graph compiles to the same partial-softmax + allreduce
    pattern, so the implementation is the plain loss with a constraint."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index
        self._mesh = _mp_mesh()

    def forward(self, input, label):
        from ..nn.functional.loss import cross_entropy

        mesh = self._mesh
        if mesh is not None:
            def fn(a):
                return _constraint(a, mesh, last="mp")

            input = apply("vocab_shard_constraint", fn, input)
        return cross_entropy(input, label, reduction="none", ignore_index=self.ignore_index)


# eager helpers kept for API parity with sequence_parallel_utils.py

def mark_as_sequence_parallel_parameter(parameter):
    parameter._sequence_parallel = True  # consumed by grad-sync hooks


class GatherOp:
    """PyLayer-parity namespace: functional gather over the sep/mp axis."""

    @staticmethod
    def apply(x, axis=1):
        attr = getattr(x, "_dist_attr", None)
        if attr is None:
            return x
        from .api import reshard

        new_p = [Replicate() if isinstance(p, Shard) and p.dim == axis else p
                 for p in attr.placements]
        return reshard(x, attr.mesh, new_p)


class ScatterOp:
    @staticmethod
    def apply(x, axis=1):
        hcg = get_hybrid_communicate_group()
        if hcg is None:
            return x
        mesh = hcg.mesh
        placements = [Replicate()] * mesh.ndim
        placements[mesh.dim_names.index("mp")] = Shard(axis)
        from .api import reshard

        return reshard(x, mesh, placements)


AllGatherOp = GatherOp
ReduceScatterOp = ScatterOp
