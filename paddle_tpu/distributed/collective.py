"""Communication API: groups + collectives.

Reference parity: paddle.distributed.{all_reduce, all_gather, all_to_all,
reduce_scatter, broadcast, scatter, send/recv} + Group/new_group
(python/paddle/distributed/communication/, group.py:29) over
ProcessGroupNCCL (paddle/fluid/distributed/collective/process_group_nccl.h).

TPU-native design (SURVEY.md §5 "Distributed communication backend"): there
is no eager per-rank communicator — collectives are XLA ops (psum/all_gather/
ppermute/all_to_all) compiled over mesh axes inside jit/shard_map. This
module provides:

- ``Group``: a view over one axis (or sub-axes) of a ProcessMesh — the analog
  of a NCCL communicator ring;
- eager collective functions with paddle signatures that operate on
  *sharded global arrays*: e.g. ``all_gather`` materialises every shard,
  ``all_reduce`` sums a Partial dist tensor. They jit tiny shard_map programs
  on first use (cached), which is exactly "a thin eager collective facade
  over jitted collectives" (SURVEY §7 mapping);
- in-graph collective helpers (psum/all_to_all/ppermute wrappers) for use
  inside shard_map'd model code (sequence/expert parallel paths).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from jax import shard_map  # re-exported: in-repo sites import it from here

from ..observability import flightrecorder as _frec
from ..tensor_class import Tensor, unwrap, wrap
from .process_mesh import ProcessMesh
from .placements import Replicate, Shard, Partial


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A collective group = one (or several fused) mesh axes.

    Parity: paddle Group (communication/group.py:29) / HybridCommunicateGroup's
    per-axis groups (topology.py). ``rank``/``nranks`` follow the calling
    process's coordinates when multi-process, else mesh-local semantics.
    """

    def __init__(self, mesh: ProcessMesh, axis_names: Sequence[str], id: int = 0):
        self.mesh = mesh
        self.axis_names = tuple(axis_names) if not isinstance(axis_names, str) else (axis_names,)
        self.id = id

    @property
    def nranks(self) -> int:
        n = 1
        for a in self.axis_names:
            n *= self.mesh.get_dim_size(a)
        return n

    @property
    def world_size(self):
        return self.nranks

    @property
    def rank(self) -> int:
        try:
            return jax.process_index() % self.nranks
        except RuntimeError:  # pragma: no cover — backend not initialized
            return 0

    @property
    def ranks(self) -> List[int]:
        return list(range(self.nranks))

    def get_group_rank(self, rank):
        return rank % self.nranks

    def __repr__(self):
        return f"Group(axes={self.axis_names}, nranks={self.nranks})"


_default_group: list = [None]


def _ensure_default_group() -> Group:
    if _default_group[0] is None:
        import numpy as np

        n = jax.device_count()
        mesh = ProcessMesh(np.arange(n), ["world"])
        _default_group[0] = Group(mesh, ["world"])
    return _default_group[0]


def new_group(ranks=None, backend=None, timeout=None) -> Group:
    """Parity shim: groups are mesh-axis views; arbitrary rank subsets map to
    a sub-mesh over those device ids."""
    import numpy as np

    if ranks is None:
        return _ensure_default_group()
    mesh = ProcessMesh(np.asarray(sorted(ranks)), ["sub"])
    return Group(mesh, ["sub"], id=len(ranks))


def get_group(id=0) -> Group:
    return _ensure_default_group()


def _axis(group: Optional[Group]):
    g = group or _ensure_default_group()
    return g.mesh.jax_mesh(), g.axis_names


@functools.lru_cache(maxsize=256)
def _collective_fn(kind, mesh, axes, spec_in, spec_out, extra=None):
    if kind == "allreduce_sum":
        f = lambda x: jax.lax.psum(x, axes)
    elif kind == "allreduce_max":
        f = lambda x: jax.lax.pmax(x, axes)
    elif kind == "allreduce_min":
        f = lambda x: jax.lax.pmin(x, axes)
    elif kind == "allreduce_avg":
        f = lambda x: jax.lax.pmean(x, axes)
    elif kind == "allgather":
        f = lambda x: jax.lax.all_gather(x, axes[0], axis=0, tiled=True)
    elif kind == "reduce_scatter":
        f = lambda x: jax.lax.psum_scatter(x, axes[0], scatter_dimension=0, tiled=True)
    elif kind == "alltoall":
        f = lambda x: jax.lax.all_to_all(x, axes[0], split_axis=0, concat_axis=0, tiled=True)
    elif kind == "ppermute":
        perm = list(extra)
        f = lambda x: jax.lax.ppermute(x, axes[0], perm)
    else:  # pragma: no cover
        raise ValueError(kind)
    return jax.jit(shard_map(f, mesh=mesh, in_specs=(spec_in,), out_specs=spec_out))


def _multiprocess() -> bool:
    try:
        return jax.process_count() > 1
    except RuntimeError:  # pragma: no cover — backend not initialized
        return False


@functools.lru_cache(maxsize=8)
def _process_mesh():
    """A (proc, dlocal) mesh whose first axis is exactly one row per
    PROCESS — eager ProcessGroup semantics rank = process, regardless of
    how many local devices each process owns (multi-host TPU topology)."""
    from jax.sharding import Mesh
    import numpy as np

    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    p = jax.process_count()
    local = len(devs) // p
    grid = np.array(devs).reshape(p, local)
    return Mesh(grid, ("proc", "dlocal"))


def _static_check(arr, op_name: str):
    """Cross-process shape/dtype agreement check before an eager collective
    (static_check.cc CheckShape/CheckDataType parity), behind
    FLAGS_collective_static_check — a desync here otherwise surfaces as a
    hang or garbage reduction."""
    from ..utils.flags import flag

    if not flag("FLAGS_collective_static_check"):
        return
    import numpy as np
    from jax.experimental import multihost_utils

    # rank-invariant descriptor (padded to MAX_DIMS): if ranks disagreed on
    # ndim a variable-length descriptor would wedge the agreement check
    # itself with mismatched gather shapes — the very desync being detected
    MAX_DIMS = 8
    shape = list(arr.shape[:MAX_DIMS]) + [0] * (MAX_DIMS - min(arr.ndim, MAX_DIMS))
    desc = np.array([arr.ndim, np.dtype(arr.dtype).num, *shape], np.int64)
    try:
        multihost_utils.assert_equal(
            desc, f"collective {op_name}: shape/dtype desync across ranks")
    except Exception as e:
        raise RuntimeError(
            f"collective static check failed for {op_name}: ranks disagree "
            f"on shape/dtype ({e})") from None


def _cross_process_reduce(arr, kind):
    """Eager allreduce across PROCESSES: each process contributes its own
    host-local array as one row of a [n_proc, ...] global array sharded
    over the process axis (replicated over that process's local devices);
    a shard_map psum reduces the rows and each process reads back its
    now-fully-reduced slice. This is the eager ProcessGroup semantic
    (process_group_nccl.h AllReduce) expressed as XLA collectives."""
    from jax.experimental import multihost_utils

    mesh = _process_mesh()
    row_spec = PartitionSpec("proc", *([None] * arr.ndim))
    global_arr = multihost_utils.host_local_array_to_global_array(
        arr[None], mesh, row_spec)
    fn = _collective_fn(kind, mesh, ("proc",), row_spec, row_spec)
    out_global = fn(global_arr)
    local = multihost_utils.global_array_to_host_local_array(
        out_global, mesh, row_spec)
    return jnp.asarray(local)[0]


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Reduce a tensor sharded/partial over the group axis; in paddle
    semantics every rank ends with the reduced value (here: the global array
    becomes fully reduced + replicated over the axis)."""
    mesh, axes = _axis(group)
    arr = unwrap(tensor)
    kind = {"sum": "allreduce_sum", "max": "allreduce_max",
            "min": "allreduce_min", "avg": "allreduce_avg"}[op if isinstance(op, str) else "sum"]
    rec = _frec.RECORDER
    if rec.enabled:
        # begin/end pairs in the black box: an incident bundle with an
        # unmatched begin IS the hung collective (comm-task watchdog
        # granularity, recovered at the host boundary)
        import time as _time

        rec.record(_frec.EV_COLLECTIVE_BEGIN, op=kind,
                   multiprocess=_multiprocess())
        t0 = _time.perf_counter()
        try:
            out = _all_reduce_inner(tensor, arr, kind, mesh, axes, group)
        finally:
            rec.record(_frec.EV_COLLECTIVE_END, op=kind,
                       seconds=_time.perf_counter() - t0)
        return out
    return _all_reduce_inner(tensor, arr, kind, mesh, axes, group)


def _all_reduce_inner(tensor, arr, kind, mesh, axes, group):
    if _multiprocess():
        _static_check(arr, "all_reduce")
        if group is not None and group is not _default_group[0]:
            raise NotImplementedError(
                "multi-process eager all_reduce supports only the default "
                "(world) group; sub-group collectives run in-graph via "
                "shard_map over the hybrid mesh axes")
        out = _cross_process_reduce(arr, kind)
    else:
        spec = PartitionSpec(*([None] * arr.ndim))
        fn = _collective_fn(kind, mesh, tuple(axes), spec, spec)
        out = fn(jax.device_put(arr, NamedSharding(mesh, spec)))
    result = wrap(out, tensor.stop_gradient)
    if isinstance(tensor, Tensor):
        tensor._array = result._array
    return result


def _pickle_to_u8(obj):
    import pickle

    return np.frombuffer(pickle.dumps(obj), np.uint8)


def _check_world_group(group, op_name: str):
    """The multi-process object collectives ride process-wide
    multihost_utils primitives; a sub-group would silently widen to the
    world (same guard all_reduce applies)."""
    if group is not None and group is not _default_group[0]:
        raise NotImplementedError(
            f"multi-process {op_name} supports only the default (world) "
            "group")


def all_gather_object(object_list, obj, group=None):
    """paddle.distributed.all_gather_object parity
    (communication/all_gather.py:87): every rank contributes one picklable
    object; the list receives all of them in rank order. Multi-process:
    objects ride pickled uint8 arrays through process_allgather (lengths
    gathered first — payloads are ragged); single-controller: every rank
    IS this process, so the list gets world copies."""
    g = group or _ensure_default_group()
    if _multiprocess():
        _check_world_group(group, "all_gather_object")
        import pickle

        from jax.experimental import multihost_utils

        payload = _pickle_to_u8(obj)
        lens = multihost_utils.process_allgather(
            np.asarray([payload.size], np.int64))
        width = int(lens.max())
        padded = np.zeros((width,), np.uint8)
        padded[: payload.size] = payload
        gathered = multihost_utils.process_allgather(padded)
        object_list.clear()
        object_list.extend(
            pickle.loads(gathered[r, : int(lens[r, 0])].tobytes())
            for r in range(gathered.shape[0]))
        return object_list
    import copy

    object_list.clear()
    # independent copies, matching the multiprocess branch's pickle
    # round-trip: mutating one gathered entry must not alias the rest
    object_list.extend(copy.deepcopy(obj) for _ in range(g.nranks))
    return object_list


def broadcast_object_list(object_list, src=0, group=None):
    """paddle.distributed.broadcast_object_list parity
    (communication/broadcast.py:83): rank ``src``'s objects replace every
    rank's list contents."""
    if _multiprocess():
        _check_world_group(group, "broadcast_object_list")
        import pickle

        from jax.experimental import multihost_utils

        payload = (_pickle_to_u8(list(object_list))
                   if jax.process_index() == src else np.zeros(0, np.uint8))
        n = multihost_utils.broadcast_one_to_all(
            np.asarray([payload.size], np.int64),
            is_source=jax.process_index() == src)
        buf = np.zeros((int(n[0]),), np.uint8)
        buf[: payload.size] = payload
        out = multihost_utils.broadcast_one_to_all(
            buf, is_source=jax.process_index() == src)
        object_list[:] = pickle.loads(np.asarray(out).tobytes())
        return object_list
    return object_list  # single-controller: src's list IS the list


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """paddle.distributed.scatter_object_list parity
    (communication/scatter.py:91): rank r receives ``in_object_list[r]``
    from ``src``."""
    g = group or _ensure_default_group()
    if _multiprocess():
        _check_world_group(group, "scatter_object_list")
        holder = list(in_object_list or [])
        broadcast_object_list(holder, src=src, group=group)
        if len(holder) != jax.process_count():
            raise ValueError(
                f"scatter_object_list: {len(holder)} objects for "
                f"{jax.process_count()} processes")
        out_object_list[:] = [holder[jax.process_index()]]
        return out_object_list
    if in_object_list is not None and len(in_object_list) != g.nranks:
        raise ValueError(
            f"scatter_object_list: {len(in_object_list)} objects for "
            f"{g.nranks} ranks")
    out_object_list[:] = [in_object_list[0]] if in_object_list else []
    return out_object_list


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """paddle.distributed.reduce parity (ops.yaml ``reduce``): the reduced
    value lands on rank ``dst``. Under the single-controller facade the
    reduction is computed as an all_reduce — every rank observes the
    result, a strict superset of the reference contract (which leaves
    non-dst buffers undefined after the call)."""
    return all_reduce(tensor, op=op, group=group, sync_op=sync_op)


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """paddle.distributed.gather parity: rank ``dst`` receives every
    rank's shard (single-controller: the list is filled wherever the
    caller runs, mirroring all_gather's materialization)."""
    return all_gather(gather_list, tensor, group=group, sync_op=sync_op)


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    """Gather shards along the group axis. ``tensor`` is the global sharded
    array; the list receives one tensor per rank position."""
    mesh, axes = _axis(group)
    g = group or _ensure_default_group()
    arr = unwrap(tensor)
    n = g.nranks
    gathered = jax.device_get(arr)  # materialise every shard
    if tensor_list is not None:
        import numpy as np

        parts = np.split(np.asarray(gathered), n, axis=0) if gathered.shape[0] % n == 0 else [gathered] * n
        tensor_list.clear()
        tensor_list.extend(wrap(jnp.asarray(p)) for p in parts)
        return tensor_list
    return wrap(jnp.asarray(gathered))


def reduce_scatter(output, input, op=ReduceOp.SUM, group=None, sync_op=True):
    mesh, axes = _axis(group)
    arr = unwrap(input)
    spec_in = PartitionSpec(*([None] * arr.ndim))
    spec_out = PartitionSpec(axes[0], *([None] * (arr.ndim - 1)))
    fn = _collective_fn("reduce_scatter", mesh, tuple(axes), spec_in, spec_out)
    out = fn(jax.device_put(arr, NamedSharding(mesh, spec_in)))
    res = wrap(out)
    if output is not None:
        output._array = res._array
    return res


def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    mesh, axes = _axis(group)
    arrs = [unwrap(t) for t in in_tensor_list]
    stacked = jnp.concatenate([a[None] if a.ndim == arrs[0].ndim else a for a in arrs], axis=0)
    spec = PartitionSpec(axes[0], *([None] * (stacked.ndim - 1)))
    fn = _collective_fn("alltoall", mesh, tuple(axes), spec, spec)
    out = fn(jax.device_put(stacked, NamedSharding(mesh, spec)))
    parts = jnp.split(jax.device_get(out), len(arrs), axis=0)
    if out_tensor_list is not None:
        out_tensor_list.clear()
        out_tensor_list.extend(wrap(jnp.asarray(p[0] if p.shape[0] == 1 else p)) for p in parts)
    return out_tensor_list


def broadcast(tensor, src=0, group=None, sync_op=True):
    """Under SPMD the global array is already consistent; parity no-op that
    re-commits the value replicated over the group axis."""
    mesh, axes = _axis(group)
    arr = unwrap(tensor)
    spec = PartitionSpec(*([None] * arr.ndim))
    out = jax.device_put(arr, NamedSharding(mesh, spec))
    tensor._array = out
    return tensor


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g = group or _ensure_default_group()
    if tensor_list:
        stacked = jnp.stack([unwrap(t) for t in tensor_list], axis=0)
        mesh, axes = _axis(group)
        spec = PartitionSpec(axes[0], *([None] * (stacked.ndim - 1)))
        sharded = jax.device_put(stacked, NamedSharding(mesh, spec))
        tensor._array = sharded[0] if False else jnp.take(stacked, g.rank, axis=0)
    return tensor


def barrier(group=None):
    rec = _frec.RECORDER
    if rec.enabled:
        import time as _time

        rec.record(_frec.EV_COLLECTIVE_BEGIN, op="barrier",
                   multiprocess=_multiprocess())
        t0 = _time.perf_counter()
        try:
            (jax.device_put(0) + 0).block_until_ready()
        finally:
            rec.record(_frec.EV_COLLECTIVE_END, op="barrier",
                       seconds=_time.perf_counter() - t0)
        return
    (jax.device_put(0) + 0).block_until_ready()


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv has no eager analog under SPMD; use "
        "paddle_tpu.distributed.pipeline (ppermute-based) for PP transfers")


recv = send
isend = send
irecv = send


# ---- in-graph helpers (use inside shard_map'd code) --------------------------

def psum(x, axis_name):
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name):
    return jax.lax.pmean(x, axis_name)


def ppermute(x, axis_name, perm):
    return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


def in_graph_all_gather(x, axis_name, axis=0, tiled=True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def in_graph_all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)


def in_graph_reduce_scatter(x, axis_name, scatter_dimension=0, tiled=True):
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled)
