#!/usr/bin/env python
"""Compile a train cell's REAL-SIZE step for a DESCRIBED TPU v5e (no chip
attached, no chip time) and print the compiler's memory analysis: what
decides the batch a cell can take before any chip run.

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_described.py \
        --workload olmo2_1b_pretrain_1chip [--batch 1]

The model is built for real on the CPU (shapes come from it), the
program's own ``TrainStep`` pure function is lowered with
``jax.ShapeDtypeStruct`` arguments that sit on the described device, under
``backend.lowering_target("tpu")`` so that the Pallas gates take the TPU
branch. A compile that passes is not a chip run and gives no time.
A four-chip cell builds its model on four virtual CPU devices through
the fleet mesh, then re-points the mesh at the described devices and
gives every argument the same PartitionSpec there (verify skill).
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    from benchmarks.lib import build, common
    from paddle_tpu.framework import random as _random
    from paddle_tpu.ops.pallas import backend

    cell = common.Cell(args.workload)
    if cell.traffic["kind"] != "train_job":
        raise SystemExit("compile_described: train cells only")
    batch = args.batch or int(cell.traffic["batch"])
    seq = int(cell.traffic["seq_len"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    opt = cell.config["recipe"]["optimizer"]
    layout = cell.config["recipe"].get("layout")

    def loss_fn(m, x, y):
        return m(x, labels=y)[0]

    def make_optimizer(model):
        return common.import_object(opt["class"])(
            opt["learning_rate"], parameters=model.parameters(),
            **opt.get("args", {}))

    if layout:
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.engine import parallelize

        strategy = dist.DistributedStrategy()
        strategy.hybrid_configs = dict(layout["fleet"])
        strategy.sharding_configs = {"stage": layout["sharding_stage"]}
        dist.fleet.init(is_collective=True, strategy=strategy)
        model = dist.fleet.distributed_model(build.build_model(cell.config,
                                                               0))
        optimizer = dist.fleet.distributed_optimizer(make_optimizer(model))
        step = parallelize(model, loss_fn, optimizer)
        hcg = dist.get_hybrid_communicate_group()
        cpu_mesh = hcg.mesh.jax_mesh()
        mesh = Mesh(np.asarray(topo.devices).reshape(cpu_mesh.devices.shape),
                    cpu_mesh.axis_names)
        hcg.mesh._jax_mesh = mesh

        def described(x):
            spec = getattr(getattr(x, "sharding", None), "spec",
                           PartitionSpec())
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=NamedSharding(mesh, spec))

        tokens = jax.ShapeDtypeStruct(
            (batch, seq), jnp.int32,
            sharding=NamedSharding(mesh, PartitionSpec("sharding")))
    else:
        model = build.build_model(cell.config, 0)
        step = paddle.jit.train_step(model, loss_fn, make_optimizer(model))

        def described(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

        tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=chip)
    params, buffers = step._split_state()
    opt_state = step._optimizer.init_state(params) if layout else \
        jax.eval_shape(step._optimizer.init_state, params)
    key = _random.next_key()
    shapes = jax.tree_util.tree_map(
        described, (params, buffers, opt_state, key,
                    jnp.asarray(opt["learning_rate"], jnp.float32)))
    with backend.lowering_target("tpu"):
        fresh = jax.jit(step._jitted.__wrapped__, donate_argnums=(0, 2))
        compiled = fresh.lower(*shapes, tokens, tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    text = compiled.as_text()
    common.emit({
        "workload": cell.name, "batch": batch, "seq_len": seq,
        "described_chip": str(topo.devices[0].device_kind),
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "program_bytes": mem.generated_code_size_in_bytes,
        "live_bytes_at_peak": total, "live_gib_at_peak": total / 2 ** 30,
        "per": "device" if layout else "chip",
        "pallas_kernels": text.count("tpu_custom_call"),
        "collectives": {op: text.count(f" {op}(") + text.count(
            f" {op}-start(") for op in ("all-gather", "all-reduce",
                                        "reduce-scatter",
                                        "collective-permute")},
        "note": "one program's own count, not what else the process "
                "keeps on the device; not a chip run"})
    return 0


if __name__ == "__main__":
    sys.exit(main())
