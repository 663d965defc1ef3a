#!/usr/bin/env python
"""Record a SMALL device trace for ``benchmarks/tests/data`` and dump its
structure (planes, lines, event names, stats) as text, so that the
reduction in ``benchmarks/lib/reduce_trace.py`` is written against what
the profiler really emits on the chip.

    chiprun --chips 1 -- python benchmarks/tools/record_trace.py [--mesh]

Writes ``chiprun_out/recorded/<name>.xplane.pb`` and ``<name>.dump.txt``.
A toy model (hidden 512, 2 layers): the numbers mean nothing, the NAMES
are the point. ``--mesh`` records the mp2 x sharding2 step on four chips.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
from collections import Counter, defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def dump(path: str, out) -> None:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}", file=out)
        for ln in lines:
            evs = list(ln.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {ln.name!r} events={len(evs)} "
                  f"span=[{t0:.0f}, {t1:.0f}] ns", file=out)
            tot, cnt = defaultdict(float), Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                print(f"    {ns / 1e3:12.1f} us x{cnt[name]:<5d} {name[:160]}",
                      file=out)
            seen = set()
            for e in evs:
                if e.name in seen or len(seen) >= 6:
                    continue
                seen.add(e.name)
                stats = {k: (str(v)[:200]) for k, v in e.stats}
                print(f"    STATS {e.name[:80]!r}: {stats}", file=out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", action="store_true")
    ap.add_argument("--name", default=None)
    args = ap.parse_args()
    name = args.name or ("mesh4" if args.mesh else "train1")

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models.olmo2 import Olmo2Config, Olmo2ForCausalLM

    print("devices:", jax.devices())
    cfg = Olmo2Config(vocab_size=2048, hidden_size=512, intermediate_size=1024,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=1024,
                      fuse_linear_cross_entropy=True, dtype="bfloat16")
    paddle.seed(0)

    def loss_fn(m, x, y):
        return m(x, labels=y)[0]

    if args.mesh:
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.engine import parallelize

        strategy = dist.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2,
                                   "sep_degree": 1, "sharding_degree": 2,
                                   "pp_degree": 1}
        strategy.sharding_configs = {"stage": 3}
        dist.fleet.init(is_collective=True, strategy=strategy)
        model = dist.fleet.distributed_model(Olmo2ForCausalLM(cfg))
        optimizer = dist.fleet.distributed_optimizer(
            opt.AdamW(3e-4, parameters=model.parameters(),
                      moment_dtype="bfloat16"))
        step = parallelize(model, loss_fn, optimizer)
    else:
        model = Olmo2ForCausalLM(cfg)
        step = paddle.jit.train_step(
            model, loss_fn, opt.AdamW(3e-4, parameters=model.parameters(),
                                      moment_dtype="bfloat16"))
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 1025))
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
    for _ in range(3):
        print("warm loss", float(step(x, y).numpy()))

    tdir = os.path.join(ROOT, "chiprun_out", "recorded", f"{name}_trace")
    shutil.rmtree(tdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/trace_slice"):
        for i in range(4):
            with jax.profiler.StepTraceAnnotation("bench/train_step",
                                                  step_num=i):
                loss = step(x, y)
            import time
            time.sleep(0.002)
        print("traced loss", float(loss.numpy()))
    jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))
    dst = os.path.join(ROOT, "chiprun_out", "recorded", f"{name}.xplane.pb")
    shutil.copy(pb, dst)
    shutil.rmtree(tdir)
    print("trace bytes", os.path.getsize(dst))
    with open(dst.replace(".xplane.pb", ".dump.txt"), "w") as f:
        dump(dst, f)
    with open(dst.replace(".xplane.pb", ".dump.txt")) as f:
        print(f.read()[-20000:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
