#!/usr/bin/env python
"""Run ONE cell of the benchmark ONCE and print its result as the last
line of standard output (the contract is in PERF.md, section 2).

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by its name in ``BENCHMARK.json``; its configuration,
traffic mix, traffic kind and per-layer readers are files found by the
names it gives (``benchmarks/configs``, ``traffic``, ``traffic_kinds``,
``layer_metrics``). A run that finds no TPU, or fewer chips than the cell
asks for, exits non-zero and prints no result line.

``--rehearse`` is the CPU rehearsal: the same control flow at the tiny
sizes each file states under ``rehearse`` (four virtual devices for a
four-chip cell). It can only end ``correct: false`` and prints no number
under a metric's name.
"""
from __future__ import annotations

import time

T_START = time.time()          # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; cannot pass")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("benchmark: the program (paddle_tpu/) is not in this "
              "directory", file=sys.stderr)
        return 2
    from benchmarks.lib import common, report

    cell = common.Cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell.manifest["run_seconds"])
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                f"platform_device_count={cell.chips}").strip()
    common.note("run", workload=cell.name, config=cell.entry["config"],
                traffic=cell.traffic_name, kind=cell.traffic["kind"],
                chips=cell.chips, seed=args.seed, seconds=args.seconds,
                trace=args.trace, rehearse=args.rehearse)
    out = cell.traffic_kind().run(cell, args, T_START)
    common.note("result_detail", **out["note"])
    line = report.finish(
        cell, trace=bool(args.trace), rehearse=args.rehearse,
        device=out["device"], correct=out["correct"],
        attempted=out["attempted"], failed=out["failed"],
        end_to_end=out["end_to_end"], ctx=out["ctx"])
    common.emit(line)
    sys.stdout.flush()
    # each number compared beside its limit: the last lines of stderr too
    for name, pair in line["compared"].items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
