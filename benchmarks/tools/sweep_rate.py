#!/usr/bin/env python
"""Find the knee of an open-loop cell ONCE: the highest offered rate the
system sustains. One process (one model load, one warm-up) runs the
cell's own traffic at each of a ladder of rates for a short window and
prints, per rate, what the clients saw and whether a backlog grew.

    chiprun --chips 1 -- python benchmarks/tools/sweep_rate.py \
        --workload mistral7b_chat_steady --rates 3,4,5,6,7,8 --seconds 20

The cell's traffic file then gets ``rate_per_s`` = 0.8 x the knee, by
hand, with the sweep written into PERF.md. A rate is sustained when no
request failed, the batch was not full all the time and time to first
token did not grow from the first half of the window to the second.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    from benchmarks.lib import common
    from benchmarks.lib.loadgen.schedule import percentile
    from benchmarks.lib.serve import ServeRun

    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cell = common.Cell(args.workload)
    bench = ServeRun(cell, args, T_START)
    try:
        bench.setup()
        bench.warm_up()
        common.note("check", correct=bench.check())
        for rate in [float(r) for r in args.rates.split(",")]:
            bench.traffic = dict(bench.traffic, rate_per_s=rate)
            ctx = bench.run_window()
            due = [o for o in ctx["outcomes"] if o["t_due"] >= 0.0]
            ok = [o for o in due if o["ok"]]
            half = args.seconds / 2.0
            ttft = [(o["t_first"] - o["t_due"]) * 1e3 for o in ok]
            first = [(o["t_first"] - o["t_due"]) * 1e3 for o in ok
                     if o["t_due"] < half]
            second = [(o["t_first"] - o["t_due"]) * 1e3 for o in ok
                      if o["t_due"] >= half]
            gaps = [g * 1e3 for o in ok for g in o["gaps"]]
            a, b = ctx["after"]["stats"], ctx["before"]["stats"]
            steps = max(a["decode_steps"] - b["decode_steps"], 1)
            common.emit({
                "rate_per_s": rate, "due": len(due), "ok": len(ok),
                "ttft_p50_ms": percentile(ttft, 50),
                "ttft_p85_ms": percentile(ttft, 85),
                "ttft_p50_first_half_ms": (statistics.median(first)
                                           if first else None),
                "ttft_p50_second_half_ms": (statistics.median(second)
                                            if second else None),
                "itl_p50_ms": percentile(gaps, 50),
                "itl_p95_ms": percentile(gaps, 95),
                "batch_occupancy": (a["tokens_generated"]
                                    - b["tokens_generated"])
                / (steps * bench.engine_args["max_batch"]),
                "queued_at_end": a["requests_queued"],
                "active_at_end": a["requests_active"],
                "compiles_in_window": ctx["compiles_in_window"],
                "lag_p95_ms": percentile(
                    [(o["t_sent"] - o["t_due"]) * 1e3 for o in due
                     if o["t_sent"] is not None], 95)})
            deadline = time.time() + 60.0
            while time.time() < deadline:
                st = bench.engine.stats()
                if not (st["requests_active"] or st["requests_queued"]):
                    break
                time.sleep(0.2)
    finally:
        bench.close()
    common.note("memory", peak_bytes=common.memory_peak_bytes(cell.chips))
    return 0


if __name__ == "__main__":
    sys.exit(main())
