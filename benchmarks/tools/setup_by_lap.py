#!/usr/bin/env python
"""Where a cell's set-up goes, lap by lap: the cell run once in this
process as ``run.py`` runs it, with the program's build counters
(``jit_build_seconds_total``, ``nn_param_init_seconds_total``: docs/SERVING.md
"Set-up") read at every lap of the harness's ``SetupClock``. Each lap of
the ``setup_split`` note is split into its build seconds by stage and by
cause; what is left is the lap's other work (Python, transfers, the
device, waiting).

    python benchmarks/tools/setup_by_lap.py --workload mistral7b_docs_batch \
        --seed N [--trace 1] [--seconds 10]

on the machine that holds the chip (like ``run.py``, it needs a TPU).

The run prints what ``run.py`` prints; then one ``setup_lap`` note a lap
on standard output, and the same as a table on standard error. The
window's length does not touch set-up, so a short ``--seconds`` will do.
``--rehearse`` runs it on the CPU at the tiny sizes.
"""
from __future__ import annotations

import os
import sys
import time

T_START = time.time()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def builds() -> dict:
    """``{(stage, cause): seconds}`` of the program's build counter so far,
    and ``("param_init", "")`` for the initializers' seconds."""
    from benchmarks.layer_metrics import setup_trace_lower_s as base

    ctx = {"kind": "train_job"}       # the registry in this process
    out = {}
    for labels, v in base.labelled(ctx, "jit_build_seconds_total") or []:
        key = (labels["stage"], labels["cause"])
        out[key] = out.get(key, 0.0) + v
    out[("param_init", "")] = base.total(
        ctx, "nn_param_init_seconds_total") or 0.0
    return out


def main(argv=None) -> int:
    from benchmarks import run
    from benchmarks.lib import common

    laps, last = [], [{}]
    lap = common.SetupClock.lap

    def counted_lap(self, name):
        if not laps:
            laps.append(("imports_s", self.parts["imports_s"], {}))
        lap(self, name)
        now = builds() if "paddle_tpu" in sys.modules else {}
        d = {k: v - last[0].get(k, 0.0) for k, v in now.items()}
        last[0] = now
        laps.append((name, self.parts[name], d))

    common.SetupClock.lap = counted_lap
    run.T_START = T_START
    try:
        rc = run.main(argv)
    finally:
        common.SetupClock.lap = lap
    for name, seconds, d in laps:
        by_stage, by_cause = {}, {}
        for (stage, cause), v in d.items():
            if stage == "param_init":
                continue
            by_stage[stage] = by_stage.get(stage, 0.0) + v
            by_cause[cause] = by_cause.get(cause, 0.0) + v
        common.note("setup_lap", lap=name, seconds=seconds,
                    by_stage=by_stage, by_cause=by_cause,
                    param_init_s=d.get(("param_init", ""), 0.0),
                    other_s=seconds - sum(by_stage.values()))
        print(f"{name:>18} {seconds:8.3f} s | " + "  ".join(
            f"{k} {v:.3f}" for k, v in sorted(by_stage.items())) + " | "
            + "  ".join(f"{k} {v:.3f}" for k, v in sorted(by_cause.items())),
            file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
