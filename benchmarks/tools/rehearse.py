#!/usr/bin/env python
"""The CPU rehearsal of EVERY cell in ``BENCHMARK.json``, before any chip
time: each runs end to end at the tiny sizes its files state under
``rehearse`` (four virtual devices for a four-chip cell), traced and
untraced, one child process each (this parent never touches JAX).

    python benchmarks/tools/rehearse.py [--seconds 4] [--workload NAME]

A rehearsal can only end ``correct: false`` (the device is no TPU) with
no number under a metric's name; what it shows is on the ``rehearsal``
line: whether the comparison with the reference WOULD have passed, how
many programs were built inside the window (must be 0), and which
metrics the run could fill. Exit code 0 when every cell rehearsed clean.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rehearse(workload: str, seconds: float, trace: int) -> list:
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", "0", "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    faults = []
    if proc.returncode != 0 or not lines:
        return [f"exit {proc.returncode}: {proc.stderr[-400:]}"]
    last = lines[-1]
    note = next((ln for ln in lines if ln.get("note") == "rehearsal"), {})
    if last.get("correct") is not False or last.get("metrics"):
        faults.append("a rehearsal printed a result as if from a chip")
    if not note.get("would_be_correct"):
        faults.append("the comparison with the reference failed")
    if note.get("compiles_in_window") != 0:
        faults.append(f"{note.get('compiles_in_window')} programs were "
                      f"built inside the window")
    if last.get("failed"):
        faults.append(f"{last['failed']} of {last['attempted']} failed")
    print(json.dumps({"workload": workload, "trace": trace, "faults": faults,
                      "attempted": last.get("attempted"),
                      "fills": note.get("end_to_end_names", [])
                      + note.get("per_layer_names", [])}), flush=True)
    return faults


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--workload", default=None)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    bad = 0
    for name in cells:
        if args.workload in (None, name):
            for trace in (0, 1):
                bad += bool(rehearse(name, args.seconds, trace))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
