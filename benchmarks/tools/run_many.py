#!/usr/bin/env python
"""Several runs of the benchmark in ONE chip call, one child process each
(this parent never touches JAX: a chip belongs to one process), as the
driver runs them: the sets of runs a bound is set from, and parent against
change from two checkouts.

    chiprun --chips 1 --timeout 3000 -- python benchmarks/tools/run_many.py \
        --label setA --runs mistral7b_chat_steady:2900000201:0 \
                            mistral7b_chat_steady:2900000202:0 ...
    ... --runs parent=olmo2_1b_pretrain_1chip:7:0 olmo2_1b_pretrain_1chip:7:0

A run is ``[<checkout>=]<cell>:<seed>:<trace>``; ``<checkout>`` is a
directory under ``.committed_tree/`` (git-ignored) that holds another tree
(``git archive <commit> | tar -x -C .committed_tree/<name>``), and the run
is made from there with that tree's own benchmark. Every run's whole
standard output goes to ``chiprun_out/<label>/``; what is printed is one
line a run: its result line, the seconds it took and the notes a reader
of PERF.md asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: note -> the fields worth a line in the summary
KEEP = {"setup_split": ("setup_s", "warmup_s", "cache_misses"),
        "reference_check": ("worst", "abs_err", "reference"),
        "result_detail": ("by_status", "itl_ms", "ttft_ms", "requests_due",
                          "requests_ended_in_window", "steps", "loss_first",
                          "loss_last"),
        "serve_mfu": None, "reader_skipped": None}


def one(run: str, seconds, out_dir: str) -> dict:
    where, _, spec = run.rpartition("=")
    cell, seed, trace = spec.split(":")
    root = os.path.join(ROOT, ".committed_tree", where) if where else ROOT
    cmd = [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
           cell, "--seed", seed, "--trace", trace]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=root, capture_output=True, text=True)
    took = time.time() - t0
    name = f"{where or 'tree'}_{cell}_{seed}_t{trace}"
    with open(os.path.join(out_dir, name + ".jsonl"), "w") as f:
        f.write(proc.stdout)
    with open(os.path.join(out_dir, name + ".err"), "w") as f:
        f.write(proc.stderr[-20000:])
    lines = []
    for ln in proc.stdout.splitlines():
        try:
            lines.append(json.loads(ln))
        except ValueError:
            pass
    summary = {"run": run, "rc": proc.returncode, "took_s": round(took, 1)}
    for ln in lines:
        fields = KEEP.get(ln.get("note"), ())
        if fields is None:
            summary[ln["note"]] = {k: v for k, v in ln.items() if k != "note"}
        elif fields:
            summary[ln["note"]] = {k: ln[k] for k in fields if k in ln}
    if lines and "correct" in lines[-1]:
        summary["result"] = lines[-1]
    else:
        summary["stderr_tail"] = proc.stderr[-1500:]
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--label", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: the benchmark's run_seconds")
    ap.add_argument("--runs", nargs="+", required=True)
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", args.label)
    os.makedirs(out_dir, exist_ok=True)
    bad = 0
    with open(os.path.join(out_dir, "summary.jsonl"), "a") as log:
        for run in args.runs:
            summary = one(run, args.seconds, out_dir)
            text = json.dumps(summary)
            print(text, flush=True)
            log.write(text + "\n")
            log.flush()
            bad += not (summary.get("result") or {}).get("correct")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
