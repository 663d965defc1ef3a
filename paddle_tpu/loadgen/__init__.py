"""paddle_tpu.loadgen — traffic replay & saturation harness.

The proof layer for "heavy traffic from millions of users": seeded
arrival synthesis (:mod:`.workload`), a replayable JSONL trace format
(:mod:`.trace`), and an open-loop HTTP driver + capacity reports +
QPS-sweep knee finder (:mod:`.harness`). Drives the single-process
``serving_http`` server and the cluster router identically (both speak
``POST /v1/completions``), and reads shed/429/preempt/migrate accounting
off the metrics the stack already exports.

CLI: ``scripts/load_replay.py``; the chaos dryrun (``chaos/dryrun.py``)
drives its load through it; runbook: docs/SERVING.md "Capacity &
overload runbook".
"""
from .trace import (TraceRequest, dump_trace, dumps_trace, load_trace,
                    loads_trace, trace_digest)
from .workload import WorkloadSpec, synthesize
from .harness import (Outcome, alerts_state, find_knee, run_schedule,
                      run_workload, stack_stats, summarize, sweep)

__all__ = [
    "TraceRequest", "dump_trace", "dumps_trace", "load_trace",
    "loads_trace", "trace_digest",
    "WorkloadSpec", "synthesize",
    "Outcome", "alerts_state", "find_knee", "run_schedule",
    "run_workload", "stack_stats", "summarize", "sweep",
]
