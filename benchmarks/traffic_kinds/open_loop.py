"""Traffic kind ``open_loop``: independent users. Requests arrive on a
seeded schedule at the rate fixed in the traffic file, whatever the
server does; each is timed from when it was DUE. Below the knee the tail
of the gaps between tokens (``itl_p95_ms``) is what the cell is judged
on; time to first token is recorded by its reader (``ttft_p85_ms``)."""
from __future__ import annotations

from benchmarks.lib.loadgen.schedule import percentile
from benchmarks.lib.serve import ServeRun, by_status


def run(cell, args, t_start: float) -> dict:
    bench = ServeRun(cell, args, t_start)
    correct, ctx = bench.run()
    counted = [o for o in ctx["outcomes"] if 0.0 <= o["t_due"]]
    done = [o for o in counted if o["ok"]]
    ttft = [(o["t_first"] - o["t_due"]) * 1e3 for o in done]
    gaps = [g * 1e3 for o in done for g in o["gaps"]]
    ctx.update(window_outcomes=counted, ttft_ms=ttft, gaps_ms=gaps)
    p = percentile
    note = {"requests_due": len(counted), "by_status": by_status(counted),
            "lead_in_requests": len(ctx["outcomes"]) - len(counted),
            "schedule_digest": ctx["digest"],
            "ttft_ms": {"n": len(ttft), "p50": p(ttft, 50),
                        "p85": p(ttft, 85), "p90": p(ttft, 90)},
            "itl_ms": {"n": len(gaps), "p50": p(gaps, 50),
                       "p95": p(gaps, 95), "p99": p(gaps, 99)}}
    return {"correct": correct, "attempted": len(counted),
            "failed": len(counted) - len(done), "device": bench.device,
            "end_to_end": {"itl_p95_ms": p(gaps, 95),
                           "setup_s": bench.setup_s},
            "ctx": ctx, "note": note}
