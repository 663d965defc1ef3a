"""Traffic kind ``closed_loop``: N callers that each wait for a reply
before sending the next request, more of them than the batch has slots,
so the queue is never empty and the server runs at its capacity. What
counts is ``tokens_per_s``: prompt + generated tokens of the requests
that COMPLETED inside the window, per second. Time to first token here
is a queue length, so it is a per-layer number (``sat_ttft_p50_ms``)."""
from __future__ import annotations

from benchmarks.lib.loadgen.schedule import percentile
from benchmarks.lib.serve import ServeRun, by_status


def run(cell, args, t_start: float) -> dict:
    bench = ServeRun(cell, args, t_start)
    correct, ctx = bench.run()
    seconds = ctx["seconds"]
    ended = [o for o in ctx["outcomes"]
             if o["t_end"] is not None and 0.0 <= o["t_end"] < seconds]
    done = [o for o in ended if o["ok"]]
    tokens = sum(o["n_prompt"] + o["n_tokens"] for o in done)
    ttft = [(o["t_first"] - o["t_due"]) * 1e3 for o in done]
    ctx.update(window_outcomes=ended, ttft_ms=ttft,
               gaps_ms=[g * 1e3 for o in done for g in o["gaps"]])
    note = {"requests_ended_in_window": len(ended),
            "by_status": by_status(ended),
            "requests_in_all": len(ctx["outcomes"]),
            "schedule_digest": ctx["digest"],
            "requests_per_s": len(done) / seconds,
            "prompt_tokens": sum(o["n_prompt"] for o in done),
            "generated_tokens": sum(o["n_tokens"] for o in done),
            "ttft_ms": {"n": len(ttft), "p50": percentile(ttft, 50)}}
    return {"correct": correct, "attempted": len(ended),
            "failed": len(ended) - len(done), "device": bench.device,
            "end_to_end": {"tokens_per_s": tokens / seconds if done else None,
                           "setup_s": bench.setup_s},
            "ctx": ctx, "note": note}
