"""Traffic kind ``train_job``: a training job, not requests. The compiled
train step (one chip) or the hybrid step on the fleet mesh (the layout
the configuration file states) runs back to back on seeded batches until
the window is spent. What counts is ``train_tokens_per_s``: tokens trained
per second over all the cell's chips (a name of its own, so that the train
cells, which repeat to 0.01%, keep a tighter bound than serving)."""
from __future__ import annotations

from benchmarks.lib.train import TrainRun


def run(cell, args, t_start: float) -> dict:
    bench = TrainRun(cell, args, t_start)
    bench.setup()
    correct = bench.check()
    ctx = bench.run_window()
    losses = ctx["losses"]
    finite = all(v == v and abs(v) != float("inf") for v in losses)
    falls = len(losses) >= 2 and losses[-1] < losses[0]
    steps = len(ctx["step_s"])
    note = {"steps": steps, "tokens_per_step": ctx["tokens_per_step"],
            "loss_first": losses[0] if losses else None,
            "loss_last": losses[-1] if losses else None,
            "losses_finite": finite, "loss_falls": falls,
            "loss_curve": [round(v, 4) for v in losses[:: max(
                1, len(losses) // 16)]]}
    return {"correct": bool(correct and finite and falls),
            "attempted": steps, "failed": 0 if finite else steps,
            "device": bench.device,
            "end_to_end": {"train_tokens_per_s": ctx["tokens_per_s"],
                           "setup_s": bench.setup_s},
            "ctx": ctx, "note": note}
