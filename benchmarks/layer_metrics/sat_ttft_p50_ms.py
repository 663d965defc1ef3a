"""Median time to first token in the SATURATED cell, client clock: above
capacity it is the queue's length, not the server's speed, so it is a
per-layer number here and no end-to-end metric."""
LAYER = "generation.py prefill / decode programs"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def read(ctx):
    from benchmarks.lib.loadgen.schedule import percentile

    if ctx.get("kind") != "closed_loop":
        return None
    return percentile(ctx.get("ttft_ms", []), 50)
