"""Median time of one train step on the host's clock: the interval
between two fetched losses (the loss is fetched one step late, so the
device never waits for the host)."""
LAYER = "jit TrainStep / distributed/engine.py"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(ctx):
    import statistics

    steps = ctx.get("step_s") or []
    return statistics.median(steps) * 1e3 if steps else None
