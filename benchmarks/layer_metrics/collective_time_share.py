"""Share of the traced window in which a collective (all-gather,
all-reduce, reduce-scatter, collective-permute; synchronous instructions
and asynchronous start-to-done pairs) was in flight on device 0."""
LAYER = "distributed/{api,parallel_layers,fleet} sharding plan"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or ctx.get("chips", 1) < 2:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
