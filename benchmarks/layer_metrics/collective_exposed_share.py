"""Share of the traced window in which a collective was in flight on
device 0 and NO compute instruction ran there: what the collectives
cost the step, as opposed to what they overlap."""
LAYER = "distributed/{api,parallel_layers,fleet} sharding plan"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or ctx.get("chips", 1) < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
