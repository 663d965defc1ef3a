"""1 - the union of the intervals in which an instruction ran on the
device, over the traced window, averaged over the cell's chips."""
LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    trace = ctx.get("trace")
    return 100.0 * trace["idle_share"] if trace else None
