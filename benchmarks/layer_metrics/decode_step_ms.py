"""Median DEVICE time of the decode program, from the traced slice: of
the programs that take a millisecond or more, the one executed most often
is the decode step (one run per token step; a prefill runs once per
request; the engine's index programs run as often but take microseconds).
The host's phase clock cannot give this: prefill and decode are
dispatched asynchronously and both land in its ``sync`` phase."""
LAYER = "generation.py prefill / decode programs"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


MIN_PROGRAM_S = 1e-3


def decode_module(trace):
    """(name, stats) of the most often executed real program, or None."""
    mods = {k: v for k, v in ((trace or {}).get("modules") or {}).items()
            if v["median_s"] >= MIN_PROGRAM_S}
    if not mods:
        return None
    name = max(mods, key=lambda k: mods[k]["count"])
    return name, mods[name]


def read(ctx):
    found = decode_module(ctx.get("trace"))
    if ctx.get("kind") == "train_job" or found is None:
        return None
    return found[1]["median_s"] * 1e3
