"""Median DEVICE time of the decode program, found by its NAME in the
traced slice: the engine's decode programs are ``jit_decode_step`` (and
``jit_decode_step_rows`` while a request overrides the sampling), so this
reading survives a program that is fused, split or run less often, where
``decode_step_ms`` ("the most-executed program of a millisecond or
more") would silently pick another. Nothing to read where the program
still runs unnamed (``jit_pure``)."""
LAYER = "generation.py prefill / decode programs"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"

PREFIX = "jit_decode_step"


def decode_program(trace):
    """(name, stats) of the most often run ``jit_decode_step*`` program
    of a reduced trace, or None."""
    mods = {k: v for k, v in ((trace or {}).get("modules") or {}).items()
            if k.startswith(PREFIX)}
    if not mods:
        return None
    name = max(mods, key=lambda k: mods[k]["count"])
    return name, mods[name]


def read(ctx):
    found = decode_program(ctx.get("trace"))
    return None if found is None else found[1]["median_s"] * 1e3
