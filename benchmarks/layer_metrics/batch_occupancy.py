"""Tokens generated over decode steps x max_batch, from
``engine.stats()`` at both ends of the window: how full the batch was
when a decode program ran."""
LAYER = "serving.py engine step loop"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    if "before" not in ctx:
        return None
    a, b = ctx["after"]["stats"], ctx["before"]["stats"]
    steps = a["decode_steps"] - b["decode_steps"]
    if steps <= 0:
        return None
    tokens = a["tokens_generated"] - b["tokens_generated"]
    return 100.0 * tokens / (steps * ctx["engine_args"]["max_batch"])
