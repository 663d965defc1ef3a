"""Programs built between the window's start and its end (backend
compiles plus loads from the persistent cache, ``jax.monitoring``). Must
read 0: otherwise that run's latencies or rate are void."""
LAYER = "generation.py / jit (sanity)"
UNIT = "count"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    return float(ctx["compiles_in_window"])
