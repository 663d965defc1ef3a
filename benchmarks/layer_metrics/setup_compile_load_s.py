"""Seconds of set-up that the backend spent compiling programs or
reading them from the persistent compilation cache:
``jit_build_seconds_total``, stages ``compile`` and ``load``. Beside
``setup_trace_lower_s`` it says whether a warm set-up's program share is
Python tracing and lowering or the cache. Nothing on a program without
the counter."""
from benchmarks.layer_metrics import setup_trace_lower_s as base

LAYER = base.LAYER
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    return base.total(ctx, "jit_build_seconds_total", ("compile", "load"))
