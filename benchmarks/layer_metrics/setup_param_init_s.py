"""Host seconds of set-up inside the weights' initializers:
``nn_param_init_seconds_total`` (``Layer.create_parameter``, every leaf
of the model): dispatch plus the builds made under cause ``param_init``
(so it overlaps ``setup_trace_lower_s`` and ``setup_compile_load_s``);
the device's work is asynchronous and not in it. Nothing on a program
without the counter."""
from benchmarks.layer_metrics import setup_trace_lower_s as base

LAYER = base.LAYER
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    return base.total(ctx, "nn_param_init_seconds_total")
