"""Programs a set-up compiled because the persistent cache did not
answer: ``jit_builds_total``, stage ``compile`` alone. On a warm cache
these are the programs whose keys moved (a source line moved; a key that
holds the checkout's path) or that bypass the cache; it equals the
``cache_misses`` of the harness's ``setup_split`` note where every build
went through the cache. Nothing on a program without the counter."""
from benchmarks.layer_metrics import setup_trace_lower_s as base

LAYER = base.LAYER
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    return base.total(ctx, "jit_builds_total", ("compile",))
