"""Share of the engine loop's wall time in its ``dispatch`` phase over all
phases: enqueueing the decode program (the dispatch guard, the key
split, the call itself). ``PhaseClock`` through ``/metrics``
``serving_step_phase_seconds``, window delta; the same span the
profiler's trace shows as ``engine/dispatch``. With its two siblings it
sums to ``engine_host_share``."""
from benchmarks.layer_metrics.engine_admit_share import (LAYER, MOVES, SOURCE,
                                                         UNIT, phase_share)


def read(ctx):
    return phase_share(ctx, "dispatch")
