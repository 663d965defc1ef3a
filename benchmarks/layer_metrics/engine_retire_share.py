"""Share of the engine loop's wall time in its ``retire`` phase over all
phases: retirement (the per-slot loop after the sync: stop checks,
logprobs, the lengths update, stream callbacks). ``PhaseClock`` through
``/metrics`` ``serving_step_phase_seconds``, window delta; the same span
the profiler's trace shows as ``engine/retire``. With its two siblings
it sums to ``engine_host_share``."""
from benchmarks.layer_metrics.engine_admit_share import (LAYER, MOVES, SOURCE,
                                                         UNIT, phase_share)


def read(ctx):
    return phase_share(ctx, "retire")
