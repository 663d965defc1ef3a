"""Share of the engine loop's wall time in its ``admit`` phase over all
phases: admission (queue triage, slot bookkeeping, enqueueing the
prefill and scatter programs of a request admitted inside a step).
``PhaseClock`` through ``/metrics`` ``serving_step_phase_seconds``,
window delta; the same span the profiler's trace shows as
``engine/admit``. With its two siblings it sums to
``engine_host_share``."""
from benchmarks.layer_metrics.engine_host_share import (LAYER, MOVES, SOURCE,
                                                        UNIT, phase_seconds)



def phase_share(ctx, phase):
    """One phase's seconds over all phases' inside the window, in %."""
    if "before" not in ctx:
        return None
    phases = phase_seconds(ctx)
    total = sum(phases.values())
    if total <= 0:
        return None
    return 100.0 * phases.get(phase, 0.0) / total


def read(ctx):
    return phase_share(ctx, "admit")
