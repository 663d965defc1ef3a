"""Share of the engine loop's wall time in its host phases (admit +
retire + dispatch) over all phases: ``PhaseClock`` through ``/metrics``
``serving_step_phase_seconds``, window delta. Prefill and decode are
dispatched asynchronously, so their DEVICE time lands in ``sync``."""
LAYER = "serving.py engine step loop"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"
HOST_PHASES = ("admit", "retire", "dispatch")


def phase_seconds(ctx):
    """{phase: seconds inside the window} from the two /metrics reads."""
    out = {}
    for when, sign in (("after", 1.0), ("before", -1.0)):
        for series, value in ctx[when]["metrics"].items():
            if series.startswith("serving_step_phase_seconds_sum{"):
                phase = series.split('phase="')[1].split('"')[0]
                out[phase] = out.get(phase, 0.0) + sign * value
    return out


def read(ctx):
    if "before" not in ctx:
        return None
    phases = phase_seconds(ctx)
    total = sum(phases.values())
    if total <= 0:
        return None
    return 100.0 * sum(phases.get(p, 0.0) for p in HOST_PHASES) / total
