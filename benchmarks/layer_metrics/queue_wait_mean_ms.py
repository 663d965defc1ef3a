"""Mean time a request waited in the ENGINE's queue before it took a
slot, over the requests admitted inside the window: window delta of
``serving_queue_wait_seconds`` sum over count (from ``add_request`` on
the engine thread to admission). Below the knee a slot is nearly always
free, so this is small; it is where a queue shows first."""
LAYER = "serving_http.py front and admission"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def series_delta(ctx, name, **labels):
    """Window delta of every ``/metrics`` series called ``name`` whose
    labels include ``labels``, summed; None where no such series is."""
    total, found = 0.0, False
    for when, sign in (("after", 1.0), ("before", -1.0)):
        for series, value in ctx[when]["metrics"].items():
            head, _, rest = series.partition("{")
            if head == name and all(f'{k}="{v}"' in rest
                                    for k, v in labels.items()):
                total += sign * value
                found = True
    return total if found else None


def histogram_mean(ctx, name):
    """Mean of the observations a histogram took inside the window, in
    its own unit; None where it took none."""
    if "before" not in ctx:
        return None
    count = series_delta(ctx, name + "_count")
    total = series_delta(ctx, name + "_sum")
    if not count or total is None:
        return None
    return total / count


def read(ctx):
    mean = histogram_mean(ctx, "serving_queue_wait_seconds")
    return None if mean is None else mean * 1e3
