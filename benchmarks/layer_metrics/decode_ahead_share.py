"""Share of the window's decode steps that the engine enqueued AHEAD: while
the step before was still unfetched, so that the host's part of the step
(dispatch, retire, admit) ran beside a program and the device did not wait
for it. ``serving_decode_dispatch_total{mode="ahead"}`` over both modes,
window delta. A ``drained`` step is the first after idle, a speculative
step, or the one after the engine had to see the tokens first (preemption,
migration): what keeps this under 100% is what still serialises the loop.
Nothing on a program without the series (the parent of the PR that added
it)."""
LAYER = "serving.py engine step loop"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks.lib.common import note
    from benchmarks.layer_metrics.queue_wait_mean_ms import series_delta

    if "before" not in ctx:
        return None
    name = "serving_decode_dispatch_total"
    ahead = series_delta(ctx, name, mode="ahead")
    both = series_delta(ctx, name)
    if ahead is None or not both:
        return None
    note("decode_ahead", ahead=ahead, drained=both - ahead,
         discarded_rows=series_delta(
             ctx, "serving_decode_discarded_rows_total"))
    return 100.0 * ahead / both
