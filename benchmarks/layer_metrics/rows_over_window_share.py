"""Share of the delivered decode rows whose context was longer than the
sliding window, so that the window layers served them from a ring that
had wrapped: window delta of ``serving_decode_rows_over_window_total``
over ``serving_decode_rows_total`` (both from the host's bookkeeping at
retirement). Those rows are where a ring saves pages and where a window
layer's decode reads less than the global layer's. Nothing on a program
without the counter, or whose engine has no ring layers."""
LAYER = "serving.py engine step loop"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks.layer_metrics.queue_wait_mean_ms import series_delta

    if "before" not in ctx:
        return None
    over = series_delta(ctx, "serving_decode_rows_over_window_total")
    rows = series_delta(ctx, "serving_decode_rows_total")
    if over is None or not rows:
        return None
    return 100.0 * over / rows
