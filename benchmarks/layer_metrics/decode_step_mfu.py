"""The whole decode step's share of the chip's peak, beside the roofline
of the one kernel inside it (``paged_attention_roofline``): the rows a
decode step advances (``serving_decode_rows_total`` per decode step) x the
flops a forward pass REQUIRES for one token behind the context those rows
hold (``serving_decode_cached_tokens_total`` per row; the
``serve_flops_per_token`` of the cell's own reference module, a token
drawn at every row) over the mean DEVICE time of the decode program in
the traced slice x the chip's bf16 peak. Small by nature (a decode step
streams every weight for a handful of rows), never 0; in an open loop the
offered rate fixes the tokens per second, so only a share per STEP can
move with the program. The counters cover the window, the program's time
the slice."""
LAYER = "generation.py prefill / decode programs"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.layer_metrics.decode_program_ms import decode_program
    from benchmarks.layer_metrics.queue_wait_mean_ms import series_delta
    from benchmarks.lib.common import note, reference_function

    found = decode_program(ctx.get("trace"))
    if found is None or not ctx.get("peaks") or "before" not in ctx:
        return None
    cached = series_delta(ctx, "serving_decode_cached_tokens_total")
    rows = series_delta(ctx, "serving_decode_rows_total")
    steps = (ctx["after"]["stats"]["decode_steps"]
             - ctx["before"]["stats"]["decode_steps"])
    if not cached or not rows or steps <= 0:
        return None
    per_token = reference_function(ctx, "decode_step_mfu",
                                   "serve_flops_per_token")
    if per_token is None:
        return None
    step_s = found[1]["total_s"] / found[1]["count"]
    flops = rows / steps * per_token(ctx["spec"], cached / rows, 1.0)
    note("decode_step_mfu", program=found[0], mean_step_s=step_s,
         rows_per_step=rows / steps, context_per_row=cached / rows,
         flops_per_step=flops)
    return (100.0 * flops
            / (step_s * ctx["chips"] * ctx["peaks"]["flops_bf16_per_s"]))
