"""K/V rows in use against K/V rows reserved, when a decode program
runs: the engine's own count of the cached tokens its decode dispatches
read (``serving_decode_cached_tokens_total``) per decode step, over
``max_batch x max_len``, the rows the page pool holds (every slot owns
``max_len`` of pages for life). The decode program rewrites the whole
pool in every step (PERF.md section 5), so this is the share of that
copy that carries anything. It also notes the cached tokens and rows per
step, exact, beside ``paged_attention_roofline``'s estimate from the
clients' side."""
LAYER = "serving.py engine step loop"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks.lib.common import note
    from benchmarks.layer_metrics.queue_wait_mean_ms import series_delta

    if "before" not in ctx:
        return None
    cached = series_delta(ctx, "serving_decode_cached_tokens_total")
    rows = series_delta(ctx, "serving_decode_rows_total")
    steps = (ctx["after"]["stats"]["decode_steps"]
             - ctx["before"]["stats"]["decode_steps"])
    if cached is None or rows is None or steps <= 0:
        return None
    engine = ctx["engine_args"]
    reserved = engine["max_batch"] * engine["max_len"]
    note("kv_in_use", decode_steps=steps, cached_tokens_per_step=cached / steps,
         rows_per_step=rows / steps, reserved_tokens=reserved)
    return 100.0 * cached / steps / reserved
