"""The bundled paged decode attention's share of its roofline, from the
traced slice: one call per layer per decode step; what it must move is
every cached K and V row of the batch once (the ``paged_decode_cost`` of
the cell's own reference module), so it is memory-bound. The cached
tokens the batch held come from the clients' outcomes: each request's
context while it decoded inside the slice, averaged over the slice."""
LAYER = "ops/pallas + bundled splash / paged kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def mean_context_tokens(ctx):
    """Time-average over the slice of the cached tokens summed over the
    requests decoding at that moment, and of their number."""
    t0, t1 = ctx["trace_window"]
    tok_s = rows_s = 0.0
    for o in ctx["outcomes"]:
        if o.get("t_first") is None or o.get("t_end") is None:
            continue
        a, b = max(o["t_first"], t0), min(o["t_end"], t1)
        if b <= a:
            continue
        life = max(o["t_end"] - o["t_first"], 1e-9)

        def at(t, o=o, life=life):
            return o["n_prompt"] + o["n_tokens"] * (t - o["t_first"]) / life

        tok_s += (at(a) + at(b)) / 2.0 * (b - a)
        rows_s += b - a
    return tok_s / (t1 - t0), rows_s / (t1 - t0)


def read(ctx):
    from benchmarks.lib.common import note, reference_function
    from benchmarks.lib.reduce_trace import kernel_seconds
    from benchmarks.reference._costs import roofline_seconds

    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not peaks or not ctx.get("trace_window"):
        return None
    seconds, calls = kernel_seconds(trace, "paged_attention")
    if seconds <= 0:
        return None
    decode_cost = reference_function(ctx, "paged_attention_roofline",
                                     "paged_decode_cost")
    if decode_cost is None:
        return None
    tokens, rows = mean_context_tokens(ctx)
    cost = decode_cost(ctx["spec"], tokens, rows)
    least, bound = roofline_seconds(cost, peaks)
    note("roofline", kernel="paged_attention", bound=bound, calls=calls,
         kernel_s_per_call=seconds / calls, least_s_per_call=least,
         mean_cached_tokens=tokens, mean_rows=rows, **cost)
    return 100.0 * least * calls / seconds
