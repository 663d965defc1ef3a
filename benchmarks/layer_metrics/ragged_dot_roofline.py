"""The grouped expert matmul's share of its COMPUTE roofline in the traced
slice: the flops the held (token, expert) pairs require (the
``ragged_dot_cost`` of the cell's own reference module: three projections
a pair) over the chip's bf16 peak, against the time of the ``ragged-dot-*``
kernels. The pairs of the slice are the window's
(``serving_moe_held_pairs_total``, counted by the programs) times slice
over window: the cell is saturated, so the rate is steady. Only flops are
counted: a decode step's call is bound by the held experts' weights it
streams (1.6 GB a layer), which this leaves out, so the share reads LOW
by the decode calls' time and can never read high by them."""
LAYER = "models/llama_moe.py dropless expert layer"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.lib.common import note, reference_function
    from benchmarks.lib.reduce_trace import kernel_seconds
    from benchmarks.layer_metrics.queue_wait_mean_ms import series_delta

    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not peaks or "before" not in ctx:
        return None
    seconds, calls = kernel_seconds(trace, "ragged-dot-none")
    pairs = series_delta(ctx, "serving_moe_held_pairs_total")
    if seconds <= 0 or not pairs:
        return None
    cost_of = reference_function(ctx, "ragged_dot_roofline",
                                 "ragged_dot_cost")
    if cost_of is None:
        return None
    in_slice = pairs * trace["window_s"] / ctx["seconds"]
    cost = cost_of(ctx["spec"], in_slice)
    least = cost["flops"] / peaks["flops_bf16_per_s"]
    note("roofline", kernel="ragged-dot", bound="compute", calls=calls,
         kernel_s=seconds, least_s=least, pairs_in_slice=in_slice, **cost)
    return 100.0 * least / seconds
