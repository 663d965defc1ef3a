"""Model FLOP/s utilization: tokens per second x the flops forward and
backward REQUIRE per token (the ``train_flops_per_token`` of the cell's
own reference module; nothing recomputed counts) over chips x the
chip's bf16 peak from ``peaks.json``. An end-to-end utilization, not a
kernel's roofline share."""
LAYER = "jit TrainStep / distributed/engine.py"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(ctx):
    from benchmarks.lib.common import reference_function

    if ctx.get("kind") != "train_job" or not ctx.get("peaks"):
        return None
    per_token = reference_function(ctx, "train_mfu", "train_flops_per_token")
    if per_token is None:
        return None
    flops = ctx["tokens_per_s"] * per_token(ctx["spec"], ctx["seq_len"])
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_bf16_per_s"])
