"""Splash flash attention's share of its roofline in the train step, from
the traced steps: the least time the chip could take for what forward
and backward need (max of flops over peak flops and bytes over peak
bandwidth; the ``flash_train_cost`` of the cell's own reference module,
``reference/_costs.py``, ``peaks.json``)
over the device time of the kernels ``splash_mha_fwd*``,
``splash_mha_dq*`` and ``splash_mha_dkv*``. Compute-bound at sequence
4096 (the note line ``roofline`` of a traced run says which)."""
LAYER = "ops/pallas + bundled splash / paged kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.lib.common import note, reference_function
    from benchmarks.lib.reduce_trace import kernel_seconds
    from benchmarks.reference._costs import roofline_seconds

    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not peaks or ctx["kind"] != "train_job":
        return None
    seconds, calls = kernel_seconds(trace, "splash_mha")
    steps = ctx.get("traced_steps")
    if seconds <= 0 or not steps:
        return None
    train_cost = reference_function(ctx, "flash_attention_roofline",
                                    "flash_train_cost")
    if train_cost is None:
        return None
    cost = train_cost(ctx["spec"], ctx["batch"], ctx["seq_len"])
    least, bound = roofline_seconds(cost, peaks)
    note("roofline", kernel="splash_mha fwd+dq+dkv", bound=bound,
         kernel_s_per_step=seconds / steps, least_s_per_step=least,
         calls_per_step=calls / steps, **cost)
    return 100.0 * least * steps / seconds
