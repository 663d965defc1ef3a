"""Time in the routed experts' grouped matmuls over the device's busy
time in the traced slice: the kernels XLA's ``ragged-dot`` lowers to on a
TPU (``ragged-dot-*`` custom calls with the target ``tpu_custom_call``,
which ``reduce_trace`` lists among the Pallas kernels). The gathers, the
activation and the combine around them, and the shared experts' dense
matmuls, are fusions no name tells from attention's and are NOT counted:
this is the grouped matmul's own share, the time ``ragged_dot_roofline``
divides by, and NOT the expert layer's (whose sorts, gathers and combine
take about as long again: a true ``moe_time_share`` needs ``reduce_trace``
to read the ``moe/*`` scopes out of the ops' ``op_name``). Nothing on a
program that runs no such kernel."""
LAYER = "models/llama_moe.py dropless expert layer"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.lib.reduce_trace import kernel_seconds

    trace = ctx.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    seconds, calls = kernel_seconds(trace, "ragged-dot")
    if seconds <= 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
