"""Time in Pallas kernels (``custom-call`` instructions with the target
``tpu_custom_call``) over the device's busy time in the traced window.
Expected 0 on the mesh: no Mosaic kernel under GSPMD (ROADMAP D11)."""
LAYER = "ops/pallas + bundled splash / paged kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
