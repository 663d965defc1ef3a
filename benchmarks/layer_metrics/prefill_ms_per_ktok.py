"""Device milliseconds of prefill per 1000 REAL (unpadded) prompt tokens,
from the traced slice: the time of every program other than the decode
step (prefill and scatter programs of all buckets) over the prompt
tokens of the requests whose first token came inside the slice. Padding
to the bucket and the dense attention above the 512 bucket show here."""
LAYER = "generation.py prefill / decode programs"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.layer_metrics.decode_step_ms import (MIN_PROGRAM_S,
                                                         decode_module)

    trace, span = ctx.get("trace"), ctx.get("trace_window")
    found = decode_module(trace)
    if found is None or span is None or ctx.get("kind") == "train_job":
        return None
    prefill_s = sum(m["total_s"] for name, m in trace["modules"].items()
                    if name != found[0] and m["median_s"] >= MIN_PROGRAM_S)
    tokens = sum(o["n_prompt"] for o in ctx["outcomes"]
                 if o.get("t_first") is not None
                 and span[0] <= o["t_first"] < span[1])
    return 1e3 * prefill_s / (tokens / 1e3) if tokens else None
