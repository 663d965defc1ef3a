"""Time from when a request was DUE to its first streamed token, client
side, 85th percentile over the window's requests (the highest round
percentile with ten samples beyond it among 90). What a chat user feels
first; but it repeats only to about 4% from run to run (the phase of the
engine's 45 ms steps at each arrival, and the host's speed), which no
bound of at most 10% can hold, so it is recorded here and not judged.
The prefill that makes one request's first token is the stall in every
other request's gaps, hence ``moves``."""
LAYER = "serving_http.py front and admission"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"


def read(ctx):
    from benchmarks.lib.loadgen.schedule import percentile

    if ctx.get("kind") != "open_loop":
        return None
    return percentile(ctx.get("ttft_ms", []), 85)
