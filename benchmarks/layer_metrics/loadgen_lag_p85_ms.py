"""How late the load generator itself ran: sent minus due, 85th
percentile over the window's requests. Above a few ms, time to first
token is the generator's reading and not the server's."""
LAYER = "load generator (benchmark's own)"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"


def read(ctx):
    from benchmarks.lib.loadgen.schedule import percentile

    lags = [(o["t_sent"] - o["t_due"]) * 1e3
            for o in ctx.get("window_outcomes", [])
            if o.get("t_sent") is not None]
    return percentile(lags, 85) if ctx.get("kind") == "open_loop" else None
