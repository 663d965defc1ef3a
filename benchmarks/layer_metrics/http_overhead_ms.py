"""What the HTTP front adds to a request's first token: the clients'
mean time from SENDING a request to its first streamed token (so the
generator's own lag is out) minus the engine's mean from submission to
first token (``serving_time_to_first_token_seconds``, which starts at
``add_request`` on the engine thread and so already holds the engine's
queue wait), both over the requests whose first token came inside the
window. What is left is HTTP parsing, the wait in the front's submission
queue until the engine thread drains it (up to one whole step), and the
SSE write."""
LAYER = "serving_http.py front and admission"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmarks.layer_metrics.queue_wait_mean_ms import histogram_mean

    engine = histogram_mean(ctx, "serving_time_to_first_token_seconds")
    if engine is None:
        return None
    seconds = ctx["seconds"]
    client = [o["t_first"] - o["t_sent"] for o in ctx.get("outcomes", [])
              if o.get("ok") and o.get("t_sent") is not None
              and o.get("t_first") is not None
              and 0.0 <= o["t_first"] < seconds]
    if not client:
        return None
    return (sum(client) / len(client) - engine) * 1e3
