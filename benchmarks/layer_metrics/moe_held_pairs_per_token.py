"""(token, expert) pairs the held experts computed per row routed, per
expert layer: window delta of ``serving_moe_held_pairs_total`` over
``serving_moe_tokens_total`` (rows x expert layers), both counted by the
prefill and decode programs themselves and fetched with a step's tokens.
In expectation top-k x held / routed experts (8 x 16 / 128 = 1.0 in
``command-a-plus-ep8-d4``); it is the work of the grouped expert matmul,
so a seed whose weights load the held share more gets fewer tokens per
second through it. Nothing on a program without the counters."""
LAYER = "models/llama_moe.py dropless expert layer"
UNIT = "pairs/token"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks.lib.common import note
    from benchmarks.layer_metrics.queue_wait_mean_ms import series_delta

    if "before" not in ctx:
        return None
    tokens = series_delta(ctx, "serving_moe_tokens_total")
    pairs = series_delta(ctx, "serving_moe_held_pairs_total")
    if not tokens or pairs is None:
        return None
    note("moe_pairs", routed_rows=tokens, held_pairs=pairs)
    return pairs / tokens
