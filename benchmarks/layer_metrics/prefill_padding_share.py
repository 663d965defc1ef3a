"""Share of the admission prefills' tokens that are padding: 1 - real
prompt tokens over the bucket lengths the prefill programs ran at, from
the engine's own count at each admission
(``serving_prefill_tokens_total``, kinds ``prompt`` and ``bucket``),
window delta. A prompt is padded to the next power-of-two bucket, so a
1100-token document pays for 2048."""
LAYER = "generation.py prefill / decode programs"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks.layer_metrics.queue_wait_mean_ms import series_delta

    if "before" not in ctx:
        return None
    prompt = series_delta(ctx, "serving_prefill_tokens_total", kind="prompt")
    bucket = series_delta(ctx, "serving_prefill_tokens_total", kind="bucket")
    if prompt is None or not bucket:
        return None
    return 100.0 * (1.0 - prompt / bucket)
