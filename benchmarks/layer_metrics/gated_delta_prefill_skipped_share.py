"""Share of the 64-token chunks handed to the chunked gated delta prefill
kernel (``gated_delta_prefill``) that it skipped: chunks past the prompt,
all pads, for which it does no math and fetches nothing. Window delta of
``serving_gdn_prefill_chunks_total{kind="skipped"}`` over both kinds
(``run`` + ``skipped``), counted by the engine at each admission from the
prompt's length and its bucket, times linear layers. What the bucket rule
pads whole chunks with; nothing on a program without the counter."""
LAYER = "ops/pallas + bundled splash / paged kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks.layer_metrics.queue_wait_mean_ms import series_delta

    if "before" not in ctx:
        return None
    name = "serving_gdn_prefill_chunks_total"
    skipped = series_delta(ctx, name, kind="skipped")
    run = series_delta(ctx, name, kind="run")
    if skipped is None or run is None or skipped + run <= 0:
        return None
    return 100.0 * skipped / (skipped + run)
