"""Device milliseconds of prefill per 1000 real prompt tokens in the saturated cell. The same reading as ``prefill_ms_per_ktok``, under a name of its own because in
this cell it should move ``tokens_per_s`` (a per-layer metric names ONE
end-to-end metric that it moves)."""
from benchmarks.layer_metrics import prefill_ms_per_ktok as base

LAYER = base.LAYER
UNIT = base.UNIT
MOVES = "tokens_per_s"
SOURCE = base.SOURCE
read = base.read
