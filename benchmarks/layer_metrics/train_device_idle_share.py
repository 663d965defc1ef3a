"""The device's idle share in a train cell. The same reading as ``device_idle_share``, under a name of its own because in
the train cells it should move ``train_tokens_per_s`` (a per-layer metric names ONE
end-to-end metric that it moves)."""
from benchmarks.layer_metrics import device_idle_share as base

LAYER = base.LAYER
UNIT = base.UNIT
MOVES = "train_tokens_per_s"
SOURCE = base.SOURCE
read = base.read
