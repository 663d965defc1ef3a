"""Programs built inside a train cell's window (must read 0). The same reading as ``compiles_in_window``, under a name of its own because in
the train cells it should move ``train_tokens_per_s`` (a per-layer metric names ONE
end-to-end metric that it moves)."""
from benchmarks.layer_metrics import compiles_in_window as base

LAYER = base.LAYER
UNIT = base.UNIT
MOVES = "train_tokens_per_s"
SOURCE = base.SOURCE
read = base.read
