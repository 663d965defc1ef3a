"""Time in Pallas kernels over busy time in a train cell (0 on the mesh, D11). The same reading as ``pallas_time_share``, under a name of its own because in
the train cells it should move ``train_tokens_per_s`` (a per-layer metric names ONE
end-to-end metric that it moves)."""
from benchmarks.layer_metrics import pallas_time_share as base

LAYER = base.LAYER
UNIT = base.UNIT
MOVES = "train_tokens_per_s"
SOURCE = base.SOURCE
read = base.read
