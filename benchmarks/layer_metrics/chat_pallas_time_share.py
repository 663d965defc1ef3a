"""Time in Pallas kernels over busy time in the chat cell. The same reading as ``pallas_time_share``, under a name of its own because in
this cell it should move ``itl_p95_ms`` (a per-layer metric names ONE
end-to-end metric that it moves)."""
from benchmarks.layer_metrics import pallas_time_share as base

LAYER = base.LAYER
UNIT = base.UNIT
MOVES = "itl_p95_ms"
SOURCE = base.SOURCE
read = base.read
