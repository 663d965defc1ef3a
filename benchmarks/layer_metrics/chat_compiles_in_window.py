"""Programs built inside the chat cell's window (must read 0). The same reading as ``compiles_in_window``, under a name of its own because in
this cell it should move ``itl_p95_ms`` (a per-layer metric names ONE
end-to-end metric that it moves)."""
from benchmarks.layer_metrics import compiles_in_window as base

LAYER = base.LAYER
UNIT = base.UNIT
MOVES = "itl_p95_ms"
SOURCE = base.SOURCE
read = base.read
