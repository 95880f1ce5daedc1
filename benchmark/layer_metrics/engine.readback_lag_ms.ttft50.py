"""`engine.readback_lag_ms` in a cell that judges the median first token."""
from harness.layer_common import variant_of

LAYER, UNIT, BETTER, SOURCE, read = variant_of("engine.readback_lag_ms")
MOVES = "ttft_p50_ms"
