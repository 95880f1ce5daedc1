"""`engine.readback_lag_ms` in a closed loop, where the cell judges tokens/s/chip and not the gap's tail."""
from harness.layer_common import variant_of

LAYER, UNIT, BETTER, SOURCE, read = variant_of("engine.readback_lag_ms")
MOVES = "out_tokens_per_s_chip"
