"""`engine.idle_ms_per_step.dispatch` in a closed loop, where the cell judges tokens/s/chip and not the gap's tail."""
from harness.layer_common import variant_of

LAYER, UNIT, BETTER, SOURCE, read = variant_of("engine.idle_ms_per_step.dispatch")
MOVES = "out_tokens_per_s_chip"
