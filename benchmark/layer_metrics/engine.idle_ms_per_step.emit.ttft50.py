"""`engine.idle_ms_per_step.emit` where the cell judges the median first token and not the gap's tail."""
from harness.layer_common import variant_of

LAYER, UNIT, BETTER, SOURCE, read = variant_of("engine.idle_ms_per_step.emit")
MOVES = "ttft_p50_ms"
