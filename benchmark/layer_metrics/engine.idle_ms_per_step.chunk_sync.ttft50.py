"""`engine.idle_ms_per_step.chunk_sync` where the cell judges the median first token and not the gap's tail."""
from harness.layer_common import variant_of

LAYER, UNIT, BETTER, SOURCE, read = variant_of("engine.idle_ms_per_step.chunk_sync")
MOVES = "ttft_p50_ms"
