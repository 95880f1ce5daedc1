"""`step.decode_ms` where the cell judges the median first token and not the gap's tail."""
from harness.layer_common import variant_of

LAYER, UNIT, BETTER, SOURCE, read = variant_of("step.decode_ms")
MOVES = "ttft_p50_ms"
