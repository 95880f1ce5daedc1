"""`first_token.read_lag_ms.ttft50` in the cell whose first-token tail is the 95th percentile (the cell judges `gap_p95_ms` since PR 34)."""
from harness.layer_common import variant_of

LAYER, UNIT, BETTER, SOURCE, read = variant_of("first_token.read_lag_ms.ttft50")
MOVES = "gap_p95_ms"
