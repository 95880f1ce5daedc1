"""Submit -> a slot the request could take was free on the host's books (`LatencyBreakdown.slot_wait_s`): admission and slots.
Mean over the band of the requests of the first-token tail (90th percentile of first - due and above; the cell judges `gap_p95_ms` since PR 34) (`harness/first_token.py`), so that the band's stages add up to its mean first token."""
from harness.first_token import stage_ms

read = stage_ms("slot_wait", "ttft95")

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "gap_p95_ms"
