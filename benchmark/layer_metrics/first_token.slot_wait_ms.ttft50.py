"""Submit -> a slot the request could take was free on the host's books (`LatencyBreakdown.slot_wait_s`): admission and slots.
Mean over the band of the requests around the median first token (40th-60th percentile of first - due) (`harness/first_token.py`), so that the band's stages add up to its mean first token."""
from harness.first_token import stage_ms

read = stage_ms("slot_wait", "ttft50")

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "ttft_p50_ms"
