"""The handle's first-token stamp -> the consumer's thread running, with `submit()`'s own call in it: (first - sent) - `LatencyBreakdown.ttft_s`.
Mean over the band of the requests around the median first token (40th-60th percentile of first - due) (`harness/first_token.py`), so that the band's stages add up to its mean first token."""
from harness.first_token import stage_ms

read = stage_ms("deliver", "ttft50")

LAYER, UNIT, BETTER = "provider boundary", "ms", "lower"
SOURCE, MOVES = "program_span", "ttft_p50_ms"
