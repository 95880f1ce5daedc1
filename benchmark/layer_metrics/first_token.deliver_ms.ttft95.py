"""The handle's first-token stamp -> the consumer's thread running, with `submit()`'s own call in it: (first - sent) - `LatencyBreakdown.ttft_s`.
Mean over the band of the requests of the first-token tail (90th percentile of first - due and above; the cell judges `gap_p95_ms` since PR 34) (`harness/first_token.py`), so that the band's stages add up to its mean first token."""
from harness.first_token import stage_ms

read = stage_ms("deliver", "ttft95")

LAYER, UNIT, BETTER = "provider boundary", "ms", "lower"
SOURCE, MOVES = "program_span", "gap_p95_ms"
