"""A slot was free and the engine thread had not come round: until the start of the scheduling pass that claimed the request (`LatencyBreakdown.loop_wait_s`); the thread was reading or emitting a chunk, so the chunk's length bounds it.
Mean over the band of the requests of the first-token tail (90th percentile of first - due and above; the cell judges `gap_p95_ms` since PR 34) (`harness/first_token.py`), so that the band's stages add up to its mean first token."""
from harness.first_token import stage_ms

read = stage_ms("loop_wait", "ttft95")

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "gap_p95_ms"
