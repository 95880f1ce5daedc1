"""A slot was free and the engine thread had not come round: until the start of the scheduling pass that claimed the request (`LatencyBreakdown.loop_wait_s`); the thread was reading or emitting a chunk, so the chunk's length bounds it.
Mean over the band of the requests around the median first token (40th-60th percentile of first - due) (`harness/first_token.py`), so that the band's stages add up to its mean first token."""
from harness.first_token import stage_ms

read = stage_ms("loop_wait", "ttft50")

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "ttft_p50_ms"
