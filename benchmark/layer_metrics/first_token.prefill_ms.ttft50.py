"""The placement's last piece enqueued -> the first token's stamp (`LatencyBreakdown.prefill_s`): the device's prefill as the host sees it, through the read-back and that token's emit.
Mean over the band of the requests around the median first token (40th-60th percentile of first - due) (`harness/first_token.py`), so that the band's stages add up to its mean first token."""
from harness.first_token import stage_ms

read = stage_ms("prefill", "ttft50")

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "ttft_p50_ms"
