"""Claim -> the placement's last prefill piece on the device's queue (`LatencyBreakdown.place_s`): the host before the prefill.
Mean over the band of the requests around the median first token (40th-60th percentile of first - due) (`harness/first_token.py`), so that the band's stages add up to its mean first token."""
from harness.first_token import stage_ms

read = stage_ms("place", "ttft50")

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "ttft_p50_ms"
