"""Device time of `jit_prefill_insert` per 1000 prompt tokens, in cells that judge the median first token."""
from harness.layer_common import prefill_ms_per_ktok as read  # noqa: F401

LAYER, UNIT, BETTER = "programs and model", "ms", "lower"
SOURCE, MOVES = "device_trace", "ttft_p50_ms"
