"""How long a finished first token waited: end of its `.chunk_sync` less the end of its last prefill module, the
transfer and the deferral behind the next decode dispatch included; mean over the placements whose first token was
read in the trace (`harness/causal.py`), in cells that judge the median first token."""
from harness.causal import read_lag_ms as read  # noqa: F401

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "device_trace", "ttft_p50_ms"
