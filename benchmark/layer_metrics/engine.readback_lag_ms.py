"""How long a decode chunk's finished tokens took to reach a host that was already asking: end of the `.chunk_sync`
less the later of the end of the module it waited for and its own start; mean over the chunks read in the trace
(`harness/causal.py`). The transfer, and then the wait for the engine thread to run again."""
from harness.causal import readback_lag_ms as read  # noqa: F401

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "device_trace", "gap_p95_ms"
