"""Device time of `jit_prefill_insert` per 1000 prompt tokens, in the cell whose first-token tail is the
95th percentile (`request.ttft_p95_ms.steady`; judged end to end until the check of PR 34, so the cell's `gap_p95_ms` is what it names)."""
from harness.layer_common import prefill_ms_per_ktok as read  # noqa: F401

LAYER, UNIT, BETTER = "programs and model", "ms", "lower"
SOURCE, MOVES = "device_trace", "gap_p95_ms"
