"""What `gap_p95_ms` measures, in a closed loop, where the cell judges tokens/s/chip and not the gap's tail: the check of PR 28 read its runs there
too far apart for any bound the contract allows (PERF.md, section 2). From the traced
run, so `stop_trace`'s hold on the process is in it."""
from harness.metrics import gap_p95_ms as read  # noqa: F401

LAYER, UNIT, BETTER = "provider boundary", "ms", "lower"
SOURCE, MOVES = "host_clock", "out_tokens_per_s_chip"
