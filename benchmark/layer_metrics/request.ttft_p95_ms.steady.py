"""What `ttft_p95_ms` measured end to end in chat-steady until PR 34: the 95th
percentile of due -> first token. At 0.4 of the knee the tail is a few chance
meetings of an arrival with two decode chunks in flight, and the check of PR 34
read its runs too far apart for any bound the contract allows (PERF.md,
section 2), so the cell judges `gap_p95_ms` and this is read beside it. From
the traced run, so `stop_trace`'s hold on the process is in it."""
from harness.layer_common import ttft_percentile

read = ttft_percentile(95)

LAYER, UNIT, BETTER = "provider boundary", "ms", "lower"
SOURCE, MOVES = "host_clock", "gap_p95_ms"
