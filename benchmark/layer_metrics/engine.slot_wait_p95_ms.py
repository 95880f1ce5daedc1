"""The same span in a closed loop with more clients than slots, where it
is the wait for a slot by construction and decides no PR."""
from harness.layer_common import queue_wait_p95_ms as read  # noqa: F401

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "out_tokens_per_s_chip"
