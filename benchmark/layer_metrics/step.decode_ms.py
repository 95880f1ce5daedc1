"""Device time of one decode step: the decode module's device seconds in
the trace over the steps it ran."""
from harness.layer_common import decode_step_s

LAYER, UNIT, BETTER = "programs and model", "ms", "lower"
SOURCE, MOVES = "device_trace", "gap_p95_ms"


def read(ctx):
    s = decode_step_s(ctx)
    return None if s is None else s * 1e3
