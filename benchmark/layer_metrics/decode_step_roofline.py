"""The decode step's share of its roofline, bound by HBM: the least time
for one step (weights once + the live contexts' K and V rows once, bytes
from shapes by `harness/roofline.py`, over the chips' HBM bandwidth from
peaks.json) over the step's device time."""
from harness import roofline
from harness.layer_common import decode_step_s, live_context_tokens

LAYER, UNIT, BETTER = "programs and model", "%", "higher"
SOURCE, MOVES = "device_trace", "gap_p95_ms"


def read(ctx):
    step = decode_step_s(ctx)
    if not step:
        return None
    floor = roofline.decode_step_floor_s(
        ctx["model"], live_context_tokens(ctx), ctx["chips"],
        ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor / step
