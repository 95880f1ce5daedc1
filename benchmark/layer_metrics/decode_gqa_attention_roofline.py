"""The Pallas decode-attention kernel's share of its roofline, bound by
HBM: the K and V rows of the live contexts read once (bytes from shapes,
`harness/roofline.py`) over the chips' HBM bandwidth, over the kernel's
device time for one step (its seconds in the decode module over the steps)."""
from harness import roofline
from harness.layer_common import (decode_steps_in_trace, kernel_in_decode,
                                  live_context_tokens)

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "gap_p95_ms"


def read(ctx):
    kernel, steps = kernel_in_decode(ctx), decode_steps_in_trace(ctx)
    if not kernel or not steps:
        return None
    floor = (live_context_tokens(ctx) * roofline.kv_bytes_per_token(ctx["model"])
             / (ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"]))
    return 100.0 * floor / (kernel[1] / steps)
