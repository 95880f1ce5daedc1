"""The window layers' decode kernel's (`decode_window_attention`) share of
its roofline, bound by HBM: the ring rows it spans for the live slots (the
program's counter `decode_window_rows`, a window layer a step, over its
`decode_steps`), K and V, in every window layer, read once
(`decode_bytes/kexaone_bytes.py::window_row_bytes`), over the chips' HBM
bandwidth, over the kernel's device seconds a step. The steps are the
configuration's (`program.decode_kernel`'s calls over the layers that call
it). Over 100 % is a wrong count, not a fast kernel."""
from harness.layer_common import DECODE_MODULE, decode_steps_in_trace
from harness.manifest import load_decode_bytes

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"

KERNEL = "decode_window_attention"


def read(ctx):
    tr, steps = ctx.get("trace"), decode_steps_in_trace(ctx)
    counters = (ctx.get("traced") or {}).get("counters", {})
    rows, dispatched = counters.get("decode_window_rows"), counters.get("decode_steps")
    if not tr or not steps or not rows or not dispatched:
        return None
    seconds = sum(s for name, (_n, s) in tr["ops_in_module"].get(DECODE_MODULE, {}).items()
                  if name.split(".")[0] == KERNEL)
    if not seconds:
        return None
    bytes_a_step = rows / dispatched * load_decode_bytes(ctx["model"]).window_row_bytes(ctx["model"])
    floor = bytes_a_step / (ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor / (seconds / steps)
