"""The selective scan's state kernel's (`decode_mamba_state`) share of its
roofline, bound by HBM: the states it updates (the program's counter
`decode_mamba_slots`: live slots, a Mamba layer a step, over its
`decode_steps`), each read once and written once in float32, and the step's
vectors beside them (`decode_bytes/jamba_bytes.py::state_bytes`,
`step_vector_bytes`), over the chips' HBM bandwidth, over the kernel's device
seconds a step. The steps are the configuration's (`program.decode_kernel`'s
calls over the layers that call it). A dead slot inside a visited group of
eight is moved and not counted, so a batch with holes reads lower. Over 100 %
is a wrong count, not a fast kernel. A program without the counter or the
kernel (the parent of PR 54) reads None."""
from harness.layer_common import DECODE_MODULE, decode_steps_in_trace
from harness.manifest import load_decode_bytes

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"

KERNEL = "decode_mamba_state"


def read(ctx):
    tr, steps = ctx.get("trace"), decode_steps_in_trace(ctx)
    counters = (ctx.get("traced") or {}).get("counters", {})
    slots, dispatched = counters.get("decode_mamba_slots"), counters.get("decode_steps")
    sizes = load_decode_bytes(ctx["model"])
    if (not tr or not steps or not slots or not dispatched
            or not hasattr(sizes, "step_vector_bytes")):
        return None
    seconds = sum(s for name, (_n, s) in tr["ops_in_module"].get(DECODE_MODULE, {}).items()
                  if name.split(".")[0] == KERNEL)
    if not seconds:
        return None
    a_slot = sizes.state_bytes(ctx["model"]) + sizes.step_vector_bytes(ctx["model"])
    floor = slots / dispatched * a_slot / (ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor / (seconds / steps)
