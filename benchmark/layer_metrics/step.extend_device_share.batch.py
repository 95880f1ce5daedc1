"""The extend programs' share of the device's busy seconds in the traced
window: how much of what the chip did was prompts placed in pieces and not
decode steps (`step.prefill_device_share.batch` for a cell whose prompts go
through `jit_extend_nosample` / `jit_extend`)."""
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    tr = ctx.get("trace")
    seconds = load_layer_metric("step.extend_ms_per_ktok.batch").extend_seconds(ctx)
    if not seconds or not tr["busy_s"]:
        return None
    return 100.0 * seconds / tr["busy_s"]
