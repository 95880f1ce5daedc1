"""The prefill modules' share of the device's busy seconds in the traced
window: how much of what the chip did was prompts and not decode steps.
Where it is near half, `out_tokens_per_s_chip` moves as much with
`step.prefill_ms_per_ktok.batch` as with `step.decode_ms.batch`."""
from harness.layer_common import PREFILL_MODULES

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    seconds = sum(tr["modules"][m]["seconds"] for m in PREFILL_MODULES if m in tr["modules"])
    return 100.0 * seconds / tr["busy_s"] if seconds else None
