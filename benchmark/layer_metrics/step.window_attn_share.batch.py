"""Share of the decode module's device seconds under `attn.window`: the
window layers' attention over their rings (`omnia_tpu/models/llama.py`:
`ring_decode_attention`, the kernel `decode_window_attention`). A ring is at
most `sliding_window` rounded up to a power of two rows a slot, so this share
does not grow with the context; `step.full_attn_share.batch` does."""
from harness import spans
from harness.layer_common import DECODE_MODULE

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    return spans.scope_share(ctx, DECODE_MODULE, "attn.window")
