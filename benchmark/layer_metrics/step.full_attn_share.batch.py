"""Share of the decode module's device seconds under `attn.full`: the full
layers' attention over whole contexts (the kernel `decode_gqa_attention`),
in a model whose other layers attend over a window
(`step.window_attn_share.batch`)."""
from harness import spans
from harness.layer_common import DECODE_MODULE

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    return spans.scope_share(ctx, DECODE_MODULE, "attn.full")
