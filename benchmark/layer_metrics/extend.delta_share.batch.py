"""Share of the extend programs' device seconds (`jit_extend_nosample`,
`jit_extend`: a prompt's pieces) spent in the pair family's linear attention:
`attn.delta` and the scopes inside it (`step.delta_share.batch` names them),
the chunk-wise rule with the state handed from piece to piece among them.
O(piece) whatever the context, where the full layers' [H, piece, S] scores
grow with the cache's rows."""
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    share = load_layer_metric("extend.window_attn_share.batch").share
    return share(ctx, *load_layer_metric("step.delta_share.batch").SCOPES)
