"""Share of the decode module's device seconds spent in latent attention:
the ops under `attn.qkv` (the two low-rank projections and their norms and
rotations), `attn.decode` (the absorbing einsums and the
`decode_mla_attention` kernel) and `attn.out`. The rows written to the
cache are `step.kv_update_share.batch`'s."""
from harness import spans
from harness.layer_common import DECODE_MODULE

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    return spans.scope_share(ctx, DECODE_MODULE, "attn.qkv", "attn.rope", "attn.decode",
                             "attn.out")
