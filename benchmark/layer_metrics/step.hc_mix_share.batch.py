"""Share of the decode module's device seconds spent mixing the residual's
copies: the ops under `hc.mix` (the pre-mix and the residual mix), `hc.maps`
(the flat norm, `X Phi`, the sigmoids) and `hc.sinkhorn`
(`omnia_tpu/ops/hyper_connections.py`; the configuration names the scopes,
`program.scopes`). A program without them reads None."""
from harness import spans
from harness.layer_common import DECODE_MODULE

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"
SCOPES = ("hc.mix", "hc.maps", "hc.sinkhorn")


def read(ctx):
    return spans.scope_share(ctx, DECODE_MODULE, *SCOPES)
