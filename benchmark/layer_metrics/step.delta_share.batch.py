"""Share of the decode module's device seconds spent in the pair family's
linear attention: the ops under `attn.delta` (`omnia_tpu/models/stacks.py`:
everything a delta layer does between the residual's two ends, its output
norm among them) and under the scopes inside it, `delta.conv`,
`delta.gates`, `delta.state` (the kernel `decode_delta_state`), `delta.chunk`
and `delta.out`. `harness/spans.py` gives an op its innermost scope, so the
whole is their sum. It does not grow with the context;
`step.full_attn_share.batch` does."""
from harness import spans
from harness.layer_common import DECODE_MODULE

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"

SCOPES = ("attn.delta", "delta.conv", "delta.gates", "delta.chunk", "delta.state", "delta.out")


def read(ctx):
    return spans.scope_share(ctx, DECODE_MODULE, *SCOPES)
