"""Share of the decode module's device seconds spent in the pair family's
state-space layers: the ops under `attn.mamba` (`omnia_tpu/models/stacks.py`:
everything a Mamba layer does between the residual's two ends) and under the
scopes inside it, `mamba.in`, `mamba.conv`, `mamba.gates`, `mamba.state` (the
kernel `decode_mamba_state`), `mamba.scan` and `mamba.out`. `harness/spans.py`
gives an op its innermost scope, so the whole is their sum. It does not grow
with the context; `step.full_attn_share.batch` does."""
from harness import spans
from harness.layer_common import DECODE_MODULE

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"

SCOPES = ("attn.mamba", "mamba.in", "mamba.conv", "mamba.gates", "mamba.scan",
          "mamba.state", "mamba.out")


def read(ctx):
    return spans.scope_share(ctx, DECODE_MODULE, *SCOPES)
