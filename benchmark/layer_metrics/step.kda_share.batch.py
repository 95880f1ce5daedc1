"""Share of the decode module's device seconds spent in linear attention:
the ops under `attn.kda` (`omnia_tpu/models/mla.py::_kda_layer`: everything
such a layer does between its norm and the residual) and under the scopes
inside it, `kda.conv`, `kda.gates`, `kda.state` (the kernel
`decode_kda_state`), `kda.chunk` and `kda.out`. `harness/spans.py` gives an op
its innermost scope, so the whole is their sum. It does not grow with the
context; `step.latent_attn_share.batch` does."""
from harness import spans
from harness.layer_common import DECODE_MODULE

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"

SCOPES = ("attn.kda", "kda.conv", "kda.gates", "kda.chunk", "kda.state", "kda.out")


def read(ctx):
    return spans.scope_share(ctx, DECODE_MODULE, *SCOPES)
