"""`step.hc_mix_share.batch` in the prefill modules, where the n x D stream
of a whole prompt passes through each mix: share of their device seconds
under `hc.mix`, `hc.maps` and `hc.sinkhorn`. A tenth and more of the module
is the issue's line for a kernel of its own."""
from harness import spans
from harness.layer_common import PREFILL_MODULES
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    (module,) = PREFILL_MODULES
    return spans.scope_share(ctx, module, *load_layer_metric("step.hc_mix_share.batch").SCOPES)
