"""Share of the extend programs' device seconds under `kda.chunk` alone: the
chunk-wise gated delta rule in plain `jax.numpy` (`omnia_tpu/ops/kda.py::
kda_chunked`: the pairwise decays of a 64-token chunk, the triangular
solve, the state carried from chunk to chunk), which is what a Pallas kernel
for the rule would replace; the projections, the convolution, the gates and
the output around it are `extend.kda_share.batch`'s."""
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    return load_layer_metric("extend.window_attn_share.batch").share(ctx, "kda.chunk")
