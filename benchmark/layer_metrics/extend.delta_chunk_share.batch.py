"""Share of the extend programs' device seconds under `delta.chunk` alone:
the chunk-wise scalar-gated delta rule in plain `jax.numpy`
(`omnia_tpu/ops/delta.py::delta_chunked`: a 64-token chunk's two [64, 64]
products times their decays, the triangular inverse, the state carried from
chunk to chunk), which is what a Pallas kernel for the rule would replace;
the projections, the convolution, the gates and the output around it are
`extend.delta_share.batch`'s."""
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    return load_layer_metric("extend.window_attn_share.batch").share(ctx, "delta.chunk")
