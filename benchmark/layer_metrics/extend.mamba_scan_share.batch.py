"""Share of the prefill and extend programs' device seconds under `mamba.scan`
alone: the selective scan over a piece's rows (`omnia_tpu/ops/mamba.py`: the
Pallas kernel `mamba_scan` where the piece is whole blocks of 128 tokens, with
the spreading of B and C over a tile's lanes in front of it; `mamba_chunked`
in plain `jax.numpy` for any other length); the projections, the convolution,
the gates and the output around it are `extend.mamba_share.batch`'s, and the
kernel's own seconds against its bytes `batch.mamba_scan_roofline`'s."""
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    return load_layer_metric("extend.mamba_share.batch").share(ctx, "mamba.scan")
