"""Share of the decode module's device seconds under `rope.tables` and
`attn.rope`: what the rotary positions cost a step in a model with a table a
kind of attention layer (`omnia_tpu/models/stacks.py::rope_tables`: the window
layers' plain pair and the full layers' YaRN pair with cos and sin scaled,
made once a step; `attn.rope`: each layer turning its q and k by its kind's).
A program without either scope (every model before the tables were made a
kind: their cells do not name them) gives nothing to read."""
from harness import spans
from harness.layer_common import DECODE_MODULE

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    return spans.scope_share(ctx, DECODE_MODULE, "rope.tables", "attn.rope")
