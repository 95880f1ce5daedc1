"""Share of the extend programs' device seconds under `attn.full`: a piece's
full layers attend over the slot's whole row axis, [H, piece, S] scores
masked (`ops/attention.py::gqa_attention`); a blocked prefill attention would
move this (ROADMAP Reach A)."""
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    return load_layer_metric("extend.window_attn_share.batch").share(ctx, "attn.full")
