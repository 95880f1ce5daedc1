"""Share of the extend programs' device seconds under `attn.window`: a piece's
window layers attend over a band, [the window's rows before the piece, out of
the ring | the piece] (`ops/attention.py::band_attention`), O(piece x 2 x
window) whatever the context, so this share stays small beside
`extend.full_attn_share.batch`, whose [H, piece, S] scores grow with the
cache's rows."""
from harness import spans
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def share(ctx, *scopes):
    """Share (%) of the extend modules' scoped-or-not device seconds, both
    modules together, under `scopes`."""
    red = spans.reduced(ctx)
    modules = load_layer_metric("step.extend_ms_per_ktok.batch").EXTEND_MODULES
    per = [red["scopes"][m] for m in modules if m in red["scopes"]] if red else []
    mine = sum(p.get(s, 0.0) for p in per for s in scopes)
    return 100.0 * mine / sum(sum(p.values()) for p in per) if mine else None


def read(ctx):
    return share(ctx, "attn.window")
