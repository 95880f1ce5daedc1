"""Share of the prefill and extend programs' device seconds
(`jit_prefill_insert`; `jit_extend_nosample`, `jit_extend`: a prompt's pieces)
spent in the pair family's state-space layers: `attn.mamba` and the scopes
inside it (`step.mamba_share.batch` names them), the scan with the state
handed from piece to piece among them. O(piece) whatever the context, where
the attention layers' scores grow with the cache's rows."""
from harness import spans
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"

PREFILL_MODULE = "jit_prefill_insert"


def share(ctx, *scopes):
    """Share (%) of the prefill and extend modules' device seconds, all of
    them together, under `scopes`."""
    red = spans.reduced(ctx)
    modules = (PREFILL_MODULE,
               *load_layer_metric("step.extend_ms_per_ktok.batch").EXTEND_MODULES)
    per = [red["scopes"][m] for m in modules if m in red["scopes"]] if red else []
    mine = sum(p.get(s, 0.0) for p in per for s in scopes)
    return 100.0 * mine / sum(sum(p.values()) for p in per) if mine else None


def read(ctx):
    return share(ctx, *load_layer_metric("step.mamba_share.batch").SCOPES)
