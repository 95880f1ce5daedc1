"""Share of the decode module's device seconds spent in the expert layer:
the ops under `mlp` (the second norm, the sort of the assignments, the
combine and the shared expert sit directly under it), `moe.route` and
`moe.experts` (`omnia_tpu/models/mla.py`, `ops/moe.py::moe_dropless`), and
the grouped matmuls themselves where the compiler left them without a
scope (`batch.moe_experts_roofline` says how they are found)."""
from harness import spans
from harness.layer_common import DECODE_MODULE
from harness.manifest import load_layer_metric

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def read(ctx):
    red = spans.reduced(ctx)
    per = red["scopes"].get(DECODE_MODULE) if red else None
    if not per:
        return None
    matmuls = load_layer_metric("batch.moe_experts_roofline").grouped_matmul_seconds(ctx)
    mine = per.get("mlp", 0.0) + per.get("moe.route", 0.0) + (matmuls or 0.0)
    return 100.0 * mine / sum(per.values()) if mine else None
