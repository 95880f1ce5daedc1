"""The routed experts' grouped matmuls' share of their roofline in a decode
step, bound by HBM: the bytes of the experts that got a token (the
program's counter `moe_experts_hit`, a layer a step, over its
`decode_steps`, times one expert's three matrices from shapes:
`decode_bytes/mla_moe_bytes.py::expert_bytes`) over the chips' HBM bandwidth,
over the grouped matmuls' device seconds a step. Over 100 % is a wrong
count, not a fast kernel.

The grouped matmuls are what `jax.lax.ragged_dot` becomes under the
program's `moe.experts` scope (`ops/moe.py::moe_dropless`). This compiler
expands each into an op it names `ragged-dot…` and drops the scope from it
(my chip run, PR 32: the three sit under `unscoped`), so those ops are
counted by name; where a trace keeps them under the scope, the scope's
seconds are."""
from harness import spans
from harness.layer_common import DECODE_MODULE, decode_steps_in_trace
from harness.manifest import load_decode_bytes

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"


def grouped_matmul_seconds(ctx):
    """Device seconds of the grouped matmuls inside the decode module over
    the traced span: the larger of what the ops named `ragged-dot…` took
    and what the `moe.experts` scope holds (it holds them too where the
    compiler kept the scope on them); None where a trace has neither."""
    tr = ctx.get("trace")
    if not tr:
        return None
    ops = tr["ops_in_module"].get(DECODE_MODULE, {})
    by_name = sum(s for name, (_n, s) in ops.items() if name.startswith("ragged-dot"))
    red = spans.reduced(ctx)
    scoped = (red["scopes"].get(DECODE_MODULE, {}) if red else {}).get("moe.experts", 0.0)
    return max(by_name, scoped) or None


def read(ctx):
    steps = decode_steps_in_trace(ctx)
    counters = (ctx.get("traced") or {}).get("counters", {})
    hit, dispatched = counters.get("moe_experts_hit"), counters.get("decode_steps")
    seconds = grouped_matmul_seconds(ctx)
    if not steps or not hit or not dispatched or not seconds:
        return None
    bytes_a_step = hit / dispatched * load_decode_bytes(ctx["model"]).expert_bytes(ctx["model"])
    floor = bytes_a_step / (ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor / (seconds / steps)
