"""Device idle time per decode step that falls under the program calls
themselves, `omnia.engine.decode_dispatch` and
`omnia.engine.prefill_dispatch`: building the operands and the enqueue."""
from harness import spans

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "gap_p95_ms"


def read(ctx):
    return spans.idle_ms_per_step(
        ctx, "omnia.engine.decode_dispatch", "omnia.engine.prefill_dispatch")
