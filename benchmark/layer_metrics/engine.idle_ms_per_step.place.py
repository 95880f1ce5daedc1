"""Device idle time per decode step that falls under `omnia.engine.place`:
placement outside its program calls (session and page bookkeeping, the first token's readback, the scatters into the slot vectors)."""
from harness import spans

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "gap_p95_ms"


def read(ctx):
    return spans.idle_ms_per_step(ctx, "omnia.engine.place")
