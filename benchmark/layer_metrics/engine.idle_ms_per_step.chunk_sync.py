"""Device idle time per decode step that falls under `omnia.engine.chunk_sync`:
the wait for a decode chunk's tokens to reach the host."""
from harness import spans

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "gap_p95_ms"


def read(ctx):
    return spans.idle_ms_per_step(ctx, "omnia.engine.chunk_sync")
