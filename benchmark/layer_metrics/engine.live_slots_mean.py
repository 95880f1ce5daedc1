"""Mean number of slots live in a decode step over the measured window,
counted where the batch is formed (`decode_slot_steps` / `decode_steps`),
not reckoned from tokens."""

LAYER, UNIT, BETTER = "engine scheduler", "slots", "higher"
SOURCE, MOVES = "program_counter", "gap_p95_ms"


def read(ctx):
    c = ctx["counters_window"]
    if "decode_slot_steps" not in c or c.get("decode_steps", 0) <= 0:
        return None
    return c["decode_slot_steps"] / c["decode_steps"]
