"""Share of the decode steps of the measured window that the one-step
decode program ran (`decode_dispatches_single` / `decode_steps`): the
scheduler asks for it while requests wait (`single=queued`), and it is
slower a step than the chunk of 8."""

LAYER, UNIT, BETTER = "engine scheduler", "%", "lower"
SOURCE, MOVES = "program_counter", "gap_p95_ms"


def read(ctx):
    c = ctx["counters_window"]
    if "decode_dispatches_single" not in c or c.get("decode_steps", 0) <= 0:
        return None
    return 100.0 * c["decode_dispatches_single"] / c["decode_steps"]
