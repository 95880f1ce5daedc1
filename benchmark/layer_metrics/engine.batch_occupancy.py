"""Share of decode slot-steps that produced a token, over the measured
window, from `engine.metrics` deltas: (tokens_generated - prefill_steps) /
(decode_steps x num_slots). `tokens_generated` counts every emitted token,
the first of each request included, and that one comes from the prefill
(`prefill_steps` counts one per placement); `decode_steps` counts scan
steps dispatched, whatever the number of live slots."""

LAYER, UNIT, BETTER = "engine scheduler", "%", "higher"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_chip"


def read(ctx):
    c = ctx["counters_window"]
    slot_steps = c["decode_steps"] * ctx["engine"]["num_slots"]
    if slot_steps <= 0:
        return None
    return 100.0 * (c["tokens_generated"] - c["prefill_steps"]) / slot_steps
