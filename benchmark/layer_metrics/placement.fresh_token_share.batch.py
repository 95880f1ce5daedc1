"""Of the prompt tokens placed in the measured window, the share that one
fresh prefill placed (`prefill_insert`: a prompt of at most the largest
bucket, which over rings leaves its ring partly filled or exactly filled and
never wrapped), from `engine.metrics` deltas: 1 - `extend_tokens` /
`prefill_tokens`, `extend_tokens` being those of placements that went through
the extend programs in pieces. It says which mix of the two placement routes
the window ran: `code-mixed` offers 9.8 % of a round's prompt tokens in
prompts of at most 1,024. An engine without the counter gives nothing to
read."""

LAYER, UNIT, BETTER = "engine scheduler", "%", "higher"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_chip"


def read(ctx):
    c = ctx.get("counters_window") or {}
    placed, pieces = c.get("prefill_tokens"), c.get("extend_tokens")
    if not placed or pieces is None:
        return None
    return 100.0 * (placed - pieces) / placed
