"""Device time of the extend programs (`jit_extend_nosample`, the pieces of a
prompt placed against the slot's rows, and `jit_extend`, its last piece with
the first token's sample) per 1000 prompt tokens prefilled while the trace
ran (`prefill_tokens` delta), in a cell whose prompts are longer than its
largest prefill bucket and so are all placed in pieces: what
`step.prefill_ms_per_ktok.batch` is where `jit_prefill_insert` places them.
A parent without the programs' scopes still has the modules; a trace without
them reads None."""
LAYER, UNIT, BETTER = "programs and model", "ms", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"

EXTEND_MODULES = ("jit_extend_nosample", "jit_extend")


def extend_seconds(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return sum(tr["modules"][m]["seconds"] for m in EXTEND_MODULES if m in tr["modules"]) or None


def read(ctx):
    seconds = extend_seconds(ctx)
    tokens = ((ctx.get("traced") or {}).get("counters") or {}).get("prefill_tokens")
    if not seconds or not tokens:
        return None
    return seconds / tokens * 1e6
