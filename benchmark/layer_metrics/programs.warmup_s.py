"""Seconds of set-up spent in `engine.warmup()` (the coldstart tracker's
warmup_compile + warmup_restore phases)."""

LAYER, UNIT, BETTER = "programs warmup", "s", "lower"
SOURCE, MOVES = "program_span", "setup_s"


def read(ctx):
    phases = ctx["setup"]["phases"]
    return sum(v for k, v in phases.items() if k.startswith("warmup")) or None
