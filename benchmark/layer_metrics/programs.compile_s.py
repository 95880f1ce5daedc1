"""Seconds of warm-up the backend spent compiling programs the persistent
cache did not hold (`programs.compile` of the coldstart tracker: the
`/jax/core/compile/backend_compile_duration` intervals of the program records
with no `/jax/compilation_cache/cache_hits` inside). 0.0 on a warm start;
over 0 says this side met a miss. `None` where the program keeps no
records."""

LAYER, UNIT, BETTER = "programs warmup", "s", "lower"
SOURCE, MOVES = "program_span", "setup_s"


def read(ctx):
    return ctx["setup"]["phases"].get("programs.compile")
