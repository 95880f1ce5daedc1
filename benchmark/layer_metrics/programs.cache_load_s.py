"""Seconds of warm-up spent reading, unpacking and loading executables the
persistent cache held (`programs.cache_load` of the coldstart tracker: the
`/jax/core/compile/backend_compile_duration` intervals of the program records
with a `/jax/compilation_cache/cache_hits` inside). `None` where the program
keeps no records."""

LAYER, UNIT, BETTER = "programs warmup", "s", "lower"
SOURCE, MOVES = "program_span", "setup_s"


def read(ctx):
    return ctx["setup"]["phases"].get("programs.cache_load")
