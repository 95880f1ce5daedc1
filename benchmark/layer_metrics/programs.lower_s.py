"""Seconds of warm-up spent lowering jaxprs to StableHLO, each Pallas kernel's
lowering to Mosaic included, which no cache saves (`programs.lower` of the
coldstart tracker: the intervals that
`/jax/core/compile/jaxpr_to_mlir_module_duration` covers inside the program
records). `None` where the program keeps no records."""

LAYER, UNIT, BETTER = "programs warmup", "s", "lower"
SOURCE, MOVES = "program_span", "setup_s"


def read(ctx):
    return ctx["setup"]["phases"].get("programs.lower")
