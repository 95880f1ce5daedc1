"""Seconds of warm-up spent tracing: the program records' `trace_s` summed
(`programs.trace` of the coldstart tracker: a record's wall before its last
compile or load ended that neither lowering nor the backend covers, which is
the Python that traces the programs and builds their operands;
`/jax/core/compile/jaxpr_trace_duration` says where tracing ended). `None`
where the program keeps no records."""

LAYER, UNIT, BETTER = "programs warmup", "s", "lower"
SOURCE, MOVES = "program_span", "setup_s"


def read(ctx):
    return ctx["setup"]["phases"].get("programs.trace")
