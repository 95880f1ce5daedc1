"""Seconds of warm-up spent running what was compiled: after each program
record's last compile or load to the record's end (`programs.run`), and the
closing wait for the device over the warm-up states (`programs.drain`).
`None` where the program keeps no records."""

LAYER, UNIT, BETTER = "programs warmup", "s", "lower"
SOURCE, MOVES = "program_span", "setup_s"


def read(ctx):
    phases = ctx["setup"]["phases"]
    if "programs.run" not in phases:
        return None
    return phases["programs.run"] + phases.get("programs.drain", 0.0)
