"""Share of the device's idle seconds in the traced window that fall under
the self time of a named `omnia.engine.*` phase on the engine thread
(`harness/spans.py`). What is left is a gap nobody can name: above the
Provider boundary, or in a thread the program puts no span on."""
from harness import spans

LAYER, UNIT, BETTER = "engine scheduler", "%", "higher"
SOURCE, MOVES = "program_span", "gap_p95_ms"


def read(ctx):
    sp = spans.reduced(ctx)
    if not sp or not sp["has_engine_spans"] or sp["idle_s"] <= 0:
        return None
    named = sp["idle_s"] - sp["idle_by_phase"].get(spans.UNATTRIBUTED, 0.0)
    return 100.0 * named / sp["idle_s"]
