"""The five readers of warm-up's stages (`layer_metrics/programs.{trace,lower,
compile,cache_load,run}_s.py`) on hand-made contexts: each returns the float,
0.0 kept, and `None` only where the program keeps no records (the parent of
the PR that brought them, whose phases hold no `programs.*` key). Their
declarations are the ones a `benchmark` PR enters in `BENCHMARK.json`; once it
has, each of the seven closed-loop cells lists all five and the two open loops
of `mistral-7b` none (they warm eval-batch's programs)."""
import pytest

from harness.manifest import Cell, benchmark_json, load_layer_metric

READERS = {  # reader -> the keys of `ctx["setup"]["phases"]` it adds up
    "programs.trace_s": ("programs.trace",),
    "programs.lower_s": ("programs.lower",),
    "programs.compile_s": ("programs.compile",),
    "programs.cache_load_s": ("programs.cache_load",),
    "programs.run_s": ("programs.run", "programs.drain"),
}
DECLARED = {"layer": "programs warmup", "unit": "s", "better": "lower",
            "source": "program_span", "moves": "setup_s"}
CELLS = [  # one a configuration: warm-up's stages follow the program set
    "mistral-7b.eval-batch", "mistral-small-4.reason-batch", "xing4-29b-a4b.judge-batch",
    "k-exaone-236b-a23b.longdoc-batch", "kimi-linear-48b-a3b.longdoc-wide",
    "mellum2-12b-a2p5b.code-mixed", "olmo-hybrid-7b.think-batch",
]
# As the tracker writes them after a warm start (judge-batch on the chip).
PHASES = {
    "backend_init": 21.4, "warmup_compile": 36.1, "warmup_restore": 1.9,
    "programs.trace": 14.2, "programs.lower": 9.8, "programs.compile": 0.0,
    "programs.cache_load": 11.3, "programs.run": 0.5, "programs.drain": 0.25,
    "programs.other": 0.0, "programs.after": 0.4,
}


def _ctx(phases):
    return {"setup": {"setup_s": 90.0, "phases": phases}}


@pytest.mark.parametrize("name", READERS)
def test_a_reader_adds_up_its_keys(name):
    want = sum(PHASES[k] for k in READERS[name])
    assert load_layer_metric(name).read(_ctx(PHASES)) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_zero_seconds_is_a_number(name):
    """A warm start compiled nothing: 0.0, where `or None` would drop the
    one reading that says the cache held."""
    zeros = {k: 0.0 for k in PHASES}
    value = load_layer_metric(name).read(_ctx(zeros))
    assert value == 0.0 and value is not None


@pytest.mark.parametrize("name", READERS)
def test_no_key_no_number(name):
    """The parent's phases: the driver lays these files over its checkout
    for the traced runs, and the reader must not raise there."""
    parent = {k: v for k, v in PHASES.items() if not k.startswith("programs.")}
    assert load_layer_metric(name).read(_ctx(parent)) is None


def test_the_accepted_reader_does_not_see_the_stages():
    read = load_layer_metric("programs.warmup_s").read
    assert read(_ctx(PHASES)) == pytest.approx(36.1 + 1.9)


@pytest.mark.parametrize("name", READERS)
def test_declarations(name):
    """What the file declares is what its `BENCHMARK.json` entry says, from
    the day it has one (`harness/manifest.py::Cell` raises otherwise)."""
    mod = load_layer_metric(name)
    mine = {"layer": mod.LAYER, "unit": mod.UNIT, "better": mod.BETTER,
            "source": mod.SOURCE, "moves": mod.MOVES}
    assert mine == DECLARED
    entry = next((m for m in benchmark_json()["per_layer"] if m["name"] == name), None)
    if entry is not None:
        assert {k: entry[k] for k in DECLARED} == DECLARED
        assert entry["workloads"] == CELLS


@pytest.mark.parametrize("cell", [w["name"] for w in benchmark_json()["workloads"]])
def test_a_cell_lists_all_five_or_none(cell):
    """All five where the cell is one of the seven and the entries are in,
    none anywhere else: a cell file that lists a reader without its entry
    does not load."""
    listed = {n for n, _ in Cell(cell).layer_metrics} & set(READERS)
    entered = {m["name"] for m in benchmark_json()["per_layer"]} & set(READERS)
    assert listed == (entered if cell in CELLS else set())
    assert entered in (set(), set(READERS))
