"""The benchmark's own tests of `k-exaone-236b-a23b` (benchmark/tests/
test_kexaone.py: the configuration file's promises, the flat copies the
reference reads, the byte counts against the parameter trees, the check at
the rehearsal's widths with a window one row too wide, and the seven readers
the cell brings), run by tier-1 as `tests/test_bench_harness_stacks.py` runs
the stacked family's. The program's side of the same model is
`tests/test_kexaone.py`."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (os.path.join(BENCH, "tests"), BENCH):  # the case file; harness
    if path not in sys.path:
        sys.path.insert(0, path)

import importlib.util  # noqa: E402

# (Loaded by path: `tests/test_kexaone.py` has the same module name.)
_spec = importlib.util.spec_from_file_location(
    "bench_test_kexaone", os.path.join(BENCH, "tests", "test_kexaone.py"))
cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cases)
globals().update({name: value for name, value in vars(cases).items()
                  if name.startswith("test_") or name in ("cell", "traced")})


import pytest  # noqa: E402


@pytest.mark.parametrize("metric", cases.NEW_READERS)
def test_a_new_readers_declarations_equal_its_entry(metric):  # noqa: F811
    """The case file's own case, but for the list: it holds a reader's
    `workloads` to this one cell, where benchmark/README.md's rule for a new
    cell is that it joins the list of every reader it reports. What is meant
    is held here: this cell brought the reader, so it stands first, and every
    name in the list is a cell of `BENCHMARK.json` whose cell file lists the
    reader. The benchmark's file is left as it is (PERF.md section 7 says
    which edit a `benchmark` PR owes it)."""
    bench = cases.mf.benchmark_json()
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    mod = cases.load_layer_metric(metric)
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"])
    assert entry["workloads"][0] == cases.CELL and mod.MOVES == "out_tokens_per_s_chip"
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    cells = {w["name"] for w in bench["workloads"]}
    for name in entry["workloads"]:
        assert name in cells and metric in cases.Cell(name).spec["per_layer"], name


def test_tier_1_runs_the_seven_new_readers_cases():
    assert len(cases.NEW_READERS) == 7
    assert test_the_new_readers_read_the_cell is cases.test_the_new_readers_read_the_cell  # noqa: F821
