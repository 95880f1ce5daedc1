"""The benchmark's own tests of `jamba2-3b` (benchmark/tests/test_jamba2.py:
the configuration file's promises, what `assumed` states held equal to what
the reference reads, the byte counts against the parameter trees and the
cache's shapes and the issue's arithmetic, the traffic's mix, the check at the
rehearsal's widths with three planted faults, and the five readers the cell
brings), run by tier-1 as `tests/test_bench_olmo_hybrid.py` runs that
configuration's. The program's side of the same model is
`tests/test_jamba2.py`."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (os.path.join(BENCH, "tests"), BENCH):  # the case file; harness
    if path not in sys.path:
        sys.path.insert(0, path)

import importlib.util  # noqa: E402

# (Loaded by path: `tests/test_jamba2.py` has the same module name.)
_spec = importlib.util.spec_from_file_location(
    "bench_test_jamba2", os.path.join(BENCH, "tests", "test_jamba2.py"))
cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cases)
globals().update({name: value for name, value in vars(cases).items()
                  if name.startswith("test_") or name in ("cell", "traced")})


def test_tier_1_runs_the_benchmarks_cases_of_the_configuration():
    assert len(cases.NEW_READERS) == 5
    assert test_the_flat_copies_equal_what_the_reference_reads is (  # noqa: F821
        cases.test_the_flat_copies_equal_what_the_reference_reads)
