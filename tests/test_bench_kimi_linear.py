"""The benchmark's own tests of `kimi-linear-48b-a3b` (benchmark/tests/
test_kimi_linear.py: the configuration file's promises, the flat copies the
reference reads, the byte counts against the parameter trees, the check at
the rehearsal's widths with the decay behind the update, and the four readers
the cell brings), run by tier-1 as `tests/test_bench_kexaone.py` runs
`k-exaone-236b-a23b`'s. The program's side of the same model is
`tests/test_kimi_linear.py`."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (os.path.join(BENCH, "tests"), BENCH):  # the case file; harness
    if path not in sys.path:
        sys.path.insert(0, path)

import importlib.util  # noqa: E402

# (Loaded by path: `tests/test_kimi_linear.py` has the same module name.)
_spec = importlib.util.spec_from_file_location(
    "bench_test_kimi_linear", os.path.join(BENCH, "tests", "test_kimi_linear.py"))
cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cases)
globals().update({name: value for name, value in vars(cases).items()
                  if name.startswith("test_") or name in ("cell", "traced")})


def test_tier_1_runs_the_four_new_readers_cases():
    assert len(cases.NEW_READERS) == 4
    assert test_the_new_readers_read_the_cell is cases.test_the_new_readers_read_the_cell  # noqa: F821
