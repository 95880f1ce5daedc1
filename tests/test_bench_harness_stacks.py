"""The benchmark's own tests of a family whose layers are not all alike, run
by tier-1: the cases of `benchmark/tests/test_stacks.py` (PR 34, a `benchmark`
PR, which may not touch `tests/`), imported as `tests/test_mla.py` imports the
plain reference. They take the fixture of `benchmark/tests/stacked_family/`
through the manifest, the weights, the check and the readers, and plant five
faults that `harness/correct.py` must refuse: two stacks in the wrong order,
the cache read at the wrong layer across the stacks, one sparse layer's
experts swapped with another's, a period's kinds exchanged, and the wide
stream folded to the model's width."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (os.path.join(BENCH, "tests"), BENCH):  # fixture_family, stacked_family; harness
    if path not in sys.path:
        sys.path.insert(0, path)

import test_stacks as cases  # noqa: E402
from test_stacks import *  # noqa: E402,F401,F403  (its tests and its fixtures)

FAULTS = [case for case, (_, fault, _) in cases.CHECKS.items() if fault]


def test_tier_1_runs_the_five_planted_faults():
    assert len(FAULTS) == 5 and len(cases.CHECKS) == 7
    assert test_the_check_judges_stacks_and_each_fault_fails_it is (  # noqa: F405
        cases.test_the_check_judges_stacks_and_each_fault_fails_it)
