"""The benchmark's own tests of PR 37's readers, run by tier-1: the cases of
`benchmark/tests/test_causal.py` (every read-back joined to the device program
it waited for: hand-made traces, six that must not join, a trace recorded on
the chip) and of `benchmark/tests/test_first_token.py` (the first
token's stages for the band of requests around the percentile a cell judges: on
hand-made records, on the parent's breakdowns, on a `ctx["flight"]` recorded on
the chip), imported as `tests/test_bench_harness_stacks.py` imports its cases."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (os.path.join(BENCH, "tests"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import test_causal as causal_cases  # noqa: E402
import test_first_token as first_token_cases  # noqa: E402
from test_causal import *  # noqa: E402,F401,F403  (its tests and its fixture)
from test_first_token import *  # noqa: E402,F401,F403


def test_tier_1_runs_the_traces_that_must_not_join_and_both_recorded_samples():
    assert test_what_does_not_line_up_is_no_join is (  # noqa: F405
        causal_cases.test_what_does_not_line_up_is_no_join)
    assert test_the_recorded_trace_joins is causal_cases.test_the_recorded_trace_joins  # noqa: F405
    assert test_every_recorded_request_tiles is (  # noqa: F405
        first_token_cases.test_every_recorded_request_tiles)
    assert len(causal_cases.READERS) + 2 * len(first_token_cases.STAGES) == 17
