"""The benchmark's own tests of the readers of warm-up's stages
(`benchmark/tests/test_setup_stages.py`), run by tier-1: imported as
`tests/test_bench_first_token.py` imports its cases."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (os.path.join(BENCH, "tests"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

from test_setup_stages import *  # noqa: E402,F401,F403
