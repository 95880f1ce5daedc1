"""The benchmark's own tests of `olmo-hybrid-7b` (benchmark/tests/
test_olmo_hybrid.py: the configuration file's promises, the flat copies held
equal to what the reference reads, the byte counts against the parameter trees
and the cache's shapes and the issue's arithmetic, the traffic's mix, the check
at the rehearsal's widths with three planted faults, and the four readers the
cell brings), run by tier-1 as `tests/test_bench_mellum2.py` runs that
configuration's. The program's side of the same model is
`tests/test_olmo_hybrid.py`."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (os.path.join(BENCH, "tests"), BENCH):  # the case file; harness
    if path not in sys.path:
        sys.path.insert(0, path)

import importlib.util  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

# (Loaded by path: `tests/test_olmo_hybrid.py` has the same module name.)
_spec = importlib.util.spec_from_file_location(
    "bench_test_olmo_hybrid", os.path.join(BENCH, "tests", "test_olmo_hybrid.py"))
cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cases)
globals().update({name: value for name, value in vars(cases).items()
                  if name.startswith("test_") or name in ("cell", "traced")})


def test_tier_1_runs_the_benchmarks_cases_of_the_configuration():
    assert len(cases.NEW_READERS) == 4
    assert test_the_flat_copies_equal_what_the_reference_reads is (  # noqa: F821
        cases.test_the_flat_copies_equal_what_the_reference_reads)


# The benchmark's case of the byte counts names the cache's states a head a
# row, `(6, 64, 30, 96, 192)`, as they lay when it was written (PR 50); since
# PR 51 the cache holds them two heads side by side (`stacks.state_shape`:
# the same elements, whole 128-lane tiles). A PR that claims a gain edits no
# file of the benchmark's, so the case runs here over the cache with its
# states handed back a head a row, and the packed shape is held beside it; a
# `benchmark` PR's to reword (PERF.md section 7, PR 51).
_byte_counts = cases.test_the_byte_counts_equal_the_parameter_trees_and_the_issues_arithmetic


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_byte_counts_equal_the_parameter_trees_and_the_issues_arithmetic(  # noqa: F811
        cell, rehearse, monkeypatch):
    from omnia_tpu.models import llama, stacks
    from omnia_tpu.ops.delta import unpack_state

    mc = cell.model_config(rehearse)
    p = stacks.state_heads_a_row(mc)
    assert p == (1 if rehearse else 2)
    packed = jax.eval_shape(lambda: llama.init_kv_cache(mc, 64, 3072))[2]
    assert packed.shape[1:] == (64, *stacks.state_shape(mc))
    if not rehearse:
        assert packed.shape == (6, 64, 15, 96, 384)
    held = llama.init_kv_cache

    def a_head_a_row(*args, **kwargs):
        k, v, states, tails = held(*args, **kwargs)
        return k, v, unpack_state(states, p), tails

    monkeypatch.setattr(llama, "init_kv_cache", a_head_a_row)
    _byte_counts(cell, rehearse)
