"""The sampler's gates as the engine drives them (tiny model, CPU).

``ops/sampling.py::_gated_sample`` decides on the device, from the per-slot
temperature / top_p / top_k it is handed, whether a step's batch pays for
more than the argmax. Three things keep that honest in the engine: a slot
whose request has ended holds temperature 0 again (one stale positive value
would hold the gate open for good, and lose the gain without a sound); a
seeded request's tokens do not depend on the branch its batch-mates put the
batch on; and ``decode_steps_sampling`` / ``decode_steps_filtering`` count,
from the host's slot records, the steps in which the device took the larger
branches. The sampler's own arithmetic is pinned in ``tests/test_ops.py``.
"""

from __future__ import annotations

import numpy as np
import pytest

from omnia_tpu.engine import EngineConfig, FinishReason, InferenceEngine
from omnia_tpu.engine.types import SamplingParams
from omnia_tpu.models import get_config

PROMPT = [5, 6, 7, 8]
GREEDY = SamplingParams(temperature=0.0, max_tokens=12)
PLAIN = SamplingParams(temperature=0.8, max_tokens=12, seed=21)  # branch 2
FILTERED = SamplingParams(temperature=0.8, top_p=0.9, top_k=40, max_tokens=12,
                          seed=21)  # branch 3


def _engine(**over) -> InferenceEngine:
    base = dict(num_slots=3, max_seq=64, prefill_buckets=(8, 32),
                dtype="float32", max_sessions=0, decode_chunk=4,
                decode_pipeline=2)
    base.update(over)
    return InferenceEngine(get_config("test-tiny"), EngineConfig(**base), seed=3)


def _drain(eng) -> None:
    while eng.step():
        pass


def _temps(eng) -> list:
    return np.asarray(eng._temp).tolist()


def _step_until_live(eng, handle) -> int:
    """Step until ``handle``'s request holds a slot; the slot's index."""
    for _ in range(50):
        eng.step()
        for i, s in enumerate(eng._slots):
            if s.active and s.request.request_id == handle.request_id:
                return i
    raise AssertionError("the request was never placed")


# ---------------------------------------------------------------------------
# (a) every way a sampling request ends leaves its slot at temperature 0
# ---------------------------------------------------------------------------

def _ends_by_length(eng):
    h = eng.submit(PROMPT, FILTERED)
    slot = _step_until_live(eng, h)
    assert _temps(eng)[slot] == pytest.approx(0.8)
    _drain(eng)
    return h, FinishReason.LENGTH


def _ends_by_stop(eng):
    free_run, _ = eng.generate(PROMPT, FILTERED)
    stop = SamplingParams(temperature=0.8, top_p=0.9, top_k=40, max_tokens=12,
                          seed=21, stop_token_ids=(free_run[4],))
    h = eng.submit(PROMPT, stop)
    slot = _step_until_live(eng, h)
    assert _temps(eng)[slot] == pytest.approx(0.8)
    _drain(eng)
    return h, FinishReason.STOP


def _ends_cancelled(eng):
    h = eng.submit(PROMPT, SamplingParams(temperature=0.8, top_p=0.9,
                                          max_tokens=1000))
    slot = _step_until_live(eng, h)
    assert _temps(eng)[slot] == pytest.approx(0.8)
    h.cancel()
    _drain(eng)
    return h, FinishReason.CANCELLED


def _ends_at_its_deadline(eng):
    clock = [0.0]
    eng.clock = lambda: clock[0]
    h = eng.submit(PROMPT, SamplingParams(temperature=0.8, top_p=0.9,
                                          max_tokens=1000), deadline_s=5.0)
    slot = _step_until_live(eng, h)
    assert _temps(eng)[slot] == pytest.approx(0.8)
    clock[0] = 10.0
    _drain(eng)
    return h, FinishReason.DEADLINE


def _ends_in_recovery(eng):
    h = eng.submit(PROMPT, SamplingParams(temperature=0.8, top_p=0.9,
                                          max_tokens=1000))
    _step_until_live(eng, h)
    eng._recover("injected")  # what the loop does after a raised step
    return h, FinishReason.ERROR


def _ends_with_the_drain_window(eng):
    """``stop(drain=True)`` whose window elapses mid-request: the slots are
    released by ``_fail_all`` alone (lifecycle.py), with no ``_finish_slot``
    and no reallocation of the device state behind it."""
    h = eng.submit(PROMPT, SamplingParams(temperature=0.8, top_p=0.9,
                                          max_tokens=1000))
    slot = _step_until_live(eng, h)
    assert _temps(eng)[slot] == pytest.approx(0.8)
    eng.stop(drain=True, drain_timeout_s=0.0)
    return h, FinishReason.ERROR


def _ends_half_prefilled(eng):
    """The token-budget scheduler's abort (interleave.py): a sampling request
    cancelled between two pieces of its prompt never reached the sampler."""
    busy = eng.submit([1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=40))
    for _ in range(3):
        eng.step()
    h = eng.submit(list(range(10, 40)), SamplingParams(
        temperature=0.8, top_p=0.9, max_tokens=4))
    eng.step()
    assert eng._prefilling is not None
    assert _temps(eng)[eng._prefilling.slot_idx] == 0.0
    h.cancel()
    eng.step()
    assert eng._prefilling is None
    busy.cancel()
    _drain(eng)
    return h, FinishReason.CANCELLED


_TERMINALS = {
    "length": (_ends_by_length, {}),
    "stop": (_ends_by_stop, {}),
    "cancelled": (_ends_cancelled, {}),
    "deadline": (_ends_at_its_deadline, {}),
    "recovery": (_ends_in_recovery, {}),
    "drain_window_elapsed": (_ends_with_the_drain_window, {}),
    "cancelled_half_prefilled": (
        _ends_half_prefilled, dict(prefill_chunk_tokens=4, max_sessions=4)),
}


@pytest.mark.parametrize("terminal", list(_TERMINALS))
def test_an_ended_sampling_request_leaves_temperature_zero(terminal):
    run, over = _TERMINALS[terminal]
    eng = _engine(**over)
    handle, reason = run(eng)
    _toks, fin = handle.collect_tokens(timeout=60)
    assert fin.finish_reason is reason
    assert not any(s.active for s in eng._slots)
    assert _temps(eng) == [0.0] * eng.cfg.num_slots
    # ... so greedy traffic behind it takes the argmax alone again.
    if terminal != "drain_window_elapsed":  # that engine is stopped for good
        before = dict(eng.metrics)
        eng.generate(PROMPT, GREEDY)
        assert eng.metrics["decode_steps"] > before["decode_steps"]
        assert eng.metrics["decode_steps_sampling"] == before["decode_steps_sampling"]


# ---------------------------------------------------------------------------
# (b) a seeded request's tokens do not depend on its batch-mates' branch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def shared_engine():
    return _engine()


_MATES = {
    "greedy": [GREEDY, SamplingParams(temperature=0.0, top_p=0.5, max_tokens=5)],
    "plain_sampling": [SamplingParams(temperature=1.1, max_tokens=9, seed=5)],
    "filtering": [SamplingParams(temperature=0.6, top_k=7, max_tokens=9, seed=6),
                  GREEDY],
}


@pytest.mark.parametrize("mates", list(_MATES))
@pytest.mark.parametrize("params", [PLAIN, FILTERED, GREEDY],
                         ids=["plain", "filtered", "greedy"])
def test_seeded_tokens_are_the_same_alone_and_beside(shared_engine, params, mates):
    """Alone, a plain sampling request puts its batch on branch 2 and a
    greedy one on branch 1; beside a filtering mate both ride branch 3.
    The tokens are the request's own either way. Mates arrive first, so
    the request under test also meets steps they have already left."""
    eng = shared_engine
    alone, _ = eng.generate(PROMPT, params)
    assert len(alone) == params.max_tokens
    others = [eng.submit([9, 8, 7 + i], sp) for i, sp in enumerate(_MATES[mates])]
    eng.step()
    h = eng.submit(PROMPT, params)
    _drain(eng)
    together, fin = h.collect_tokens(timeout=60)
    assert fin.finish_reason is FinishReason.LENGTH
    assert together == alone
    for o in others:
        assert o.collect_tokens(timeout=60)[1].finish_reason is FinishReason.LENGTH
    assert _temps(eng) == [0.0] * eng.cfg.num_slots


# ---------------------------------------------------------------------------
# (c) the counters say which branch the device took, step for step
# ---------------------------------------------------------------------------

def _device_gates(eng):
    """Record, at every decode program call, the steps asked and what the
    sampler's predicates read on the DEVICE operands of that call."""
    seen = []
    run = eng._run_decode_step

    def spy(chunk=None):
        t, p, k = (np.asarray(x) for x in (eng._temp, eng._top_p, eng._top_k))
        steps = eng.cfg.decode_chunk if chunk is None else chunk
        sampling = t > 0
        seen.append((steps, bool(sampling.any()),
                     bool((sampling & ((p < 1) | (k > 0))).any())))
        return run(chunk=chunk)

    eng._run_decode_step = spy
    return seen


def _counted(eng, run):
    keys = ("decode_steps", "decode_steps_sampling", "decode_steps_filtering")
    before = {k: eng.metrics[k] for k in keys}
    run()
    return tuple(eng.metrics[k] - before[k] for k in keys)


_SCRIPTS = {
    # name: requests as (params, steps of the long greedy one before it arrives)
    "greedy_only": [(GREEDY, 0), (SamplingParams(temperature=0.0, top_p=0.5,
                                                 top_k=3, max_tokens=6), 1)],
    "plain_sampling_joins": [(PLAIN, 2)],
    "filtering_joins": [(FILTERED, 2)],
    "plain_then_filtering": [
        (SamplingParams(temperature=0.8, max_tokens=5, seed=1), 1),
        (SamplingParams(temperature=0.8, top_k=5, max_tokens=5, seed=2), 3)],
}


@pytest.mark.parametrize("script", list(_SCRIPTS))
def test_counters_read_the_gates_the_device_took(script):
    eng = _engine()
    seen = _device_gates(eng)

    def run():
        long_greedy = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                           max_tokens=40))
        handles, stepped = [long_greedy], 0
        for params, after in _SCRIPTS[script]:
            while stepped < after:
                eng.step()
                stepped += 1
            handles.append(eng.submit(PROMPT, params))
        _drain(eng)
        for h in handles:
            assert h.collect_tokens(timeout=60)[1].finish_reason is FinishReason.LENGTH

    steps, sampling, filtering = _counted(eng, run)
    assert steps == sum(n for n, _s, _f in seen) >= 39
    assert sampling == sum(n for n, s, _f in seen if s)
    assert filtering == sum(n for n, _s, f in seen if f)
    assert filtering <= sampling < steps  # the long greedy request outlives them
    if script == "greedy_only":
        assert (sampling, filtering) == (0, 0)
    elif script == "plain_sampling_joins":
        assert sampling >= PLAIN.max_tokens - 1 and filtering == 0
    elif script == "filtering_joins":
        assert sampling == filtering >= FILTERED.max_tokens - 1
    else:
        assert 0 < filtering < sampling
