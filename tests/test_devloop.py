"""Decode-pipeline suite: the watchdog's chunk drainer, the schedule's
one rule as a table, the chunk-size arithmetic, and the pipeline-depth
equivalence battery (``decode_pipeline`` 2 against 1).

Module top is jax-free by design: the drainer/state units run under the
CI analysis job's poisoned jax stub (``pytest -m devloop
--noconftest``); the scheduler tables and the engine-backed battery
importorskip jax.
"""

from __future__ import annotations

import collections
import time
from types import SimpleNamespace

import pytest

try:  # the CI analysis job runs the jax-free subset on a bare venv
    import numpy as np
except ImportError:  # pragma: no cover - CI analysis job only
    np = None

from omnia_tpu.engine.devloop import ChunkDrainer, DevLoopState, _InflightChunk
from omnia_tpu.engine.mock import MockEngine
from omnia_tpu.engine.types import FinishReason, SamplingParams

pytestmark = pytest.mark.devloop


# ---------------------------------------------------------------------------
# ChunkDrainer (jax-free)
# ---------------------------------------------------------------------------


class _Boom:
    """An array-like whose readback dies (a donated buffer freed by
    recovery while the drainer was still reading)."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("buffer deleted")


class TestChunkDrainer:
    @pytest.fixture(autouse=True)
    def _needs_numpy(self):
        # The drain IS the numpy readback; on the bare CI venv these
        # skip while the state units still run.
        pytest.importorskip("numpy")

    def test_drain_returns_host_array_fifo(self):
        d = ChunkDrainer()
        try:
            entries = [d.submit([i, i + 1]) for i in range(3)]
            outs = [d.wait(e, timeout=5) for e in entries]
            for i, out in enumerate(outs):
                assert isinstance(out, np.ndarray)
                assert out.tolist() == [i, i + 1]
            assert not d.poisoned
        finally:
            d.stop()
        assert not d._thread.is_alive()

    def test_readback_exception_parked_and_reraised(self):
        d = ChunkDrainer()
        try:
            bad = d.submit(_Boom())
            with pytest.raises(RuntimeError, match="buffer deleted"):
                d.wait(bad, timeout=5)
            # The drainer itself survives a dead buffer: next entry drains.
            good = d.wait(d.submit([7]), timeout=5)
            assert good.tolist() == [7]
        finally:
            d.stop()

    def test_timeout_poisons(self):
        d = ChunkDrainer()
        entry = d.submit([1], pre_sleep_s=0.5)
        assert d.wait(entry, timeout=0.01) is None
        assert d.poisoned
        # stop() must not block on the wedged thread.
        t0 = time.monotonic()
        d.stop()
        assert time.monotonic() - t0 < 0.4

    def test_waits_may_come_in_any_order(self):
        """The watchdog waits for the chunk it is processing, whatever
        else was handed over since: entries are boxes, not a stream."""
        d = ChunkDrainer()
        try:
            first, second = d.submit([1]), d.submit([2, 3])
            assert d.wait(second, timeout=5).tolist() == [2, 3]
            assert d.wait(first, timeout=5).tolist() == [1]
        finally:
            d.stop()

    def test_stop_joins_an_idle_thread_and_is_final(self):
        d = ChunkDrainer(name="omnia-chunk-drainer-test")
        assert d._thread.name == "omnia-chunk-drainer-test" and d._thread.daemon
        d.stop()
        assert not d._thread.is_alive()
        # Nothing reads an entry handed over after the stop.
        late = d.submit([1])
        assert d.wait(late, timeout=0.05) is None

    def test_fault_pre_sleep_is_timed(self):
        """Injected hang rides the drain wall (watchdog/chaos parity)."""
        d = ChunkDrainer()
        try:
            t0 = time.monotonic()
            d.wait(d.submit([1], pre_sleep_s=0.05), timeout=5)
            assert time.monotonic() - t0 >= 0.05
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# DevLoopState + _InflightChunk (jax-free)
# ---------------------------------------------------------------------------


class TestDevLoopState:
    def test_ring_off_builds_nothing(self):
        st = DevLoopState()
        assert st._drainer is None  # no thread until a chunk is read
        st.stop()  # no drainer ever built — a no-op
        assert st._drainer is None

    def test_drainer_lazy_and_poison_replacement(self):
        st = DevLoopState()
        d1 = st.get_drainer()
        assert st.get_drainer() is d1
        d1.poisoned = True
        d2 = st.get_drainer()  # recovery lane: fresh thread
        assert d2 is not d1 and not d2.poisoned
        st.stop()
        assert st._drainer is None

    def test_inflight_chunk_fields(self):
        ch = _InflightChunk("toks", [(0, "r0")], 0.25)
        assert ch.toks == "toks" and ch.dispatch_s == 0.25
        assert ch.active == [(0, "r0")]
        assert not hasattr(ch, "__dict__")  # __slots__: pipeline entry
        assert _InflightChunk.__slots__ == (
            "toks", "active", "dispatch_s", "placement", "seq")
        # No profiler session named its dispatch: its read-back spans
        # carry no ``seq``; one that did hands it on.
        assert ch.seq is None and ch.seq_attr == {}
        assert _InflightChunk("t", [], 0.0, seq=7).seq_attr == {"seq": 7}
        # A decode chunk holds its buffer's rows as steps; a placement's
        # first token (any placement note) stands for one.
        assert ch.placement is None
        first = _InflightChunk("tok", [(0, "r0")], 0.0, {"slot": 0})
        assert first.steps == 1 and first.placement == {"slot": 0}


# ---------------------------------------------------------------------------
# The schedule's one rule, engine-free: _SchedulerMixin._schedule on a stub
# that records what it was asked to do (needs jax only to import the mixin)
# ---------------------------------------------------------------------------


def _stub_scheduler(waiting, inflight, useful, active=True, pipeline=2,
                    first_unread=False):
    """``waiting`` is the queue as a list of booleans (has this request a
    slot to go to); ``inflight`` the chunks already dispatched;
    ``first_unread`` whether one of them is a placement's first token."""
    pytest.importorskip("jax")
    from omnia_tpu.engine.scheduler import _SchedulerMixin

    class Stub(_SchedulerMixin):
        def __init__(self):
            self.cfg = SimpleNamespace(decode_pipeline=pipeline)
            self._waiting = list(waiting)
            self._inflight = collections.deque(f"c{i}" for i in range(inflight))
            self._slots = [SimpleNamespace(active=active)]
            self._flight = None  # no recorder: the pass reads no clock
            self.calls = []

        def _mixed_enabled(self):
            return False

        def _spec_step(self):
            return False

        def _first_token_unread(self):
            return first_unread

        def _queued_placeable(self):
            return bool(self._waiting), any(self._waiting)

        def _dispatch_ahead_useful(self):
            return useful

        def _flush_for_waiting(self):
            self.calls.append(("flush", len(self._inflight)))
            self._inflight.clear()

        def _claim_pending(self):
            self.calls.append(("claim",))
            if True not in self._waiting:
                return None, None
            self._waiting.remove(True)
            return ("request", "handle"), 0

        def _place_pending(self, slot_idx, request, handle):
            self.calls.append(("place", slot_idx))

        def _dispatch_decode(self, single=False, blocked=False):
            self.calls.append((
                "dispatch", "single" if single else "chunk",
                "blocked" if blocked else "free", len(self._inflight),
            ))
            self._inflight.append("new")

        def _process_oldest_chunk(self):
            self.calls.append(("process", self._inflight.popleft()))

    return Stub()


_NOBODY, _PLACEABLE, _BLOCKED = (), (True, True), (False,)
# A full chunk on the futures of what is in flight, read `decode_pipeline`
# dispatches later; one step read at once; one step ahead of the one read.
_CHUNK = ("dispatch", "chunk", "free")
_STEP_SYNC = ("dispatch", "single", "free")
_STEP_AHEAD = ("dispatch", "single", "blocked")
_PLACED = [("claim",), ("place", 0)]


@pytest.mark.parametrize("waiting,inflight,useful,calls,left", [
    # Nobody waits: full chunks, `decode_pipeline` deep; a chunk nobody's
    # budget can still need is not dispatched, the oldest is read instead.
    (_NOBODY, 0, True, [_CHUNK + (0,)], 1),
    (_NOBODY, 0, False, [_CHUNK + (0,)], 1),
    (_NOBODY, 1, True, [_CHUNK + (1,), ("process", "c0")], 1),
    (_NOBODY, 1, False, [("process", "c0")], 0),
    (_NOBODY, 2, True, [_CHUNK + (2,), ("process", "c0"), ("process", "c1")], 1),
    (_NOBODY, 2, False, [("process", "c0")], 1),
    # A waiting request has a slot to go to: flush whatever flies, claim,
    # place, and (another still waits for its slot's placement next step)
    # one step, read back before anything else is enqueued.
    (_PLACEABLE, 0, True, _PLACED + [_STEP_SYNC + (0,), ("process", "new")], 0),
    (_PLACEABLE, 0, False, _PLACED + [_STEP_SYNC + (0,), ("process", "new")], 0),
    (_PLACEABLE, 1, True,
     [("flush", 1)] + _PLACED + [_STEP_SYNC + (0,), ("process", "new")], 0),
    (_PLACEABLE, 1, False,
     [("flush", 1)] + _PLACED + [_STEP_SYNC + (0,), ("process", "new")], 0),
    (_PLACEABLE, 2, True,
     [("flush", 2)] + _PLACED + [_STEP_SYNC + (0,), ("process", "new")], 0),
    (_PLACEABLE, 2, False,
     [("flush", 2)] + _PLACED + [_STEP_SYNC + (0,), ("process", "new")], 0),
    # Requests wait and none has a slot: nothing is flushed or claimed; one
    # step goes out ahead of the one in flight and the OLDEST is read.
    (_BLOCKED, 0, True, [_STEP_AHEAD + (0,)], 1),
    (_BLOCKED, 0, False, [_STEP_AHEAD + (0,)], 1),
    (_BLOCKED, 1, True, [_STEP_AHEAD + (1,), ("process", "c0")], 1),
    (_BLOCKED, 1, False, [("process", "c0")], 0),
    (_BLOCKED, 2, True,
     [_STEP_AHEAD + (2,), ("process", "c0"), ("process", "c1")], 1),
    (_BLOCKED, 2, False, [("process", "c0")], 1),
])
def test_schedule_rule_table(waiting, inflight, useful, calls, left):
    stub = _stub_scheduler(waiting, inflight, useful)
    assert stub._schedule() is True
    assert stub.calls == calls
    assert len(stub._inflight) == left


@pytest.mark.parametrize("inflight,calls,left", [
    (1, [_STEP_SYNC + (1,), ("process", "c0")], 1),
    (2, [_STEP_SYNC + (2,), ("process", "c0"), ("process", "c1")], 1),
])
def test_behind_an_unread_first_token_nobody_waiting_still_gets_one_step(
        inflight, calls, left):
    """The prefill may still be running, and who arrives during it is not
    known yet: no full chunk is committed behind it. The pipeline keeps
    its depth (the step is not read at once)."""
    stub = _stub_scheduler(_NOBODY, inflight, True, first_unread=True)
    assert stub._schedule() is True
    assert stub.calls == calls
    assert len(stub._inflight) == left


@pytest.mark.parametrize("waiting,inflight,calls,did", [
    # No slot is live: what flies is read, one chunk a step; nothing to
    # read and nobody to place is an idle poll.
    (_NOBODY, 1, [("process", "c0")], True),
    (_NOBODY, 0, [], False),
    # The last placeable request of the queue: after its placement nobody
    # waits, so decode goes back to full chunks in the same step.
    ((True,), 1, [("flush", 1)] + _PLACED, True),
])
def test_schedule_rule_with_no_live_slot(waiting, inflight, calls, did):
    stub = _stub_scheduler(waiting, inflight, useful=True, active=False)
    assert stub._schedule() is did
    assert stub.calls == calls


def test_schedule_rule_depth_one_reads_every_dispatch_back():
    """decode_pipeline=1: blocked or not, a dispatch is read at once."""
    for waiting in (_NOBODY, _BLOCKED):
        stub = _stub_scheduler(waiting, 0, useful=True, pipeline=1)
        stub._schedule()
        assert [c[0] for c in stub.calls] == ["dispatch", "process"]
        assert not stub._inflight


def _stub_budget(slots, inflight=(), max_seq=64, variants=(1, 4, 8)):
    """``slots``: (max_total, generated, length) or None for an idle slot;
    ``inflight``: (steps, slot indices) of each chunk already dispatched."""
    pytest.importorskip("jax")
    from omnia_tpu.engine.scheduler import _SchedulerMixin

    stub = _SchedulerMixin()
    stub.cfg = SimpleNamespace(max_seq=max_seq)
    stub._decode_fns = dict.fromkeys(variants)
    stub._slots = [
        SimpleNamespace(active=False) if s is None else SimpleNamespace(
            active=True, max_total=s[0], generated=s[1], length=s[2])
        for s in slots
    ]
    stub._inflight = collections.deque(
        _InflightChunk(SimpleNamespace(shape=(k, len(slots))),
                       [(i, f"r{i}") for i in idx], 0.0)
        for k, idx in inflight
    )
    return stub


@pytest.mark.parametrize("slots,inflight,remaining,chunk", [
    # The budget ends inside the chunk in flight: nothing more to dispatch.
    ([(12, 7, 10)], [(8, [0])], 0, 1),
    # The cache's end comes before the budget's: max_seq - 2 - length.
    ([(100, 1, 59)], [], 3, 4),
    # One slot live among idle ones, far from its end: the full chunk.
    ([None, (40, 20, 23), None], [], 20, 8),
    # None live.
    ([None, None], [], 0, 1),
    # Two live; what flies covers one of them and not the other.
    ([(9, 1, 4), (30, 1, 4)], [(8, [0, 1])], 21, 8),
    # Chunks in flight add up per slot: 8 + 1 of 10 left one step.
    ([(11, 1, 4)], [(8, [0]), (1, [0])], 1, 1),
])
def test_chunk_size_follows_the_work_left(slots, inflight, remaining, chunk):
    stub = _stub_budget(slots, inflight)
    assert stub._remaining_work() == remaining
    assert stub._dispatch_ahead_useful() is (remaining > 0)
    assert stub._pick_chunk() == chunk


# ---------------------------------------------------------------------------
# Engine-backed battery: decode_pipeline 2 against 1 (skips without jax)
# ---------------------------------------------------------------------------


def _engine(**kw):
    pytest.importorskip("jax")
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig
    from omnia_tpu.models import get_config

    seed = kw.pop("seed", 0)
    base = dict(num_slots=2, max_seq=64, prefill_buckets=(8,),
                dtype="float32", max_sessions=0)
    base.update(kw)
    return InferenceEngine(get_config("test-tiny"), EngineConfig(**base),
                           seed=seed)


GREEDY = SamplingParams(temperature=0.0, max_tokens=12)


def _drive(eng, *handles, timeout=60):
    deadline = time.monotonic() + timeout
    out = []
    while eng.step():
        assert time.monotonic() < deadline
    for h in handles:
        out.append(h.collect_tokens(timeout=timeout))
    return out


@pytest.mark.parametrize("extra", [
    pytest.param({"kv_quant": "int8"}, id="int8-kv"),
    pytest.param({"kv_pages": 9, "kv_page_tokens": 8}, id="paged"),
    pytest.param({"spec_decode": 2}, id="spec"),
    pytest.param({"prefill_chunk_tokens": 4}, id="interleave"),
])
def test_pipeline_depth_equivalence_with_cotenant(extra):
    """Two chunks in flight against one under each major engine feature,
    with TWO live requests so chunks carry multi-slot snapshots (verify
    steps and mixed interleave steps ride the same pipeline)."""
    pa, pb = [1, 2, 3], [9, 8, 7, 6]
    streams = []
    for pipeline in (1, 2):
        eng = _engine(decode_pipeline=pipeline, **extra)
        ha = eng.submit(pa, GREEDY)
        hb = eng.submit(pb, GREEDY)
        streams.append([t for t, _ in _drive(eng, ha, hb)])
        eng.stop()
    assert streams[0] == streams[1]
    assert all(len(t) == GREEDY.max_tokens for t in streams[0])


def test_pipeline_depth_equivalence_grammar_beside_free_slot():
    """A grammar-constrained slot and an unconstrained one share every
    chunk: identical streams at either depth, the constrained one
    admissible token by token, the free one what it is without a
    grammar engine's mask."""
    pytest.importorskip("jax")
    from omnia_tpu.engine.grammar import compile_json_schema
    from omnia_tpu.engine.tokenizer import ByteTokenizer
    from omnia_tpu.models import get_config

    schema = {"type": "object",
              "properties": {"a": {"type": "integer"}},
              "required": ["a"]}
    g = compile_json_schema(schema, ByteTokenizer())
    sp = SamplingParams(temperature=0.0, max_tokens=40, stop_token_ids=(0,))
    streams = []
    for pipeline in (1, 2):
        eng = _engine(decode_pipeline=pipeline, num_slots=4, max_seq=128,
                      prefill_buckets=(8, 16, 32), grammar=True,
                      grammar_max_states=512)
        hg = eng.submit(list(b"make json"), sp, grammar=g)
        hf = eng.submit([5, 6, 7], GREEDY)
        streams.append([t for t, _ in _drive(eng, hg, hf)])
        eng.stop()
    assert streams[0] == streams[1]
    constrained, free = streams[0]
    v = g.view(get_config("test-tiny").vocab_size, (0,))
    s = v.start
    for t in constrained:
        assert v.allowed(s)[t]
        s = v.advance(s, t)
    assert len(free) == GREEDY.max_tokens


@pytest.mark.parametrize("pipeline", [1, 2])
def test_deadline_inside_a_chunk_ends_at_the_next_boundary(pipeline):
    """A wall deadline that passes while the device is inside a chunk is
    found at the next step boundary (lifecycle._reap_deadlines): DEADLINE
    with streamed == num_generated_tokens, and no token of a chunk still
    in flight leaks past the terminal."""
    eng = _engine(decode_pipeline=pipeline)
    h = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=48),
                   deadline_s=600.0)
    for _ in range(3):
        eng.step()
    assert bool(eng._inflight) == (pipeline == 2)
    (slot,) = [s for s in eng._slots if s.active]
    streamed_before = slot.generated
    slot.request.deadline_at = eng.clock() - 1.0
    ((toks, fin),) = _drive(eng, h)
    assert fin.finish_reason is FinishReason.DEADLINE
    assert len(toks) == fin.num_generated_tokens == streamed_before
    assert eng.metrics["deadline_exceeded"] == 1
    assert not eng._inflight


def test_cancel_with_chunks_in_flight_exact_partial_counts():
    """A cancel landing while chunks are in flight: the terminal carries
    exactly the streamed token count (no token from a chunk read after
    the cancel leaks past the terminal)."""
    eng = _engine(decode_pipeline=2)
    h = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=48))
    for _ in range(3):
        eng.step()
    assert eng._inflight
    h.cancel()
    while eng.step():
        pass
    toks, fin = h.collect_tokens(timeout=30)
    assert fin.finish_reason is FinishReason.CANCELLED
    assert len(toks) == fin.num_generated_tokens


def test_watchdog_trip_poisons_drainer_and_recovers():
    """An injected hang on the drainer thread trips the watchdog at
    the bound, poisons the drainer, and recovery rebuilds device state
    plus a FRESH drainer lane — the engine serves again."""
    from omnia_tpu.engine.faults import FaultPlan, WatchdogTimeout

    plan = FaultPlan(hang_dispatch_s=30.0, hang_count=1)
    eng = _engine(decode_pipeline=2, watchdog_s=0.2)
    eng._fault_plan = plan
    h = eng.submit([1, 2, 3], GREEDY)
    with pytest.raises(WatchdogTimeout):
        while eng.step():
            pass
    assert eng.metrics["watchdog_trips"] == 1
    poisoned = eng._devloop._drainer
    assert poisoned is not None and poisoned.poisoned
    eng._recover("watchdog tripped")
    assert eng.healthy() and eng.metrics["recoveries"] == 1
    _toks, fin = h.collect_tokens(timeout=30)
    assert fin.finish_reason is FinishReason.ERROR
    # Post-recovery service on a fresh drainer lane.
    toks2, fin2 = eng.generate([4, 5, 6], GREEDY)
    assert toks2 and fin2.finish_reason is not None
    assert eng._devloop._drainer is not poisoned
    eng.stop()


def test_drain_stop_with_inflight_chunks():
    """stop(drain=True) with chunks in flight under a watchdog: every
    chunk's tokens are surfaced (the stream terminal arrives), and the
    drainer thread is joined."""
    eng = _engine(decode_pipeline=2, watchdog_s=30.0)
    h = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=48))
    for _ in range(4):
        eng.step()
    assert eng._inflight  # chunks genuinely in flight at the stop
    assert eng._devloop._drainer is not None
    eng.stop(drain=True)
    assert eng._devloop._drainer is None  # stop() joined and cleared it
    toks, fin = h.collect_tokens(timeout=5)
    assert fin.finish_reason is not None
    assert len(toks) == fin.num_generated_tokens


# ---------------------------------------------------------------------------
# What went with the token ring stays gone
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("owner", ["EngineConfig", "MockEngine"])
def test_decode_ring_is_not_an_option(owner):
    from omnia_tpu.engine.types import EngineConfig

    with pytest.raises(TypeError, match="decode_ring"):
        {"EngineConfig": EngineConfig, "MockEngine": MockEngine}[owner](decode_ring=2)


def test_arenajob_threshold_has_no_devloop_ratio():
    from omnia_tpu.evals.defs import ArenaJobSpec, Threshold
    from omnia_tpu.operator.crds import render_crd

    assert not hasattr(Threshold(), "min_devloop_ratio")
    spec = ArenaJobSpec.from_dict({
        "name": "perf", "providers": ["p"],
        "threshold": {"min_devloop_ratio": 0.97, "min_pass_rate": 0.5},
    })
    assert spec.threshold == Threshold(min_pass_rate=0.5)
    assert "min_devloop_ratio" not in str(render_crd("ArenaJob"))
