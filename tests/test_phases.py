"""Engine-loop phase spans on the profiler's clock (engine/phases.py), the
named scopes inside the step programs, and the counters where the
scheduler decides.

The span tests run a tiny engine under ``jax.profiler`` on the CPU and read
the ``.xplane.pb`` back the way ``benchmark/harness/spans.py`` does; the
off-path test shows what a process nobody profiles pays; the lowering test
shows that the scopes are metadata and nothing else.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import time
import tracemalloc

import jax
import pytest

from omnia_tpu.engine import EngineConfig, InferenceEngine, phases
from omnia_tpu.engine.types import SamplingParams
from omnia_tpu.models import get_config

GREEDY = SamplingParams(temperature=0.0, max_tokens=8)


def _tiny_engine(**over) -> InferenceEngine:
    base = dict(num_slots=2, max_seq=64, prefill_buckets=(8,), dtype="float32",
                max_sessions=0, decode_chunk=4, decode_pipeline=2)
    base.update(over)
    return InferenceEngine(get_config("test-tiny"), EngineConfig(**base), seed=3)


def _drain(eng: InferenceEngine) -> None:
    while eng.step():
        pass


@contextlib.contextmanager
def _profiled(tmp_path):
    """A profiler session with the host tracer on; yields a function that
    returns the ``omnia.*`` spans per thread line once the session ended."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    stopped = False

    def spans() -> dict:
        assert stopped, "read the spans after the with block"
        from jax.profiler import ProfileData

        path = sorted(glob.glob(
            os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
        out: dict = {}  # threads share a line name: keyed by position
        for plane in ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                       for e in line.events if e.name.startswith("omnia.")]
                if evs:
                    out[plane.name, i] = evs
        return out

    try:
        yield spans
    finally:
        jax.profiler.stop_trace()
        stopped = True


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_phase_spans_nest_under_step_and_carry_request_ids(tmp_path):
    eng = _tiny_engine(num_slots=1)
    want, _fin = eng.generate([1, 2, 3], GREEDY)  # compile outside the session
    # `first` ends on a stop id, which the host cannot foresee: the step
    # ahead is in flight when the end is read, and is flushed for `second`.
    stopping = SamplingParams(temperature=0.0, max_tokens=8,
                              stop_token_ids=(want[5],))
    with _profiled(tmp_path) as spans:
        t_lo = time.monotonic_ns()
        first = eng.submit([1, 2, 3], stopping)
        eng.step()  # places `first`, leaves one chunk in flight
        second = eng.submit([4, 5, 6, 7], GREEDY)  # waits for the one slot
        _drain(eng)
        t_hi = time.monotonic_ns()
    got_first, got_second = (h.collect_tokens(timeout=60)[0] for h in (first, second))
    assert got_first == want[:5] and len(got_second) == GREEDY.max_tokens
    (events,) = spans().values()  # one thread did everything

    steps = _named(events, phases.STEP)
    assert steps
    for name in (phases.HOUSEKEEPING, phases.FLUSH_PIPELINE, phases.CLAIM,
                 phases.PLACE, phases.PREFILL_DISPATCH, phases.DECODE_DISPATCH,
                 phases.CHUNK_SYNC, phases.EMIT):
        found = _named(events, name)
        assert found, name
        for _n, start, end, _attrs in found:
            assert any(s <= start and end <= e for _sn, s, e, _a in steps), name
    # The caller's submit is a span of its own, outside every step.
    submits = _named(events, phases.SUBMIT)
    assert [s[3]["request_id"] for s in submits] == [first.request_id, second.request_id]
    assert submits[1][3]["n_prompt"] == 4
    for _n, start, end, _a in submits:
        assert not any(s < end and start < e for _sn, s, e, _a2 in steps)

    # A prefill program call nests in its placement, and both name the
    # request the claim named.
    claimed = [c[3]["request_id"] for c in _named(events, phases.CLAIM) if c[3]]
    assert claimed == [first.request_id, second.request_id]
    places = _named(events, phases.PLACE)
    assert [p[3]["request_id"] for p in places] == claimed
    assert places[1][3]["n_prompt"] == 4 and places[1][3]["slot"] == 0
    for piece in _named(events, phases.PREFILL_DISPATCH):
        owner = [p for p in places if p[1] <= piece[1] and piece[2] <= p[2]]
        assert len(owner) == 1
        assert piece[3]["request_id"] == owner[0][3]["request_id"]
        assert piece[3]["bucket"] == 8

    # The step's mono_ns is the flight recorder's clock at its entry.
    assert all(t_lo <= s[3]["mono_ns"] <= t_hi for s in steps)
    assert {"queued", "inflight"} <= set(steps[0][3])
    # While `second` waited with no slot to go to, every dispatch asked for
    # the one-step program, ahead of the step being read.
    waiting = [d[3] for d in _named(events, phases.DECODE_DISPATCH)
               if places[0][2] <= d[1] and d[2] <= places[1][1]]
    # Behind the prefill, whose first token is still unread in the
    # pipeline: one step, though nobody waits yet.
    assert waiting[0]["chunk"] == 1 and waiting[0]["single"]
    assert not waiting[0]["blocked"] and waiting[0]["inflight"] == 1
    assert len(waiting) == 6
    assert all(d["chunk"] == 1 and d["single"] and d["active"] == 1
               and d["blocked"] and d["inflight"] == 1 for d in waiting[1:])
    flush = _named(events, phases.FLUSH_PIPELINE)
    assert len(flush) == 1 and flush[0][3]["chunks"] == 1
    emitted = sum(e[3]["tokens"] for e in _named(events, phases.EMIT))
    # The first token of each comes from its prefill, and is read and
    # emitted as an entry of the pipeline.
    assert emitted == len(got_first) + len(got_second)
    assert sum(e[3]["finished"] for e in _named(events, phases.EMIT)) == 2


def test_engine_thread_sleep_spans(tmp_path):
    eng = _tiny_engine()
    eng.generate([1, 2, 3], GREEDY)
    eng.start()
    try:
        with _profiled(tmp_path) as spans:
            handle = eng.submit([1, 2, 3], GREEDY)
            handle.collect_tokens(timeout=60)
            time.sleep(0.02)  # an idle engine sleeps between polls
    finally:
        eng.stop()
    by_thread = spans()
    loop = next(evs for evs in by_thread.values() if _named(evs, phases.STEP))
    sleeps = _named(loop, phases.IDLE_SLEEP)
    steps = _named(loop, phases.STEP)
    assert sleeps
    # An idle poll writes no step span: sleeps outnumber steps once idle,
    # and no sleep lies inside a step.
    assert not any(s <= sl[1] and sl[2] <= e for sl in sleeps for _n, s, e, _a in steps)


def test_counters_against_a_scripted_schedule():
    """One slot, chunk of 4, pipeline of 2. `a` is placed and decodes alone;
    `b` arrives while a chunk of `a` is in flight and has no slot to go to."""
    eng = _tiny_engine(num_slots=1)
    m = eng.metrics
    a = eng.submit([1, 2, 3], GREEDY)
    eng.step()
    # Placed; one step dispatched behind the prefill (its first token is
    # unread, so no chunk is committed yet), the first token read, the step
    # left in flight: the pipeline may run two deep.
    assert (m["decode_dispatches"], m["decode_dispatches_single"]) == (1, 1)
    assert (m["decode_steps"], m["decode_slot_steps"]) == (1, 1)
    assert m["pipeline_flushes"] == 0
    assert (len(eng._inflight), m["tokens_generated"]) == (1, 1)
    b = eng.submit([4, 5, 6], GREEDY)
    eng.step()
    # `b` waits and is blocked: nothing is flushed for it. One step is
    # dispatched ahead of the step in flight, which is then read, and the
    # new one stays in flight.
    assert m["pipeline_flushes"] == 0
    assert (m["decode_dispatches"], m["decode_dispatches_single"]) == (2, 2)
    assert m["decode_dispatches_blocked"] == 1
    assert (m["decode_steps"], m["decode_slot_steps"]) == (2, 2)
    assert (len(eng._inflight), m["tokens_generated"]) == (1, 2)
    _drain(eng)
    assert len(a.collect_tokens(timeout=60)[0]) == 8
    assert len(b.collect_tokens(timeout=60)[0]) == 8
    # a: 1 + 6 one-step calls, the six while b was blocked, each dispatched
    # with its predecessor in flight. a's end is foreseeable from its
    # budget, so no step is dispatched past it and nothing is in flight
    # when b becomes placeable: no flush at all. b decodes alone: one step
    # behind its prefill, then 4 + 2, where the tail picks the smallest
    # variant that covers it (the chunk of 4, two steps of it garbage).
    assert m["pipeline_flushes"] == 0
    assert (m["decode_dispatches_single"], m["decode_dispatches_blocked"]) == (8, 6)
    assert m["decode_dispatches"] == 7 + 3
    assert m["decode_steps"] == 7 + 9
    assert m["decode_slot_steps"] == m["decode_steps"]  # one slot, always live
    assert m["tokens_generated"] == 16


def test_an_unforeseen_end_is_found_a_step_late_and_flushed_once():
    """As above, but `a` ends on a stop id at its sixth token: the host
    learns of it from the read-back with the step ahead in flight. That
    step is flushed (it wrote nothing for `a`: the device deactivated the
    slot in the step that sampled the stop id) and `b` is placed."""
    eng = _tiny_engine(num_slots=1)
    m = eng.metrics
    want_a, _ = eng.generate([1, 2, 3], GREEDY)
    want_b, _ = eng.generate([4, 5, 6], GREEDY)
    base = dict(m)
    a = eng.submit([1, 2, 3], SamplingParams(
        temperature=0.0, max_tokens=8, stop_token_ids=(want_a[5],)))
    eng.step()  # placed, one step behind the prefill, first token read
    b = eng.submit([4, 5, 6], GREEDY)
    for _ in range(4):
        eng.step()  # single ahead; reads the step before it
    eng.step()  # single ahead; reads the stop id: a ends, one step in flight
    assert a.collect_tokens(timeout=60)[0] == want_a[:5]
    assert m["pipeline_flushes"] == base["pipeline_flushes"]
    assert len(eng._inflight) == 1 and not eng._slots[0].active
    eng.step()  # b is placeable: flush the step ahead, place, decode
    assert m["pipeline_flushes"] - base["pipeline_flushes"] == 1
    assert eng._slots[0].request.request_id == b.request_id
    _drain(eng)
    got_b, fin_b = b.collect_tokens(timeout=60)
    assert got_b == want_b and fin_b.num_generated_tokens == 8
    delta = {k: m[k] - base[k] for k in (
        "decode_dispatches", "decode_dispatches_single",
        "decode_dispatches_blocked", "decode_steps", "tokens_generated")}
    # a: one step behind its prefill + five blocked single steps (the last
    # one garbage); b: one step behind its prefill, then 4 + 4. Tokens: a 5,
    # b 8.
    assert delta == {
        "decode_dispatches": 6 + 3, "decode_dispatches_single": 6 + 1,
        "decode_dispatches_blocked": 5, "decode_steps": 6 + 9,
        "tokens_generated": 13,
    }


def test_a_placeable_waiting_request_keeps_the_synchronous_schedule():
    """Two slots, one free: `b` arrives while a chunk of `a` is in flight
    and can be placed at once: the chunk is flushed for it and it is
    placed in the same step; nothing is counted as blocked."""
    eng = _tiny_engine(num_slots=2)
    m = eng.metrics
    a = eng.submit([1, 2, 3], GREEDY)
    eng.step()
    assert (m["decode_dispatches"], len(eng._inflight)) == (1, 1)
    b = eng.submit([4, 5, 6], GREEDY)
    c = eng.submit([7, 8, 9], GREEDY)
    eng.step()
    # Flushed for b (the step behind a's prefill: one of a's tokens), b
    # placed; c still waits and has no slot: the next dispatch is one step
    # for both live slots, and since c is blocked it stays in flight while
    # b's first token is read.
    assert m["pipeline_flushes"] == 1 and m["prefill_steps"] == 2
    assert (m["decode_dispatches"], m["decode_dispatches_single"]) == (2, 2)
    assert m["decode_dispatches_blocked"] == 1
    assert (m["decode_steps"], m["decode_slot_steps"]) == (2, 1 + 2)
    assert (len(eng._inflight), m["tokens_generated"]) == (1, 1 + 1 + 1)
    _drain(eng)
    for h in (a, b, c):
        toks, fin = h.collect_tokens(timeout=60)
        assert len(toks) == 8 and fin.num_generated_tokens == 8
    assert m["tokens_generated"] == 24


def test_nobody_blocked_counts_nothing_blocked():
    """Two slots, two requests, one free slot each time: the waiting
    request is always placeable, so the schedule and its counters are the
    ones before the blocked regime existed."""
    eng = _tiny_engine(num_slots=2)
    m = eng.metrics
    a = eng.submit([1, 2, 3], GREEDY)
    eng.step()
    b = eng.submit([4, 5, 6], GREEDY)
    eng.step()
    # Flushed for b, b placed, nobody waits any more: one step behind b's
    # prefill (its first token is unread), full chunks from the next step on.
    assert m["pipeline_flushes"] == 1
    assert (m["decode_dispatches"], m["decode_dispatches_single"]) == (2, 2)
    assert (m["decode_steps"], m["decode_slot_steps"]) == (2, 1 + 2)
    _drain(eng)
    assert len(a.collect_tokens(timeout=60)[0]) == 8
    assert len(b.collect_tokens(timeout=60)[0]) == 8
    assert m["pipeline_flushes"] == 1
    # The two one-step calls are those behind the two prefills.
    assert (m["decode_dispatches_single"], m["decode_dispatches_blocked"]) == (2, 0)
    assert m["tokens_generated"] == 16


def test_live_slots_are_counted_where_the_batch_is_formed():
    eng = _tiny_engine(num_slots=2, decode_chunk=1, decode_pipeline=1)
    m = eng.metrics
    eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=3))
    eng.submit([4, 5, 6], SamplingParams(temperature=0.0, max_tokens=5))
    _drain(eng)
    # Step 1 places the first request and decodes it alone (1 live slot);
    # from then on both are live until the short one ends.
    assert m["decode_dispatches"] == m["decode_steps"] == m["decode_dispatches_single"]
    assert m["decode_steps"] < m["decode_slot_steps"] < 2 * m["decode_steps"]


@pytest.mark.parametrize("params,sampling,filtering", [
    (SamplingParams(temperature=0.0, top_p=0.5, max_tokens=6), False, False),
    (SamplingParams(temperature=0.7, max_tokens=6, seed=1), True, False),
    (SamplingParams(temperature=0.7, top_p=0.9, max_tokens=6, seed=1), True, True),
], ids=["greedy", "plain", "filtered"])
def test_decode_dispatch_span_says_what_the_sampler_pays_for(
        tmp_path, params, sampling, filtering):
    """``sampling`` / ``filtering`` on a ``.decode_dispatch`` span are the
    gates the sampler takes on the device for that dispatch, and summed
    over the spans' steps they are the two counters."""
    eng = _tiny_engine(num_slots=2)
    eng.generate([1, 2, 3], params)  # compile outside the session
    before = dict(eng.metrics)
    with _profiled(tmp_path) as spans:
        eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=20))
        eng.submit([4, 5, 6], params)
        _drain(eng)
    (events,) = spans().values()
    dispatches = [d[3] for d in _named(events, phases.DECODE_DISPATCH)]
    assert dispatches and all(d["filtering"] <= d["sampling"] for d in dispatches)
    assert any(d["sampling"] for d in dispatches) == sampling
    assert any(d["filtering"] for d in dispatches) == filtering
    # The greedy request outlives the six tokens of the other one.
    assert not dispatches[-1]["sampling"] and dispatches[-1]["active"] == 1
    for key, attr in (("decode_steps_sampling", "sampling"),
                      ("decode_steps_filtering", "filtering")):
        assert eng.metrics[key] - before[key] == sum(
            d["chunk"] for d in dispatches if d[attr])


def test_programs_compiled_after_warmup_are_counted(tmp_path, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    # The event is recorded only while the persistent cache is on, as it
    # is wherever the engine serves.
    prev_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    try:
        eng = _tiny_engine()
        assert eng.metrics["programs_compiled_serving"] == 0
        eng.warmup(sessions=False)
        assert eng.metrics["programs_compiled_serving"] == 0
        eng.generate([1, 2, 3], GREEDY)
        assert eng.metrics["programs_compiled_serving"] == 0  # all warmed
        # Placements and finishes with other slots live: the two programs
        # that write a slot's state meet vectors that came out of a decode
        # step, of each other and of a fresh allocation.
        handles = [eng.submit([i + 1, i + 2, i + 3], GREEDY) for i in range(5)]
        _drain(eng)
        assert all(len(h.collect_tokens(timeout=60)[0]) == 8 for h in handles)
        assert eng.metrics["programs_compiled_serving"] == 0
        import numpy as np

        # A program warm-up never saw (numpy in: nothing else to compile).
        jax.jit(lambda x: x * 3 + 1)(np.ones((7, 3), np.float32))
        assert eng.metrics["programs_compiled_serving"] == 1
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        cc.reset_cache()


def test_off_path_allocates_nothing_and_records_nothing():
    """No profiler session, flight_events=0: no recorder, and the helper
    hands out one shared falsy object without allocating."""
    eng = _tiny_engine()
    assert eng._flight is None
    assert not phases.enabled()
    eng.generate([1, 2, 3], GREEDY)
    assert phases.phase(phases.STEP) is phases.phase(phases.EMIT) is phases._OFF
    with phases.phase(phases.CLAIM) as sp:
        assert not sp
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(2000):
            with phases.phase(phases.STEP) as sp:
                if sp:
                    sp.set_metadata(mono_ns=time.monotonic_ns())
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    here = [tracemalloc.Filter(True, phases.__file__)]
    grown = sum(s.size_diff for s in
                after.filter_traces(here).compare_to(before.filter_traces(here), "filename"))
    assert grown == 0
    # An idle engine's step takes the same path as before: no span object.
    assert eng.step() is False


def test_off_path_computes_no_attribute_of_the_readback_join(monkeypatch):
    """No profiler session: no chunk carries a ``seq``, no
    ``.prefill_dispatch`` draws one, and the stage seconds are never
    turned into span attributes, recorder on or off."""
    from omnia_tpu.engine import scheduler

    made, turned = [], []

    class Chunk(scheduler._InflightChunk):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self.seq)

    monkeypatch.setattr(scheduler, "_InflightChunk", Chunk)
    monkeypatch.setattr(phases, "as_ms", lambda parts: turned.append(parts) or {})
    for flight_events in (0, 64):
        eng = _tiny_engine(flight_events=flight_events)
        handles = [eng.submit([i + 1, i + 2, i + 3], GREEDY) for i in range(3)]
        _drain(eng)
        assert all(len(h.collect_tokens(timeout=60)[0]) == 8 for h in handles)
        assert next(eng._prefill_seq) == 0
    assert made and set(made) == {None}
    assert turned == []


@pytest.mark.parametrize("flight_events", [0, 256], ids=["recorder-off", "recorder-on"])
def test_every_readback_names_the_dispatch_it_waited_for(tmp_path, flight_events):
    """Under a session a ``.decode_dispatch`` says which dispatch it is
    (``seq``), and the ``.chunk_sync`` and ``.emit`` that read that chunk
    back say the same; a ``.prefill_dispatch`` has a ``seq`` of its own
    series and says whether it is its placement's last piece, and the
    first token's ``.chunk_sync`` and ``.emit`` name the request. With a
    recorder on, the claim and the first token's emit carry
    ``LatencyBreakdown``'s stages, in ms, so the trace alone tells a
    placement's story; with none they carry no stage."""
    eng = _tiny_engine(num_slots=2, prefill_buckets=(8,), flight_events=flight_events)
    eng.generate([1, 2, 3], GREEDY)
    eng.generate(list(range(1, 21)), GREEDY)  # the extend programs too
    d0 = eng.metrics["decode_dispatches"]
    with _profiled(tmp_path) as spans:
        short = eng.submit([1, 2, 3], GREEDY)
        long_ = eng.submit(list(range(1, 21)), GREEDY)  # three pieces of 8
        late = eng.submit([7, 8, 9], GREEDY)            # waits for a slot
        _drain(eng)
    for h in (short, long_, late):
        assert len(h.collect_tokens(timeout=60)[0]) == 8
    (events,) = spans().values()

    dispatches = _named(events, phases.DECODE_DISPATCH)
    seqs = [d[3]["seq"] for d in dispatches]
    assert seqs == list(range(d0, d0 + len(seqs)))  # the counter, one by one
    syncs = [s for s in _named(events, phases.CHUNK_SYNC) if s[3]["chunk"] > 0]
    emits = [e for e in _named(events, phases.EMIT) if "seq" in e[3]]
    # Every chunk dispatched in the session was read in it, once, after
    # its dispatch, and emitted right after its read.
    assert sorted(s[3]["seq"] for s in syncs) == seqs
    assert [e[3]["seq"] for e in emits] == [s[3]["seq"] for s in syncs]
    start_of = {d[3]["seq"]: d[1] for d in dispatches}
    for sync, emit in zip(syncs, emits):
        assert start_of[sync[3]["seq"]] < sync[1] <= sync[2] <= emit[1]
        assert "request_id" not in sync[3]

    pieces = _named(events, phases.PREFILL_DISPATCH)
    assert [p[3]["seq"] for p in pieces] == list(range(len(pieces)))
    by_request = {}
    for p in pieces:
        by_request.setdefault(p[3]["request_id"], []).append(p[3]["last"])
    assert by_request == {
        short.request_id: [1], long_.request_id: [0, 0, 1], late.request_id: [1],
    }
    first_reads = [s for s in _named(events, phases.CHUNK_SYNC) if s[3]["chunk"] == 0]
    assert [s[3]["request_id"] for s in first_reads] == [
        short.request_id, long_.request_id, late.request_id]
    assert all("seq" not in s[3] for s in first_reads)
    first_emits = [e for e in _named(events, phases.EMIT) if "request_id" in e[3]]
    assert [e[3]["request_id"] for e in first_emits] == [
        s[3]["request_id"] for s in first_reads]
    last_piece = {p[3]["request_id"]: p for p in pieces if p[3]["last"]}
    for read in first_reads:
        assert last_piece[read[3]["request_id"]][2] <= read[1]

    claims = [c[3] for c in _named(events, phases.CLAIM) if c[3]]
    stage_keys = {"place_ms", "prefill_ms", "read_blocked_ms"}
    queue_keys = {"slot_wait_ms", "loop_wait_ms", "flush_ms"}
    if not flight_events:
        assert all(not queue_keys & set(c) for c in claims)
        assert all(not stage_keys & set(e[3]) for e in first_emits)
        return
    bds = {e.request_id: e.attrs["breakdown"] for e in eng._flight.events("terminal")}
    assert len(claims) == 3
    for c in claims:
        bd = bds[c["request_id"]]
        for key in queue_keys:
            assert c[key] == pytest.approx(bd[key[:-2] + "s"] * 1e3, abs=2e-3)
    assert claims[2]["slot_wait_ms"] > 0 and claims[0]["slot_wait_ms"] == 0
    for e in first_emits:
        bd = bds[e[3]["request_id"]]
        assert e[3]["place_ms"] == pytest.approx(bd["place_s"] * 1e3, abs=2e-3)
        assert e[3]["read_blocked_ms"] == pytest.approx(
            bd["read_blocked_s"] * 1e3, abs=2e-3)
        assert 0 <= bd["prefill_s"] * 1e3 - e[3]["prefill_ms"] < 5.0


def _decode_args(eng):
    return (eng.params, eng._ck, eng._cv, eng._tokens, eng._positions,
            eng._active, eng._budget, eng._stop_ids, eng._key_data,
            eng._temp, eng._top_p, eng._top_k)


def _prefill_args(eng):
    import jax.numpy as jnp

    toks = jnp.zeros((1, 8), jnp.int32)
    return (eng.params, eng._ck, eng._cv, toks, toks, jnp.int32(0), jnp.int32(2),
            eng._key_data[0], jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0))


def _extend_args(eng):
    import jax.numpy as jnp

    toks = jnp.zeros((1, 8), jnp.int32)
    return (eng.params, eng._ck, eng._cv, toks, toks, jnp.int32(0), jnp.int32(0),
            jnp.int32(2), eng._key_data[0], jnp.float32(0.0), jnp.float32(1.0),
            jnp.int32(0))


class _NoScope(contextlib.ContextDecorator):
    """`jax.named_scope` taken out: a context manager and a decorator that
    names nothing. (The sampler's own `sample` scope is applied when its
    module is imported and stays.)"""

    def __init__(self, _name: str) -> None:
        pass

    def __enter__(self) -> "_NoScope":
        return self

    def __exit__(self, *_exc) -> bool:
        return False


def _in_op_names(scope_path: str, lowered_text: str) -> bool:
    """Whether some op's name path (`loc("jit(f)/layers/mlp/dot_general"...)`
    in the lowered text; relative inside a called body) runs through it."""
    return re.search(r'["/]' + re.escape(scope_path) + "/", lowered_text) is not None


@pytest.mark.parametrize("program,args,scopes", [
    ("_decode_fn", _decode_args,
     ("embed", "layers", "attn.qkv", "attn.rope", "kv.update", "attn.decode",
      "attn.out", "mlp", "lm_head", "sample", "finish_mask")),
    ("_prefill_insert_fn", _prefill_args,
     ("embed", "layers", "attn.qkv", "attn.prefill", "attn.out", "mlp",
      "lm_head", "insert", "sample")),
    ("_extend_fn", _extend_args,
     ("kv.update", "attn.prefill", "mlp", "insert", "sample")),
])
def test_named_scopes_are_in_the_op_metadata_and_change_nothing_else(
        monkeypatch, program, args, scopes):
    scoped = _tiny_engine()
    lowered = getattr(scoped, program).lower(*args(scoped))
    with_names = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert _in_op_names(scope, with_names), scope
    # The same program traced with the scopes taken out, as the parent
    # traced it: the computation is the same text, only locations differ.
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    bare = _tiny_engine()
    assert not _in_op_names(scopes[0], getattr(bare, program).lower(
        *args(bare)).as_text(debug_info=True))
    assert getattr(bare, program).lower(*args(bare)).as_text() == lowered.as_text()


def test_greedy_tokens_equal_the_unscoped_programs(monkeypatch):
    prompts = ([1, 2, 3], [9, 8, 7, 6, 5], list(range(20, 40)))
    scoped = _tiny_engine()
    want = [scoped.generate(p, GREEDY)[0] for p in prompts]
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    bare = _tiny_engine()
    assert [bare.generate(p, GREEDY)[0] for p in prompts] == want


def test_moe_scopes():
    import jax.numpy as jnp

    from omnia_tpu.models import llama

    cfg = get_config("test-tiny-moe")
    params = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    ck, cv = llama.init_kv_cache(cfg, 2, 16, dtype=jnp.float32)
    toks = jnp.zeros((2, 1), jnp.int32)
    text = jax.jit(
        lambda p, ck, cv: llama.forward(p, cfg, toks, toks, ck, cv, toks[:, 0])
    ).lower(params, ck, cv).as_text(debug_info=True)
    assert _in_op_names("mlp/moe.experts", text)
    assert _in_op_names("moe.experts/moe.route", text)


def test_place_span_says_deferred_and_the_first_token_read_is_chunk_zero(tmp_path):
    """A plain request's placement ends with its first token unread
    (`deferred` on the span); the token is then read as an entry of the
    pipeline: a `chunk_sync` of no decode step, and an `emit` of one
    token. Made to read at once, the same engine says so and writes no
    such entry."""
    eng = _tiny_engine(num_slots=1)
    eng.generate([1, 2, 3], GREEDY)  # compile outside the session
    with _profiled(tmp_path) as spans:
        eng.generate([1, 2, 3], GREEDY)
        eng._defers_first_token = lambda request: False
        eng.generate([4, 5, 6], GREEDY)
    (events,) = spans().values()
    places = _named(events, phases.PLACE)
    assert [p[3]["deferred"] for p in places] == [1, 0]
    assert all({"slot", "reuse", "seeded"} <= set(p[3]) for p in places)
    syncs = _named(events, phases.CHUNK_SYNC)
    first_reads = [s for s in syncs if s[3]["chunk"] == 0]
    assert len(first_reads) == 1
    # After its placement, before any chunk of that request is read, and
    # before the second placement.
    (read,) = first_reads
    assert places[0][2] <= read[1] and read[2] <= places[1][1]
    assert not any(s[1] < read[1] and places[0][2] <= s[1] for s in syncs)
    emits = [e for e in _named(events, phases.EMIT) if read[2] <= e[1]]
    assert emits[0][3]["tokens"] == 1 and emits[0][3]["finished"] == 0
    # The decode step that follows the prefill was dispatched before the read.
    ahead = [d for d in _named(events, phases.DECODE_DISPATCH)
             if places[0][2] <= d[1] and d[2] <= read[1]]
    assert len(ahead) == 1 and ahead[0][3]["inflight"] == 1
