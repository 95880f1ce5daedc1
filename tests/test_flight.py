"""Engine flight recorder (engine/flight.py): event-ledger exactness,
ring bounds, latency-breakdown arithmetic, Chrome-trace export schema,
traceparent continuity across a counted pre-token worker death, mock
vocabulary parity, and seeded-interleaving concurrency.

Module-level imports are deliberately jax-free: the recorder, its export
CLI, the mock engine, and the coordinator run with no device stack (the
CI analysis job runs this file with no jax installed — engine-backed
tests importorskip jax and simply skip there; tier-1 runs everything).
"""

from __future__ import annotations

import json
import threading

import pytest

from omnia_tpu.engine.coordinator import EngineCoordinator
from omnia_tpu.engine.faults import FaultPlan
from omnia_tpu.engine.flight import (
    EVENTS,
    FlightRecorder,
    load_jsonl,
    main as flight_main,
    to_chrome_trace,
)
from omnia_tpu.engine.mock import MockEngine, Scenario
from omnia_tpu.engine.types import FinishReason, SamplingParams
from omnia_tpu.utils import tracing as tr

pytestmark = pytest.mark.flight

GREEDY = SamplingParams(temperature=0.0, max_tokens=8)


def _scripted_run(rec: FlightRecorder, clock: list, rid: str = "r1",
                  tokens: int = 3) -> None:
    """One full request lifecycle against an injected clock. The emit
    hot path never calls the recorder — the first-token stamp (taken by
    the handle) rides the terminal, exactly like the engine seams."""
    rec.note_submit(rid, 5)
    clock[0] += 1.0
    rec.note_claim(rid)
    clock[0] += 2.0
    rec.note_placement(rid, 0, 5, reuse=1, seeded=2, prefill_s=1.5)
    first_token_at = clock[0]  # first token lands AT placement
    clock[0] += float(tokens)  # decode: 1.0 per further token + finish
    rec.note_terminal(rid, "stop", tokens=tokens,
                      first_token_at=first_token_at)


class TestRecorderUnit:
    def _clocked(self, capacity: int = 64):
        clock = [0.0]
        return FlightRecorder(capacity, clock=lambda: clock[0]), clock

    def test_capacity_zero_refused(self):
        with pytest.raises(ValueError):
            FlightRecorder(0)

    def test_breakdown_stage_arithmetic(self):
        """The LatencyBreakdown fields against a scripted clock: the
        stages must tile the wall exactly (queue + placement + decode ==
        terminal - submit) and per-token decode is the mean gap."""
        rec, clock = self._clocked()
        _scripted_run(rec, clock, tokens=3)
        term = rec.events("terminal")[0]
        bd = term.attrs["breakdown"]
        assert bd["queue_s"] == 1.0
        assert bd["placement_s"] == 2.0
        assert bd["prefill_s"] == 1.5
        assert bd["ttft_s"] == 3.0          # submit → first token
        assert bd["decode_s"] == 3.0        # first token → terminal
        assert bd["decode_s_per_token"] == 1.5
        assert bd["tokens"] == 3
        wall = 6.0  # terminal mono - submit mono under the scripted clock
        assert bd["queue_s"] + bd["placement_s"] + bd["decode_s"] == wall
        # Histograms observed once per request (inter_token = the mean
        # gap at the terminal — never a per-token observe on the hot path).
        assert rec.hist["ttft"].count == 1
        assert rec.hist["queue_wait"].count == 1
        assert rec.hist["inter_token"].count == 1
        # Open books closed at the terminal: no leak on a long-lived engine.
        assert rec.stats()["open_requests"] == 0

    def test_ring_overwrite_bounds(self):
        rec, clock = self._clocked(capacity=8)
        for i in range(10):
            _scripted_run(rec, clock, rid=f"r{i}", tokens=2)
        evs = rec.events()
        stats = rec.stats()
        assert len(evs) == 8 == stats["retained"]
        assert stats["recorded"] == 40  # 4 ring events per request
        assert stats["dropped"] == 32
        # The retained window is the contiguous TAIL of the seq stream.
        seqs = [e.seq for e in evs]
        assert seqs == list(range(32, 40))
        assert stats["open_requests"] == 0

    def test_vocabulary_is_closed(self):
        rec, _clock = self._clocked()
        with pytest.raises(AssertionError):
            rec._record("not-a-kind", "", {})
        for e in rec.events():
            assert e.kind in EVENTS

    def test_stall_attribution_windows_per_request(self):
        """stall_steps counts engine stalls observed during THIS
        request's lifetime, not all-time."""
        rec, clock = self._clocked()
        rec.note_stall(3)                    # before r1 exists
        rec.note_submit("r1", 4)
        rec.note_stall(2)                    # during r1
        rec.note_terminal("r1", "stop")
        rec.note_submit("r2", 4)
        rec.note_terminal("r2", "stop")      # no stalls during r2
        bds = [e.attrs["breakdown"] for e in rec.events("terminal")]
        assert bds[0]["stall_steps"] == 2
        assert bds[1]["stall_steps"] == 0

    def test_queue_reaped_terminal_attributes_wait_to_queue(self):
        """A request reaped from the queue (deadline/cancel/drain) was
        never claimed — its whole lifetime IS queue wait, and the
        breakdown must say so (an all-zero breakdown would blind the
        queue-pressure diagnosis the runbook leans on)."""
        rec, clock = self._clocked()
        rec.note_submit("q1", 4)
        clock[0] += 2.5
        rec.note_terminal("q1", "deadline")
        bd = rec.events("terminal")[0].attrs["breakdown"]
        assert bd["queue_s"] == 2.5
        assert bd["placement_s"] == 0.0 and bd["ttft_s"] == 0.0

    def test_chrome_trace_head_duration_event_stays_nonnegative(self):
        """Ring-overwrite head case: when the earliest retained event is
        a duration event (decode_chunk recorded at its END), its computed
        start must not land at a negative ts."""
        rec, clock = self._clocked()
        rec.note_decode_chunk(4, 0.010, 0.005, 2)  # recorded at end
        clock[0] += 1.0
        rec.note_submit("r", 4)
        rec.note_terminal("r", "stop")
        doc = to_chrome_trace(rec.events())
        for e in doc["traceEvents"]:
            if e["ph"] != "M":
                assert e["ts"] >= 0, e
        chunk = next(e for e in doc["traceEvents"]
                     if e["name"] == "decode_chunk")
        assert chunk["ts"] == 0.0  # the dump's origin is its true start

    def test_chrome_trace_on_the_profilers_time_axis(self, tmp_path):
        """With the offset an ``omnia.engine.step`` span gives (its start
        on the profiler's clock less its ``mono_ns``), every event lands
        at mono + offset instead of at the dump's own origin — from the
        function and from the CLI alike."""
        rec, clock = self._clocked()
        clock[0] = 100.0
        rec.note_submit("r", 4)
        clock[0] = 100.5
        rec.note_claim("r")
        offset = 2.25 - 100.0  # the profiler's 2.25 s is this clock's 100 s
        doc = to_chrome_trace(rec.events(), profiler_offset_s=offset)
        queue = next(e for e in doc["traceEvents"] if e["name"] == "queue")
        assert queue["ts"] == pytest.approx(2.25e6)
        assert queue["dur"] == pytest.approx(0.5e6)
        assert to_chrome_trace(rec.events())["traceEvents"][-1]["ts"] == 0.0
        dump, out = str(tmp_path / "f.jsonl"), str(tmp_path / "t.json")
        rec.dump_jsonl(dump)
        assert flight_main([dump, "-o", out, "--profiler-offset-s", str(offset)]) == 0
        cli = json.load(open(out))
        assert next(e for e in cli["traceEvents"]
                    if e["name"] == "queue")["ts"] == pytest.approx(2.25e6)

    def test_terminal_without_submit_is_tolerated(self):
        """A terminal for a request the recorder never saw (ring
        recycled mid-incident) records an empty breakdown, not a crash."""
        rec, _clock = self._clocked()
        rec.note_terminal("ghost", "error", error="boom")
        term = rec.events("terminal")[0]
        assert term.attrs["reason"] == "error"
        assert term.attrs["breakdown"]["tokens"] == 0

    def test_jsonl_dump_and_cli_chrome_export(self, tmp_path, capsys):
        rec, clock = self._clocked()
        _scripted_run(rec, clock)
        dump = str(tmp_path / "flight.jsonl")
        n = rec.dump_jsonl(dump)
        assert n == len(load_jsonl(dump)) == 4
        out = str(tmp_path / "trace.json")
        assert flight_main([dump, "-o", out]) == 0
        assert "1 terminals" in capsys.readouterr().out
        doc = json.load(open(out))
        self._check_chrome_schema(doc)

    def _check_chrome_schema(self, doc: dict) -> None:
        evs = doc["traceEvents"]
        assert isinstance(evs, list) and evs
        for e in evs:
            assert e["ph"] in ("M", "X", "i")
            assert e["pid"] == 1
            if e["ph"] != "M":
                assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            if e["ph"] == "X":
                assert e["dur"] >= 0
        names = {e["name"] for e in evs}
        # The per-request phase rows and the terminal marker.
        assert {"queue", "placement", "decode"} <= names
        assert any(n.startswith("finish:") for n in names)
        # Request rows are named via thread_name metadata.
        assert any(
            e["ph"] == "M" and e["name"] == "thread_name"
            and e["args"]["name"] == "r1" for e in evs
        )

    def test_chrome_trace_engine_step_row(self):
        rec, _clock = self._clocked()
        rec.note_decode_chunk(4, 0.001, 0.002, 2)
        rec.note_mixed_step("r", 8, 8, 0.003)
        rec.note_prefill_piece("r", 8, 8, 0.004)
        rec.note_offload("s", 16)
        rec.note_restore("s", 1)
        doc = to_chrome_trace(rec.events())
        by_name = {}
        for e in doc["traceEvents"]:
            by_name.setdefault(e["name"], e)
        assert by_name["decode_chunk"]["ph"] == "X"
        assert by_name["decode_chunk"]["tid"] == 0
        assert by_name["decode_chunk"]["dur"] == pytest.approx(3000, abs=1)
        assert by_name["offload"]["ph"] == "i"
        # Per-chunk dispatch/sync histograms observed in µs.
        assert rec.hist["dispatch_us"].count == 1
        assert rec.hist["sync_us"].count == 1


class TestMockParity:
    def test_mock_records_engine_vocabulary(self):
        """The mock emits the IDENTICAL event vocabulary on a playback:
        hermetic tests see the same timeline shape the real engine
        records, and the terminal ledger reconciles exactly."""
        m = MockEngine([Scenario("hi", "hello")], flight_events=64)
        toks, fin = m.generate(m.tokenizer.encode("hi"), GREEDY)
        assert fin.finish_reason is FinishReason.STOP
        kinds = [e.kind for e in m._flight.events()]
        assert set(kinds) <= EVENTS
        assert kinds == ["submit", "claim", "placement", "terminal"]
        assert m.metrics["flight_enabled"] == 1
        term = m._flight.events("terminal")[0]
        bd = term.attrs["breakdown"]
        assert bd["tokens"] == len(toks) == 5
        assert bd["ttft_s"] >= 0 and bd["queue_s"] >= 0
        assert m._flight.hist["ttft"].count == 1
        # Ledger exactness: one terminal per accepted submit.
        assert len(m._flight.events("terminal")) == m.metrics["requests_finished"]
        assert len(m._flight.events("submit")) == m.metrics["requests_submitted"]

    def test_metrics_rebind_replaces_dead_engine(self):
        """Rebinding a registry to a replacement engine must repoint the
        collector — a first-wins register would keep exposing the dead
        engine's frozen counters while still passing the freshness stamp."""
        from omnia_tpu.utils.metrics import Registry, bind_engine_metrics

        old = MockEngine([Scenario(".*", "abc")], flight_events=16)
        old.generate(old.tokenizer.encode("x"), GREEDY)
        reg = Registry(prefix="omnia_facade")
        bind_engine_metrics(reg, old)
        assert "omnia_engine_requests_finished 1.0" in reg.expose()
        new = MockEngine([Scenario(".*", "abc")], flight_events=16)
        bind_engine_metrics(reg, new)  # provider reload: engine replaced
        assert "omnia_engine_requests_finished 0.0" in reg.expose()
        new.generate(new.tokenizer.encode("x"), GREEDY)
        body = reg.expose()
        assert "omnia_engine_requests_finished 1.0" in body
        # The replacement recorder's histograms took over too.
        assert "omnia_engine_ttft_seconds_count 1" in body
        # Rebinding to a recorder-LESS engine sweeps the old flight
        # histograms — frozen series from the dead engine must not
        # survive behind a passing freshness stamp.
        bind_engine_metrics(reg, MockEngine([], flight_events=0))
        swept = reg.expose()
        assert "omnia_engine_ttft_seconds" not in swept
        assert "omnia_engine_flight_enabled 0.0" in swept

    def test_doctor_presence_ignores_freshness_stamp(self):
        """The collector's own scrape_unixtime stamp must not satisfy
        the engine-family presence check: a collector bound to an empty
        source (mis-wired engine) exposes ONLY the stamp, and that is a
        FAIL, not '1 live engine series'."""
        from omnia_tpu.doctor import Doctor
        from omnia_tpu.utils.metrics import DictCollector, Registry

        reg = Registry(prefix="omnia_facade")
        reg.register(DictCollector("omnia_engine", lambda: {}))
        d = Doctor()
        d.add_engine_metrics_check(reg.expose)
        check = d.run()["checks"][0]
        assert check["status"] == "fail", check
        assert "no omnia_engine_* series" in check["detail"]

    def test_mock_shed_records_no_submit(self):
        """Rejected requests (validation/overload) never enter the
        flight books — submit events mirror requests_submitted, never
        requests_shed."""
        m = MockEngine([], flight_events=64, max_queue=0)
        h = m.submit([], GREEDY)  # validation reject: empty prompt
        _toks, fin = h.collect_tokens(timeout=5)
        assert fin.finish_reason is FinishReason.ERROR
        assert m._flight.events() == []


class TestTraceContinuity:
    def _fleet(self, fault_worker0: FaultPlan):
        w0 = MockEngine([Scenario(".*", "abcde")], flight_events=64,
                        fault_plan=fault_worker0)
        w1 = MockEngine([Scenario(".*", "abcde")], flight_events=64)
        w0.tracer = tr.Tracer("worker-0")
        w1.tracer = tr.Tracer("worker-1")
        coord = EngineCoordinator([w0, w1], flight_events=64,
                                  probe_timeout_s=None)
        return coord, w0, w1

    def test_traceparent_survives_pretoken_worker_death(self):
        """ISSUE 10 acceptance: one injected pre-token worker death —
        the request transparently resubmits, the coordinator records the
        failure as flight events, and BOTH workers' engine spans carry
        the SAME trace id as the caller's span (new events, not a new
        trace)."""
        plan = FaultPlan(die_after_tokens=0, die_count=1)
        coord, w0, w1 = self._fleet(plan)
        root = tr.Tracer("runtime").start_span("llm-turn")
        # Ties route to worker 0 (least-loaded min by (load, idx)), so
        # the counted death fires on the first placement.
        h = coord.submit(w0.tokenizer.encode("go"), GREEDY,
                         trace_ctx=root.traceparent())
        toks, fin = h.collect_tokens(timeout=30)
        assert fin.finish_reason is FinishReason.STOP
        assert w0.tokenizer.decode(toks) == "abcde"
        assert plan.fired["deaths"] == 1
        assert coord.metrics["resubmits"] == 1
        # The coordinator's flight trail shows the re-placement.
        coord_kinds = [e.kind for e in coord._flight.events()]
        assert "resubmit" in coord_kinds
        # Both workers opened engine-request spans under ONE trace id.
        s0 = w0.tracer.spans(tr.SPAN_ENGINE)
        s1 = w1.tracer.spans(tr.SPAN_ENGINE)
        assert len(s0) == 1 and len(s1) == 1
        assert s0[0].trace_id == s1[0].trace_id == root.trace_id
        # The dead worker's span closed with the error; the replacement
        # carries the real finish.
        assert s0[0].attrs["llm.finish_reason"] == "error"
        assert s1[0].attrs["llm.finish_reason"] == "stop"
        assert s1[0].attrs["engine.tokens"] == 5
        root.end()

    def test_submit_failover_reuses_trace_ctx(self):
        """A worker whose submit() raises is failed over — the
        replacement still receives the caller's trace context and the
        coordinator records the failover event."""
        plan = FaultPlan(flaky_submit=1)
        coord, w0, w1 = self._fleet(plan)
        root = tr.Tracer("runtime").start_span("llm-turn")
        h = coord.submit(w0.tokenizer.encode("go"), GREEDY,
                         trace_ctx=root.traceparent())
        _toks, fin = h.collect_tokens(timeout=30)
        assert fin.finish_reason is FinishReason.STOP
        assert [e.kind for e in coord._flight.events()].count("failover") == 1
        spans = w1.tracer.spans(tr.SPAN_ENGINE)
        assert len(spans) == 1 and spans[0].trace_id == root.trace_id
        root.end()

    def test_unsampled_parent_opens_no_engine_span(self):
        """Parent-based sampling holds end-to-end: an unsampled llm span
        (flags 00 — what a _NoopSpan propagates) must not resurrect as
        an engine span."""
        m = MockEngine([Scenario(".*", "hi")], flight_events=64)
        m.tracer = tr.Tracer("w")
        unsampled = tr.Tracer("up", sample_rate=0.0)
        noop = unsampled.start_span("llm")
        h = m.submit(m.tokenizer.encode("x"), GREEDY,
                     trace_ctx=noop.traceparent())
        h.collect_tokens(timeout=10)
        assert m.tracer.spans(tr.SPAN_ENGINE) == []
        # The flight books still record the lifecycle (tracing and
        # recording are independent planes).
        assert len(m._flight.events("terminal")) == 1


class TestConcurrentRecorders:
    def test_seeded_interleavings_keep_books_exact(self):
        """raceharness satellite: N threads drive full request
        lifecycles into ONE recorder under forced interleavings — the
        seq stream stays strictly contiguous, the ledger reconciles
        exactly (recorded == dropped + retained), every terminal closes
        its books, and the histograms count every request."""
        from raceharness import run_interleaved

        threads, per_thread = 4, 6

        def scenario():
            rec = FlightRecorder(32)

            def body_for(t):
                def body():
                    import time as _t

                    for i in range(per_thread):
                        rid = f"t{t}-r{i}"
                        rec.note_submit(rid, 4)
                        rec.note_claim(rid)
                        rec.note_placement(rid, 0, 4)
                        rec.note_terminal(rid, "stop", tokens=2,
                                          first_token_at=_t.monotonic())
                return body

            def check():
                stats = rec.stats()
                total = threads * per_thread * 4  # 4 ring events/request
                assert stats["recorded"] == total, stats
                assert stats["retained"] + stats["dropped"] == total
                assert stats["open_requests"] == 0
                seqs = [e.seq for e in rec.events()]
                assert seqs == sorted(seqs)
                assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
                assert rec.hist["ttft"].count == threads * per_thread
                n_term = threads * per_thread
                assert rec.hist["queue_wait"].count == n_term

            return [body_for(t) for t in range(threads)], check

        failures = run_interleaved(scenario, seeds=range(6))
        assert not failures, failures

    def test_concurrent_submit_vs_terminal_no_deadlock(self):
        """Submit path (caller thread) racing terminal path (engine
        thread) through the recorder must never deadlock — the regression
        shape of the nested-lock bug found during development."""
        rec = FlightRecorder(64)
        stop = threading.Event()

        def submits():
            i = 0
            while not stop.is_set():
                rec.note_submit(f"s{i}", 1)
                rec.note_terminal(f"s{i}", "stop")
                i += 1

        ts = [threading.Thread(target=submits, daemon=True) for _ in range(3)]
        for t in ts:
            t.start()
        import time as _time

        _time.sleep(0.2)
        stop.set()
        for t in ts:
            t.join(timeout=5)
        assert not any(t.is_alive() for t in ts)
        assert rec.stats()["open_requests"] == 0


# ---------------------------------------------------------------------------
# Real-engine suite (skips cleanly where jax is absent — the CI analysis
# job; tier-1 runs it on the CPU backend).
# ---------------------------------------------------------------------------


def _tiny_engine(**over):
    pytest.importorskip("jax")
    from omnia_tpu.engine import EngineConfig, InferenceEngine
    from omnia_tpu.models import get_config

    base = dict(num_slots=2, max_seq=64, prefill_buckets=(8,),
                dtype="float32", max_sessions=4, flight_events=512)
    base.update(over)
    return InferenceEngine(get_config("test-tiny"), EngineConfig(**base), seed=3)


class TestEngineLedger:
    def test_end_to_end_timeline_and_trace_continuity(self):
        """ISSUE 10 acceptance: one request traced end-to-end — the
        caller's span and the engine's `omnia.engine.request` span share
        a trace id, and the flight dump reconstructs a complete
        queue→placement→prefill→decode→finish timeline whose summed
        stages equal the request's wall time within 5%."""
        eng = _tiny_engine()
        tracer = tr.Tracer("engine-under-test")
        eng.tracer = tracer
        root = tr.Tracer("runtime").start_span("llm")
        h = eng.submit([1, 2, 3], GREEDY, trace_ctx=root.traceparent())
        while eng.step():
            pass
        toks, fin = h.collect_tokens(timeout=60)
        assert fin.finish_reason is FinishReason.LENGTH and len(toks) == 8
        evs = eng._flight.events()
        kinds = [e.kind for e in evs]
        # Complete lifecycle, in order.
        for a, b in zip(["submit", "claim", "placement", "terminal"],
                        ["claim", "placement", "terminal", None]):
            if b is not None:
                assert kinds.index(a) < kinds.index(b), kinds
        assert "prefill_piece" in kinds and "decode_chunk" in kinds
        assert set(kinds) <= EVENTS
        # Stage sum == wall within 5% (plus a tiny absolute epsilon for
        # scheduler bookkeeping between the stage boundaries).
        sub = next(e for e in evs if e.kind == "submit")
        term = next(e for e in evs if e.kind == "terminal")
        bd = term.attrs["breakdown"]
        wall = term.mono - sub.mono
        staged = bd["queue_s"] + bd["placement_s"] + bd["decode_s"]
        assert abs(staged - wall) <= 0.05 * wall + 0.02, (staged, wall, bd)
        assert bd["tokens"] == 8
        assert 0 < bd["ttft_s"] <= wall
        # Trace continuity: engine span under the caller's trace id,
        # breakdown stamped on the span.
        spans = tracer.spans(tr.SPAN_ENGINE)
        assert len(spans) == 1
        assert spans[0].trace_id == root.trace_id
        assert spans[0].parent_id == root.span_id
        assert spans[0].attrs["llm.finish_reason"] == "length"
        assert spans[0].attrs["engine.tokens"] == 8
        assert spans[0].end_ns >= spans[0].start_ns
        # Chrome export of the real run keeps the schema.
        doc = to_chrome_trace(evs)
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"queue", "placement", "decode", "decode_chunk"} <= names
        root.end()

    def test_ledger_reconciles_with_terminal_counters(self):
        """Event-ledger exactness: submit events == requests_submitted,
        terminal events == requests_finished — across normal finishes
        AND a queue-cancelled request."""
        eng = _tiny_engine()
        handles = [eng.submit([1, 2, 3], GREEDY) for _ in range(3)]
        handles[2].cancel()  # reaped from the queue, still a terminal
        while eng.step():
            pass
        for h in handles:
            h.collect_tokens(timeout=60)
        assert len(eng._flight.events("submit")) == (
            eng.metrics["requests_submitted"]) == 3
        assert len(eng._flight.events("terminal")) == (
            eng.metrics["requests_finished"]) == 3
        reasons = sorted(
            e.attrs["reason"] for e in eng._flight.events("terminal")
        )
        assert reasons == ["cancelled", "length", "length"]
        assert eng._flight.stats()["open_requests"] == 0
        # Per-chunk dispatch/sync observations landed.
        assert eng._flight.hist["dispatch_us"].count > 0
        assert eng._flight.hist["sync_us"].count > 0

    def test_prometheus_bridge_and_doctor_freshness(self):
        """bind_engine_metrics exposes the live omnia_engine_* family +
        the recorder histograms through a Registry, and the doctor's
        engine-metrics check passes against it (present AND non-stale)."""
        from omnia_tpu.doctor import Doctor
        from omnia_tpu.utils.metrics import Registry, bind_engine_metrics

        eng = _tiny_engine()
        eng.generate([1, 2, 3], GREEDY)
        reg = Registry(prefix="omnia_facade")
        bind_engine_metrics(reg, eng)
        body = reg.expose()
        assert "omnia_engine_requests_finished 1.0" in body
        assert "omnia_engine_flight_enabled 1.0" in body
        assert "omnia_engine_ttft_seconds_count 1" in body
        assert "omnia_engine_dispatch_us_bucket" in body
        doctor = Doctor()
        doctor.add_engine_metrics_check(reg.expose)
        report = doctor.run()
        assert report["status"] == "pass", report
        # And the check has teeth: a frozen snapshot FAILS freshness.
        frozen = body
        stale = Doctor()
        stale.add_engine_metrics_check(lambda: frozen)
        assert stale.run()["checks"][0]["status"] == "fail"
        # An exposition with no engine family FAILS presence.
        empty = Doctor()
        empty.add_engine_metrics_check(lambda: "omnia_facade_x 1\n")
        assert empty.run()["checks"][0]["status"] == "fail"


def test_flight_off_is_true_noop():
    """KNOB_GUARDS row for EngineConfig.flight_events: 0 (default) must
    allocate ZERO recorder state, trace zero new operands (byte-identical
    lowered decode programs vs a flight-on engine — the layer is
    host-side by design), emit identical greedy tokens, and never open a
    span even when trace_ctx arrives."""
    pytest.importorskip("jax")
    off = _tiny_engine(flight_events=0, max_sessions=0)
    on = _tiny_engine(max_sessions=0)
    assert off._flight is None
    assert off.metrics["flight_enabled"] == 0
    assert on.metrics["flight_enabled"] == 1

    def lowered(eng):
        return eng._decode_fn_single.lower(
            eng.params, eng._ck, eng._cv, eng._tokens, eng._positions,
            eng._active, eng._budget, eng._stop_ids, eng._key_data,
            eng._temp, eng._top_p, eng._top_k,
        ).as_text()

    assert lowered(off) == lowered(on)
    # trace_ctx on a flight-off engine: accepted, ignored, no span.
    tracer = tr.Tracer("off-engine")
    off.tracer = tracer
    root = tr.Tracer("up").start_span("llm")
    t_off, _ = off.generate([4, 5, 6], GREEDY)
    h = off.submit([4, 5, 6], GREEDY, trace_ctx=root.traceparent())
    while off.step():
        pass
    t_ctx, _ = h.collect_tokens(timeout=60)
    t_on, _ = on.generate([4, 5, 6], GREEDY)
    assert t_off == t_on == t_ctx
    assert tracer.spans(tr.SPAN_ENGINE) == []
    root.end()


class TestConversationContinuity:
    def test_runtime_llm_span_and_engine_span_share_trace(self):
        """The full runtime path: Conversation's llm span rides submit()
        as trace_ctx, so the llm span and the engine's request span land
        in one trace — with the turn's conversation span as the root."""
        from omnia_tpu.runtime import contract as c
        from omnia_tpu.runtime.context_store import InMemoryContextStore
        from omnia_tpu.runtime.conversation import Conversation
        from omnia_tpu.runtime.packs import load_pack

        tracer = tr.Tracer("runtime-test")
        engine = MockEngine([Scenario(".*", "hello there")],
                            flight_events=64)
        engine.tracer = tracer
        conv = Conversation(
            session_id="flight-e2e",
            pack=load_pack({"name": "t", "version": "1.0.0",
                            "prompts": {"system": "s"},
                            "sampling": {"max_tokens": 64}}),
            engine=engine,
            tokenizer=engine.tokenizer,
            store=InMemoryContextStore(),
            tracer=tracer,
        )
        msgs = list(conv.stream(c.ClientMessage(content="hi")))
        assert msgs[-1].type == "done"
        conv_spans = tracer.spans(tr.SPAN_CONVERSATION)
        llm_spans = tracer.spans(tr.SPAN_LLM)
        eng_spans = tracer.spans(tr.SPAN_ENGINE)
        assert len(conv_spans) == 1 and len(llm_spans) == 1
        assert len(eng_spans) == 1
        assert eng_spans[0].trace_id == llm_spans[0].trace_id == (
            conv_spans[0].trace_id)
        assert eng_spans[0].parent_id == llm_spans[0].span_id
        # The flight terminal matched the turn's streamed tokens.
        bd = engine._flight.events("terminal")[0].attrs["breakdown"]
        assert bd["tokens"] == len("hello there")

    def test_legacy_engine_without_trace_ctx_still_serves(self):
        """Engines predating the trace_ctx kwarg are supported duck
        types: the conversation retries without it."""
        from omnia_tpu.runtime import contract as c
        from omnia_tpu.runtime.context_store import InMemoryContextStore
        from omnia_tpu.runtime.conversation import Conversation
        from omnia_tpu.runtime.packs import load_pack

        class LegacyEngine(MockEngine):
            def submit(self, prompt_tokens, params=SamplingParams(),
                       session_id=None, grammar=None, deadline_s=None):
                return super().submit(prompt_tokens, params,
                                      session_id=session_id)

        tracer = tr.Tracer("runtime-test")
        engine = LegacyEngine([Scenario(".*", "ok")])
        conv = Conversation(
            session_id="legacy",
            pack=load_pack({"name": "t", "version": "1.0.0",
                            "prompts": {"system": "s"},
                            "sampling": {"max_tokens": 16}}),
            engine=engine,
            tokenizer=engine.tokenizer,
            store=InMemoryContextStore(),
            tracer=tracer,
        )
        msgs = list(conv.stream(c.ClientMessage(content="hi")))
        assert msgs[-1].type == "done"
        assert tracer.spans(tr.SPAN_LLM)  # the llm span still exists
