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
    t0 = clock[0]
    rec.note_submit(rid, 5)
    clock[0] += 1.0
    # The slot came free 0.25 after the submit, the pass that claims
    # began at 0.75: the wait is 0.25 slot + 0.5 loop + 0.25 flush.
    rec.note_claim(rid, t_free=t0 + 0.25, t_pass=t0 + 0.75)
    clock[0] += 2.0
    # Enqueued 0.5 after the claim; the read blocked for the last 1.0.
    rec.note_placement(rid, 0, 5, reuse=1, seeded=2, t_enq=t0 + 1.5,
                       t_read0=t0 + 2.0, t_read=t0 + 3.0)
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
        assert (bd["slot_wait_s"], bd["loop_wait_s"], bd["flush_s"]) == (
            0.25, 0.5, 0.25)
        assert bd["place_s"] == 0.5         # claim → enqueued
        # Enqueued → first token: the device's prefill as the host sees
        # it, never the enqueue's own wall (prefill_piece.dispatch_s).
        assert bd["prefill_s"] == 1.5
        assert bd["read_blocked_s"] == 1.0
        assert bd["placement_s"] == 2.0 == bd["place_s"] + bd["prefill_s"]
        assert bd["ttft_s"] == 3.0          # submit → first token
        assert bd["queue_s"] + bd["place_s"] + bd["prefill_s"] == bd["ttft_s"]
        # The same parts ride the events that exist.
        claim = rec.events("claim")[0].attrs
        assert claim == {"slot_wait_s": 0.25, "loop_wait_s": 0.5, "flush_s": 0.25}
        placed = rec.events("placement")[0].attrs
        assert (placed["place_s"], placed["prefill_s"],
                placed["read_blocked_s"]) == (0.5, 1.5, 1.0)
        assert placed["reuse"] == 1 and placed["seeded"] == 2
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
        # No slot, no pass, no claim: the parts say nothing.
        assert bd["slot_wait_s"] == bd["loop_wait_s"] == bd["flush_s"] == 0.0
        assert bd["place_s"] == bd["prefill_s"] == 0.0

    @pytest.mark.parametrize("t_free,t_pass,parts", [
        (-5.0, 0.5, (0.0, 0.5, 0.5)),   # the slot was free before the submit
        (0.75, 0.5, (0.75, 0.0, 0.25)),  # freed inside the claiming pass
        (0.25, -1.0, (0.25, 0.0, 0.75)),  # the pass began before the submit
        (0.0, 0.0, (0.0, 0.0, 1.0)),    # a slot never used, no pass noted
        (2.0, 3.0, (1.0, 0.0, 0.0)),    # stamps past the claim clip to it
        (0.5, 1.0, (0.5, 0.5, 0.0)),    # claimed as its pass began
    ], ids=["free-before-submit", "freed-inside-the-pass",
            "pass-before-submit", "defaults", "past-the-claim",
            "claimed-at-once"])
    def test_queue_parts_are_clipped_in_order_and_tile(self, t_free, t_pass, parts):
        """The two cuts are clipped into [submit, claim] in that order,
        so whatever the stamps the three parts are non-negative and sum
        to ``queue_s``."""
        rec, clock = self._clocked()
        clock[0] = 10.0
        rec.note_submit("r", 4)
        clock[0] = 11.0
        got = rec.note_claim("r", t_free=10.0 + t_free, t_pass=10.0 + t_pass)
        assert tuple(got.values()) == parts
        rec.note_terminal("r", "stop")
        bd = rec.events("terminal")[0].attrs["breakdown"]
        assert (bd["slot_wait_s"], bd["loop_wait_s"], bd["flush_s"]) == parts
        assert sum(parts) == bd["queue_s"] == 1.0

    @pytest.mark.parametrize("seed", range(4))
    def test_stages_tile_the_first_token_whatever_the_stamps(self, seed):
        """Random boundaries, in and out of order: the queue's parts sum
        to ``queue_s`` and ``queue_s + place_s + prefill_s == ttft_s``,
        every stage non-negative."""
        import random

        rng = random.Random(seed)
        rec, clock = self._clocked()
        for i in range(50):
            rid = f"r{i}"
            clock[0] += rng.random()
            t_sub = clock[0]
            rec.note_submit(rid, 4)
            clock[0] += rng.random()
            rec.note_claim(rid, t_free=t_sub + rng.uniform(-1, 2),
                           t_pass=t_sub + rng.uniform(-1, 2))
            t_claim = clock[0]
            clock[0] += rng.random()
            rec.note_placement(rid, 0, 4, t_enq=t_claim + rng.uniform(-0.5, 1.5),
                               t_read0=clock[0] - rng.random(), t_read=clock[0])
            first = clock[0] + rng.random() * 1e-3
            clock[0] += rng.random()
            rec.note_terminal(rid, "stop", tokens=3, first_token_at=first)
        for ev in rec.events("terminal"):
            bd = ev.attrs["breakdown"]
            stages = [bd[k] for k in ("slot_wait_s", "loop_wait_s", "flush_s",
                                      "place_s", "prefill_s", "read_blocked_s")]
            assert min(stages) >= 0.0
            assert sum(stages[:3]) == pytest.approx(bd["queue_s"], abs=2e-6)
            assert bd["queue_s"] + bd["place_s"] + bd["prefill_s"] == pytest.approx(
                bd["ttft_s"], abs=2e-6)
            assert bd["placement_s"] == pytest.approx(
                bd["place_s"] + bd["prefill_s"], abs=2e-6)

    def test_a_request_without_a_first_token_ends_its_prefill_at_the_note(self):
        """A first token that is a stop id pushes no token: the prefill
        ends where the placement was noted. A placement that failed was
        never noted: all of claim → terminal is ``place_s``."""
        rec, clock = self._clocked()
        rec.note_submit("stop", 4)
        rec.note_claim("stop")
        clock[0] = 1.0
        rec.note_placement("stop", 0, 4, t_enq=0.25)
        clock[0] = 1.5
        rec.note_terminal("stop", "stop")
        rec.note_submit("failed", 4)
        rec.note_claim("failed")
        clock[0] = 2.0
        rec.note_terminal("failed", "error", error="prefill failed")
        stop, failed = (e.attrs["breakdown"] for e in rec.events("terminal"))
        assert (stop["place_s"], stop["prefill_s"], stop["ttft_s"]) == (0.25, 0.75, 0.0)
        assert (failed["place_s"], failed["prefill_s"]) == (0.5, 0.0)
        assert failed["placement_s"] == 0.5

    def test_chrome_rows_are_the_breakdowns_stages(self):
        """A request's row reads slot_wait → loop_wait → flush → place →
        prefill → decode, each as long as the field of that name, laid
        end to end from the submit to the terminal."""
        rec, clock = self._clocked()
        _scripted_run(rec, clock)
        rows = {e["name"]: e for e in to_chrome_trace(rec.events())["traceEvents"]
                if e["ph"] == "X"}
        assert list(rows) == ["slot_wait", "loop_wait", "flush", "place",
                              "prefill", "decode"]
        bd = rec.events("terminal")[0].attrs["breakdown"]
        for name in ("slot_wait", "loop_wait", "flush", "place", "prefill"):
            assert rows[name]["dur"] == pytest.approx(bd[name + "_s"] * 1e6)
        assert rows["decode"]["dur"] == pytest.approx(bd["decode_s"] * 1e6)
        at = 0.0
        for row in rows.values():
            assert row["ts"] == pytest.approx(at)
            at += row["dur"]
        assert "queue" not in rows and "placement" not in rows
        assert rows["prefill"]["args"]["read_blocked_s"] == 1.0

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
        queue = next(e for e in doc["traceEvents"] if e["name"] == "flush")
        assert queue["ts"] == pytest.approx(2.25e6)
        assert queue["dur"] == pytest.approx(0.5e6)
        assert to_chrome_trace(rec.events())["traceEvents"][-1]["ts"] == 0.0
        dump, out = str(tmp_path / "f.jsonl"), str(tmp_path / "t.json")
        rec.dump_jsonl(dump)
        assert flight_main([dump, "-o", out, "--profiler-offset-s", str(offset)]) == 0
        cli = json.load(open(out))
        assert next(e for e in cli["traceEvents"]
                    if e["name"] == "flush")["ts"] == pytest.approx(2.25e6)

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
        assert {"slot_wait", "loop_wait", "flush", "place", "prefill",
                "decode"} <= names
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
        # The same attrs as the engine's, from the mock's own seams: the
        # wait for the playback thread is the loop's, the scripted ttft
        # the prefill, all of it spent blocked.
        claim = m._flight.events("claim")[0].attrs
        placed = m._flight.events("placement")[0].attrs
        assert set(claim) == {"slot_wait_s", "loop_wait_s", "flush_s"}
        assert {"place_s", "prefill_s", "read_blocked_s"} <= set(placed)
        assert "prefill_s" in placed and placed["prefill_s"] >= placed["read_blocked_s"] > 0
        assert claim["slot_wait_s"] == 0.0 and bd["flush_s"] < 1e-3
        assert sum(claim.values()) == pytest.approx(bd["queue_s"], abs=2e-6)
        assert bd["queue_s"] + bd["place_s"] + bd["prefill_s"] == pytest.approx(
            bd["ttft_s"], abs=2e-6)
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
        assert {"flush", "place", "prefill", "decode", "decode_chunk"} <= names
        root.end()

    @pytest.mark.parametrize("path", ["deferred", "read-at-once", "extend",
                                      "interleaved"])
    def test_every_request_tiles_on_every_placement_path(self, path):
        """Two slots, three requests (the third waits for a slot), on the
        path whose first token rides the pipeline, the one that reads it
        at once (a grammar), a chunked extend and an interleaved
        placement: for every request the queue's parts sum to ``queue_s``
        and ``queue_s + place_s + prefill_s == ttft_s``, to a microsecond
        (each field is rounded to one)."""
        from omnia_tpu.engine.tokenizer import ByteTokenizer

        over = {
            "deferred": {},
            "read-at-once": {"grammar": True, "grammar_max_states": 256},
            "extend": {},
            "interleaved": {"prefill_chunk_tokens": 4},
        }[path]
        eng = _tiny_engine(max_sessions=0, prefill_buckets=(8,), **over)
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        grammar = None
        if path == "read-at-once":
            from omnia_tpu.engine.grammar import compile_json_schema

            grammar = compile_json_schema(
                {"type": "object", "properties": {"a": {"type": "integer"}},
                 "required": ["a"]}, ByteTokenizer())
            sp = SamplingParams(temperature=0.0, max_tokens=24,
                                stop_token_ids=(0,))
        n = 20 if path in ("extend", "interleaved") else 4
        handles = [
            eng.submit(ByteTokenizer().encode("json") if grammar
                       else list(range(i + 1, i + 1 + n)), sp, grammar=grammar)
            for i in range(3)
        ]
        while eng.step():
            pass
        for h in handles:
            h.collect_tokens(timeout=60)
        m = eng.metrics
        assert m["prefill_steps"] == 3
        assert m["placements_deferred"] == (0 if grammar else 3)
        assert (m["extend_steps"] > 0) == (path in ("extend", "interleaved"))
        assert (m["mixed_steps"] > 0) == (path == "interleaved")
        bds = {e.request_id: e.attrs["breakdown"]
               for e in eng._flight.events("terminal")}
        assert len(bds) == 3
        for bd in bds.values():
            parts = [bd[k] for k in ("slot_wait_s", "loop_wait_s", "flush_s")]
            assert min(parts + [bd["place_s"], bd["prefill_s"]]) >= 0.0
            assert sum(parts) == pytest.approx(bd["queue_s"], abs=2e-6)
            assert bd["queue_s"] + bd["place_s"] + bd["prefill_s"] == (
                pytest.approx(bd["ttft_s"], abs=2e-6))
            assert bd["placement_s"] == pytest.approx(
                bd["place_s"] + bd["prefill_s"], abs=2e-6)
            # The thread blocked in the read for part of the prefill, and
            # the enqueue came after the claim.
            assert 0.0 < bd["read_blocked_s"] <= bd["prefill_s"]
            assert bd["place_s"] > 0.0
        # The third request had no slot until one of the first two ended.
        assert bds[handles[2].request_id]["slot_wait_s"] > 0.0
        assert bds[handles[0].request_id]["slot_wait_s"] == 0.0
        # The events carry what the breakdown was made of.
        for ev in eng._flight.events("claim"):
            assert sum(ev.attrs.values()) == pytest.approx(
                bds[ev.request_id]["queue_s"], abs=2e-6)
        for ev in eng._flight.events("placement"):
            bd = bds[ev.request_id]
            assert ev.attrs["place_s"] == pytest.approx(bd["place_s"], abs=2e-6)
            # Stamped as the emit begins, microseconds before the handle's.
            assert 0.0 <= bd["prefill_s"] - ev.attrs["prefill_s"] < 5e-3

    def test_slot_wait_ends_when_any_slot_it_could_take_was_free(self):
        """Two slots. `a` ends inside the flush of the pass that claims
        `c`, and `c` takes the slot `a` left (the lowest-numbered free
        one), freed after `c` was submitted; the other slot was free all
        along, so `c` never waited for a slot: its wait is the loop's and
        the flush's."""
        eng = _tiny_engine(max_sessions=0)
        a = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=2))
        eng.step()  # a placed in slot 0, its second token in flight
        assert eng._slots[0].active and len(eng._inflight) == 1
        c = eng.submit([4, 5, 6], GREEDY)
        eng.step()  # flushed for c: a ends; c claimed and placed
        placed = {e.request_id: e.attrs for e in eng._flight.events("placement")}
        claim = {e.request_id: e.attrs for e in eng._flight.events("claim")}
        assert placed[c.request_id]["slot"] == 0 == placed[a.request_id]["slot"]
        assert eng._slots[0].freed_at > 0.0 == eng._slots[1].freed_at
        assert claim[c.request_id]["slot_wait_s"] == 0.0
        assert claim[c.request_id]["flush_s"] > 0.0
        while eng.step():
            pass
        assert len(c.collect_tokens(timeout=60)[0]) == 8

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


class _CountedTime:
    """``time`` as a module under test sees it, with every
    ``monotonic()`` call counted under the name of the function that
    made it."""

    def __init__(self):
        import collections

        self.calls = collections.Counter()

    def monotonic(self):
        import sys
        import time

        self.calls[sys._getframe(1).f_code.co_name] += 1
        return time.monotonic()

    def __getattr__(self, name):
        import time

        return getattr(time, name)


@pytest.mark.parametrize("flight_events", [0, 512], ids=["off", "on"])
def test_the_stage_stamps_are_taken_only_with_a_recorder(monkeypatch, flight_events):
    """With ``flight_events=0`` and no profiler session the scheduling
    pass, the claim, the placement, the first token's read and the
    slot's release read the clock exactly where the parent did:
    ``_place_request`` twice a placement (around the prefill's enqueue,
    for ``prefill_dispatch_s``) and nowhere else. With a recorder on,
    every boundary of ``LatencyBreakdown`` is one read more."""
    pytest.importorskip("jax")
    from omnia_tpu.engine import interleave, placement, scheduler, sessions

    eng = _tiny_engine(flight_events=flight_events, max_sessions=0, num_slots=1)
    eng.generate([1, 2, 3], GREEDY)  # compile first
    clock = _CountedTime()
    for mod in (scheduler, placement, interleave, sessions):
        monkeypatch.setattr(mod, "time", clock)
    handles = [eng.submit([i + 1, i + 2, i + 3], GREEDY) for i in range(2)]
    passes = 0
    while eng.step():
        passes += 1
    for h in handles:
        assert len(h.collect_tokens(timeout=60)[0]) == 8
    stamps = {name: clock.calls[name] for name in (
        "_schedule", "_claim_pending", "_place_request", "_activate_slot",
        "_process_first_token", "_emit_first_token", "_finish_slot",
        "_free_slot")}
    if flight_events == 0:
        assert stamps == {
            "_schedule": 0, "_claim_pending": 0, "_place_request": 2 * 2,
            "_activate_slot": 0, "_process_first_token": 0,
            "_emit_first_token": 0, "_finish_slot": 0, "_free_slot": 0,
        }
    else:
        assert stamps == {
            "_schedule": passes + 1, "_claim_pending": 0,
            "_place_request": 2 * 2, "_activate_slot": 0,
            "_process_first_token": 2 * 2, "_emit_first_token": 0,
            "_finish_slot": 0, "_free_slot": 2,
        }


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
