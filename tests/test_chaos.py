"""Deterministic chaos harness for the request-lifecycle robustness layer.

Every scenario injects ONE counted fault (engine/faults.FaultPlan) and
asserts the system degrades to exactly one terminal event per request
with the correct FinishReason, and that the coordinator/engine metrics
reconcile EXACTLY with the observed terminal events. No randomness: the
plans are counted, the backoff jitter is seeded, deadline tests inject
the engine's logical clock, and the suite runs hermetically on
JAX_PLATFORMS=cpu (mock workers everywhere; the two scenarios that need
the real scheduler/watchdog use the test-tiny engine).

Fault matrix (ISSUE 7 acceptance): worker death pre-token, worker death
mid-stream, hang-on-dispatch, full queue, deadline in queue, deadline
mid-decode — plus flaky-submit failover, graceful drain, and the
all-faults reconciliation battery.
"""

from __future__ import annotations

import queue as queue_mod
import time

import pytest

from omnia_tpu.engine import (
    EngineConfig,
    FinishReason,
    InferenceEngine,
    MockEngine,
    SamplingParams,
)
from omnia_tpu.engine.coordinator import EngineCoordinator
from omnia_tpu.engine.faults import FaultPlan
from omnia_tpu.engine.mock import Scenario
from omnia_tpu.engine.tokenizer import ByteTokenizer
from omnia_tpu.models import get_config

pytestmark = pytest.mark.chaos

TOK = ByteTokenizer()
SP = SamplingParams(max_tokens=64)
GREEDY = SamplingParams(temperature=0.0, max_tokens=8)


def _drain_events(handle, timeout=10.0):
    """Collect every event on a handle up to (and including) its first
    terminal, then assert NO second terminal ever arrives — the
    exactly-one-terminal invariant every fault must preserve."""
    tokens, finals = [], []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            ev = handle._queue.get(timeout=0.1)
        except queue_mod.Empty:
            if finals:
                break
            continue
        if ev.token_id is not None:
            tokens.append(ev.token_id)
        if ev.is_final:
            finals.append(ev)
            # Grace window: a buggy double-finish would land right after.
            deadline = min(deadline, time.monotonic() + 0.2)
    assert len(finals) == 1, f"expected exactly one terminal, got {finals}"
    return tokens, finals[0]


def _tiny_engine(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("dtype", "float32")
    return InferenceEngine(get_config("test-tiny"), EngineConfig(**kw), seed=0)


def _mock_pair(plan0=None, reply="hello chaos"):
    """Two scripted workers; worker 0 (the deterministic first routing
    choice — least-loaded ties break by index) carries the fault."""
    w0 = MockEngine([Scenario(".", reply)], fault_plan=plan0)
    w1 = MockEngine([Scenario(".", reply)])
    return w0, w1


class TestWorkerDeath:
    def test_pre_token_death_resubmits_transparently(self):
        """Zero tokens emitted → the coordinator may resubmit without
        any observable duplication: the caller sees one clean STOP."""
        plan = FaultPlan(die_after_tokens=0, die_count=1)
        w0, w1 = _mock_pair(plan)
        coord = EngineCoordinator([w0, w1])
        h = coord.submit(TOK.encode("hi"), SP)
        tokens, fin = _drain_events(h)
        assert fin.finish_reason == FinishReason.STOP
        assert TOK.decode(tokens) == "hello chaos"
        assert plan.fired["deaths"] == 1
        # Reconciliation: one routed request, one resubmit, no shed.
        assert coord.metrics["routed"] == 1
        assert coord.metrics["resubmits"] == 1 == plan.fired["deaths"]
        assert coord.metrics["shed"] == 0

    def test_mid_stream_death_surfaces_partial_error(self):
        """≥1 token delivered → resubmitting would silently duplicate
        the prefix: the ERROR surfaces with the exact partial count."""
        plan = FaultPlan(die_after_tokens=3, die_count=1)
        w0, w1 = _mock_pair(plan)
        coord = EngineCoordinator([w0, w1])
        h = coord.submit(TOK.encode("hi"), SP)
        tokens, fin = _drain_events(h)
        assert fin.finish_reason == FinishReason.ERROR
        assert len(tokens) == 3 == fin.num_generated_tokens
        assert coord.metrics["resubmits"] == 0
        assert coord.metrics["routed"] == 1

    def test_validation_error_never_resubmits_or_downs_a_worker(self):
        """A deterministic request rejection (zero-token ERROR with no
        accepted-prompt marker) must surface as-is: resubmitting would
        recur identically on every worker, and a malformed-request
        stream must never smear healthy workers' reputations."""
        w0, w1 = _mock_pair()
        coord = EngineCoordinator([w0, w1])
        tokens, fin = _drain_events(coord.submit([], SP))  # empty prompt
        assert fin.finish_reason == FinishReason.ERROR
        assert "empty prompt" in fin.error
        assert tokens == []
        assert coord.metrics["resubmits"] == 0
        assert coord._healthy_indices() == [0, 1]

    def test_resubmit_budget_is_bounded(self):
        """Every worker dying pre-token exhausts the resubmit budget
        and ends in ONE honest ERROR, not an infinite relocation loop."""
        w0 = MockEngine([Scenario(".", "x")],
                        fault_plan=FaultPlan(die_after_tokens=0, die_count=10))
        w1 = MockEngine([Scenario(".", "x")],
                        fault_plan=FaultPlan(die_after_tokens=0, die_count=10))
        coord = EngineCoordinator([w0, w1], resubmit_retries=1)
        h = coord.submit(TOK.encode("hi"), SP)
        tokens, fin = _drain_events(h)
        assert fin.finish_reason == FinishReason.ERROR
        assert tokens == []
        assert coord.metrics["resubmits"] == 1


class TestFlakySubmit:
    def test_submit_exception_fails_over_with_backoff(self):
        plan = FaultPlan(flaky_submit=1)
        w0, w1 = _mock_pair(plan)
        coord = EngineCoordinator([w0, w1])
        h = coord.submit(TOK.encode("hi"), SP)
        tokens, fin = _drain_events(h)
        assert fin.finish_reason == FinishReason.STOP
        assert TOK.decode(tokens) == "hello chaos"
        assert plan.fired["submit_faults"] == 1
        assert coord.metrics["failovers"] == 1
        assert coord.metrics["routed"] == 1

    def test_flaky_worker_reinstates_after_cooldown(self):
        """Hysteresis round-trip: the submit failure downs the worker,
        the cooldown holds it out, then it reinstates and serves."""
        plan = FaultPlan(flaky_submit=1)
        w0, w1 = _mock_pair(plan)
        coord = EngineCoordinator(
            [w0, w1], probe_interval_s=0.0, health_cooldown_s=0.05
        )
        h = coord.submit(TOK.encode("hi"), SP)
        # The failover happened synchronously inside submit: w0 is down
        # the moment the call returns, before any cooldown can elapse.
        assert coord._healthy_indices() == [1]
        _drain_events(h)
        deadline = time.monotonic() + 5
        while coord._healthy_indices() != [0, 1]:
            assert time.monotonic() < deadline, "worker never reinstated"
            time.sleep(0.01)

    def test_every_submit_failing_is_honest_error(self):
        w0 = MockEngine(fault_plan=FaultPlan(flaky_submit=100))
        coord = EngineCoordinator([w0], submit_retries=2)
        tokens, fin = _drain_events(coord.submit(TOK.encode("hi"), SP))
        # The failures mark the only worker down → honest no-workers
        # terminal (not a raise, not silence).
        assert fin.finish_reason == FinishReason.ERROR
        assert tokens == []


class TestHangOnDispatch:
    def test_engine_watchdog_trips_fails_handles_and_recovers(self):
        """The real scheduler path: a hung chunk sync trips the
        watchdog at the bound, in-flight handles fail, recovery
        reallocates device state, and the engine serves again."""
        eng = _tiny_engine(watchdog_s=0.15, decode_chunk=2)
        eng._fault_plan = FaultPlan(hang_dispatch_s=1.0, hang_count=1)
        eng.start()
        try:
            h = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                     max_tokens=30))
            tokens, fin = _drain_events(h, timeout=20)
            assert fin.finish_reason == FinishReason.ERROR
            assert eng.metrics["watchdog_trips"] == 1
            assert eng.metrics["recoveries"] >= 1
            deadline = time.monotonic() + 5
            while not eng.healthy() and time.monotonic() < deadline:
                time.sleep(0.02)
            assert eng.healthy(), "engine did not recover after the trip"
            toks, fin = eng.submit(
                [1, 2, 3], SamplingParams(temperature=0.0, max_tokens=4)
            ).collect_tokens(timeout=30)
            assert fin.finish_reason == FinishReason.LENGTH and len(toks) == 4
            # Books balance across the incident: every accepted submit
            # reached exactly one finish (incl. the watchdog ERROR).
            assert (eng.metrics["requests_finished"]
                    == eng.metrics["requests_submitted"])
        finally:
            eng.stop()

    def test_mock_watchdog_parity_and_coordinator_resubmit(self):
        """A hung worker dispatch fails pre-token at the watchdog bound
        and the coordinator re-places the request elsewhere — client
        latency is bounded by watchdog_s + one resubmit, not the hang."""
        plan = FaultPlan(hang_dispatch_s=5.0, hang_count=1)
        w0 = MockEngine([Scenario(".", "ok")], fault_plan=plan,
                        watchdog_s=0.1)
        w1 = MockEngine([Scenario(".", "ok")])
        coord = EngineCoordinator([w0, w1])
        t0 = time.monotonic()
        tokens, fin = _drain_events(coord.submit(TOK.encode("hi"), SP))
        assert fin.finish_reason == FinishReason.STOP
        assert TOK.decode(tokens) == "ok"
        assert time.monotonic() - t0 < 3.0, "hang leaked into the client"
        assert w0.metrics["watchdog_trips"] == 1
        assert coord.metrics["resubmits"] == 1


class TestFullQueue:
    def test_engine_sheds_overloaded_beyond_max_queue(self):
        eng = _tiny_engine(max_queue=2)
        handles = [eng.submit([1, 2], GREEDY) for _ in range(4)]
        shed = [h for h in handles
                if not h._queue.empty()
                and h._queue.queue[0].finish_reason == FinishReason.OVERLOADED]
        assert len(shed) == 2
        assert eng.metrics["requests_shed"] == 2
        while eng.step():
            pass
        finals = [_drain_events(h)[1] for h in handles]
        reasons = sorted(f.finish_reason.value for f in finals)
        assert reasons == ["length", "length", "overloaded", "overloaded"]
        # Reconciliation: submitted == finished, shed is its own ledger.
        assert eng.metrics["requests_submitted"] == 2
        assert eng.metrics["requests_finished"] == 2

    def test_coordinator_sheds_before_routing_when_saturated(self):
        """Every healthy worker at the queue bound → OVERLOADED before
        any routing/affinity work happens."""
        w0 = MockEngine([Scenario(".", "slow reply here",
                                  delay_per_token_s=0.05)], max_queue=1)
        w1 = MockEngine([Scenario(".", "slow reply here",
                                  delay_per_token_s=0.05)], max_queue=1)
        coord = EngineCoordinator([w0, w1], max_worker_queue=1)
        h_a = coord.submit(TOK.encode("a"), SP)
        h_b = coord.submit(TOK.encode("b"), SP)
        deadline = time.monotonic() + 2
        while (w0.queue_depth() + w1.queue_depth()) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        tokens, fin = _drain_events(coord.submit(TOK.encode("c"), SP))
        assert fin.finish_reason == FinishReason.OVERLOADED
        assert tokens == []
        assert coord.metrics["shed"] == 1
        assert coord.metrics["routed"] == 2
        for h in (h_a, h_b):
            _, fin = _drain_events(h)
            assert fin.finish_reason == FinishReason.STOP


class TestDeadlines:
    def test_deadline_in_queue_sheds_deterministically(self):
        """Injected logical clock: the queued request's TTL expires
        between steps → DEADLINE shed, zero tokens, books balanced."""
        eng = _tiny_engine(num_slots=1)
        clock = [0.0]
        eng.clock = lambda: clock[0]
        # Occupy the only slot so the deadlined request stays queued.
        h_busy = eng.submit([1, 2], SamplingParams(temperature=0.0,
                                                   max_tokens=40))
        h_late = eng.submit([3, 4], GREEDY, deadline_s=5.0)
        eng.step()  # places h_busy; h_late waits
        clock[0] = 10.0  # TTL expires while queued
        while eng.step():
            pass
        tokens, fin = _drain_events(h_late)
        assert fin.finish_reason == FinishReason.DEADLINE
        assert tokens == []
        _, fin_busy = _drain_events(h_busy)
        assert fin_busy.finish_reason == FinishReason.LENGTH
        assert eng.metrics["deadline_exceeded"] == 1
        assert (eng.metrics["requests_finished"]
                == eng.metrics["requests_submitted"] == 2)

    def test_deadline_mid_decode_finishes_early_with_partial(self):
        eng = _tiny_engine()
        clock = [0.0]
        eng.clock = lambda: clock[0]
        h = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                 max_tokens=1000),
                       deadline_s=5.0)
        eng.step()  # prefill + first token
        eng.step()
        clock[0] = 10.0  # boundary passes mid-decode
        while eng.step():
            pass
        tokens, fin = _drain_events(h)
        assert fin.finish_reason == FinishReason.DEADLINE
        assert 1 <= len(tokens) < 1000
        assert fin.num_generated_tokens == len(tokens)
        assert eng.metrics["deadline_exceeded"] == 1

    def test_mock_deadline_mid_stream(self):
        w = MockEngine([Scenario(".", "0123456789" * 4,
                                 delay_per_token_s=0.02)])
        h = w.submit(TOK.encode("x"), SP, deadline_s=0.1)
        tokens, fin = _drain_events(h)
        assert fin.finish_reason == FinishReason.DEADLINE
        assert 0 < len(tokens) < 40
        assert fin.num_generated_tokens == len(tokens)
        assert w.metrics["deadline_exceeded"] == 1

    def test_coordinator_threads_deadline_to_worker(self):
        w = MockEngine([Scenario(".", "0123456789" * 4,
                                 delay_per_token_s=0.02)])
        coord = EngineCoordinator([w])
        tokens, fin = _drain_events(
            coord.submit(TOK.encode("x"), SP, deadline_s=0.1)
        )
        assert fin.finish_reason == FinishReason.DEADLINE
        assert w.metrics["deadline_exceeded"] == 1


class TestGracefulDrain:
    def test_drain_finishes_active_sheds_new_offloads_sessions(self):
        eng = _tiny_engine()
        eng.start()
        h = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                 max_tokens=6),
                       session_id="drain-s")
        eng.stop(drain=True)
        tokens, fin = _drain_events(h)
        assert fin.finish_reason == FinishReason.LENGTH
        assert len(tokens) == 6
        # Admission is closed...
        _, fin2 = _drain_events(eng.submit([1, 2], GREEDY))
        assert fin2.finish_reason == FinishReason.OVERLOADED
        assert eng.metrics["requests_shed"] == 1
        # ...and the idle session's rows were paged to host.
        assert eng.metrics["session_offloads"] == 1
        assert eng._sessions["drain-s"].host_k is not None

    def test_drain_timeout_still_delivers_terminals(self):
        """A drain window that elapses with work outstanding must not
        strand clients: queued requests shed (OVERLOADED), the active
        slot fails with its partial count, books balance."""
        eng = _tiny_engine(num_slots=1, decode_chunk=1)
        # Every read-back a little slow: the 55 steps to the cache's end
        # outlast the window by far, whatever the first token waited for
        # (it is read after the first decode step is dispatched, so no
        # compile stands between it and the drain any more).
        eng._fault_plan = FaultPlan(slow_sync_s=0.01)
        eng.start()
        sp_long = SamplingParams(temperature=0.0, max_tokens=100_000)
        h_active = eng.submit(list(range(1, 9)), sp_long)
        h_queued = eng.submit(list(range(1, 9)), sp_long)
        deadline = time.monotonic() + 10
        while h_active.first_token_at is None:
            assert time.monotonic() < deadline, "request never started"
            time.sleep(0.01)
        eng.stop(drain=True, drain_timeout_s=0.05)
        toks_a, fin_a = _drain_events(h_active, timeout=20)
        assert fin_a.finish_reason == FinishReason.ERROR
        assert fin_a.num_generated_tokens == len(toks_a) >= 1
        toks_q, fin_q = _drain_events(h_queued, timeout=20)
        assert fin_q.finish_reason == FinishReason.OVERLOADED
        assert toks_q == []
        assert (eng.metrics["requests_finished"]
                == eng.metrics["requests_submitted"] == 2)

    def test_drain_wait_covers_mid_placement_under_lock(self):
        """ISSUE 9 lock-discipline regression: the drain wait reads the
        ``_placing`` claim in the SAME critical section as the queue
        (lifecycle._drain_work_left) — the pre-fix unlocked read could
        end the drain while a request sat mid-placement in neither
        ledger. Simulate a stuck placement claim and assert the drain
        genuinely waits for it, then closes admission."""
        import threading

        eng = _tiny_engine()
        with eng._lock:
            eng._placing += 1
        released_at = []

        def releaser():
            time.sleep(0.15)
            with eng._lock:
                eng._placing -= 1
            released_at.append(time.monotonic())

        threading.Thread(target=releaser, daemon=True).start()
        t0 = time.monotonic()
        eng.stop(drain=True, drain_timeout_s=5.0)
        assert released_at, "drain returned before the claim released"
        assert time.monotonic() - t0 >= 0.14
        # Draining flag was flipped under the lock; admission is closed.
        _, fin = _drain_events(eng.submit([1, 2], GREEDY))
        assert fin.finish_reason == FinishReason.OVERLOADED

    def test_restart_after_drain_reopens_admission(self):
        eng = _tiny_engine()
        eng.start()
        eng.stop(drain=True)
        eng.start()
        try:
            toks, fin = eng.submit([1, 2], GREEDY).collect_tokens(timeout=30)
            assert fin.finish_reason == FinishReason.LENGTH
        finally:
            eng.stop()


class TestLockstepReplication:
    def test_submit_event_carries_deadline_and_applies_it(self):
        """Deadline decisions replicate as events (like register_prefix):
        the TTL rides the submit event frame, and applying the event
        threads it into the engine's submit — so every rank anchors the
        same deadline to the same broadcast logical clock."""
        import json

        from omnia_tpu.engine.multihost import LockstepEngine

        inner = MockEngine([Scenario(".", "0123456789" * 4,
                                     delay_per_token_s=0.02)])
        lock = LockstepEngine(inner)
        h = lock.submit(TOK.encode("x"), SP, deadline_s=0.1)
        raws = lock._drain_pending()
        ev = json.loads(raws[0])
        assert ev["op"] == "submit" and ev["deadline_s"] == 0.1
        # Apply the event the way every rank's tick loop would; the
        # leader wrapper binds and the TTL reaps mid-stream.
        lock._apply(ev)
        tokens, fin = _drain_events(h)
        assert fin.finish_reason == FinishReason.DEADLINE
        assert inner.metrics["deadline_exceeded"] == 1
        assert fin.num_generated_tokens == len(tokens)


class TestReconciliation:
    def test_fault_battery_books_balance_exactly(self):
        """A battery across every mock-expressible fault: N submits in,
        N terminal events out, and the coordinator's routed/shed/
        resubmit/failover ledger explains every one of them."""
        plan = FaultPlan(die_after_tokens=0, die_count=2, flaky_submit=1)
        w0 = MockEngine([Scenario(".", "abc")], fault_plan=plan, max_queue=64)
        w1 = MockEngine([Scenario(".", "abc")], max_queue=64)
        coord = EngineCoordinator([w0, w1], max_worker_queue=64)
        finals = []
        for i in range(12):
            h = coord.submit(TOK.encode(f"r{i}"), SP,
                             session_id=f"sess-{i % 3}")
            finals.append(_drain_events(h)[1])
        assert len(finals) == 12  # exactly one terminal each
        clean = sum(f.finish_reason in (FinishReason.STOP,
                                        FinishReason.LENGTH) for f in finals)
        assert clean == 12  # every fault was absorbed: death resubmitted,
        # flaky submit failed over — the caller never saw one
        assert coord.metrics["routed"] == 12
        assert coord.metrics["shed"] == 0
        assert coord.metrics["resubmits"] == plan.fired["deaths"] == 2
        assert coord.metrics["failovers"] >= plan.fired["submit_faults"] == 1
        # Worker-side books also balance: every accepted submit finished.
        for w in (w0, w1):
            assert (w.metrics["requests_finished"]
                    == w.metrics["requests_submitted"])
