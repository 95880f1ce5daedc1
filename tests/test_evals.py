"""Eval plane tests: checks, partitioner, queue semantics, direct and
fleet workers, judge/sampling/budget, aggregation+thresholds, realtime
worker, and the arena job lifecycle."""

from __future__ import annotations

import json
import threading
import time

import pytest

from omnia_tpu.evals import (
    Aggregator,
    ArenaJobController,
    ArenaJobSpec,
    ArenaQueue,
    ArenaWorker,
    BudgetExceeded,
    BudgetTracker,
    Check,
    CostCalculator,
    DirectRunner,
    EvalScenario,
    FleetRunner,
    JobPhase,
    Judge,
    RealtimeEvalWorker,
    Sampler,
    ScenarioTurn,
    Threshold,
    WorkItem,
    WorkResult,
    partition,
)
from omnia_tpu.runtime.packs import load_pack
from omnia_tpu.runtime.providers import ProviderRegistry, ProviderSpec
from omnia_tpu.streams import Stream

PACK = {
    "name": "eval-agent",
    "version": "1.0.0",
    "prompts": {"system": "You are a support agent."},
    "sampling": {"temperature": 0.0, "max_tokens": 256},
}


def _registry(extra_scenarios=()):
    reg = ProviderRegistry()
    for name, scenarios in (
        ("good", [{"pattern": "refund", "reply": "you can get a refund within 30 days"},
                  {"pattern": ".", "reply": "happy to help"}, *extra_scenarios]),
        ("bad", [{"pattern": ".", "reply": "I cannot help with that"}]),
    ):
        reg.register(ProviderSpec(name=name, type="mock", options={"scenarios": list(scenarios)}))
    return reg


def _spec(providers=("good", "bad"), repeats=1, threshold=None):
    return ArenaJobSpec(
        name="job1",
        scenarios=[
            EvalScenario(
                name="refund-policy",
                turns=[
                    ScenarioTurn(
                        user="how do refunds work?",
                        checks=[Check(kind="contains", value="refund"),
                                Check(kind="not_contains", value="I cannot")],
                    )
                ],
            )
        ],
        providers=list(providers),
        repeats=repeats,
        threshold=threshold or Threshold(min_pass_rate=1.0),
    )


class TestChecks:
    def test_assertion_kinds(self):
        assert Check(kind="contains", value="Refund").evaluate_sync("a refund here", 0.1)
        assert not Check(kind="not_contains", value="cannot").evaluate_sync("I cannot", 0.1)
        assert Check(kind="regex", value=r"\d+ days").evaluate_sync("30 days", 0.1)
        assert Check(kind="max_latency_s", value=1.0).evaluate_sync("x", 0.5)
        assert not Check(kind="max_latency_s", value=1.0).evaluate_sync("x", 1.5)
        assert Check(kind="judge", rubric="r").evaluate_sync("x", 0.1) is None
        with pytest.raises(ValueError):
            Check(kind="nope").evaluate_sync("x", 0.1)


class TestPartitioner:
    def test_matrix_expansion_interleaves_providers(self):
        spec = _spec(repeats=2)
        items = partition(spec)
        assert len(items) == 1 * 2 * 2  # scenarios × providers × repeats
        assert [i.provider for i in items[:2]] == ["good", "bad"]
        assert all(i.job == "job1" for i in items)


class TestQueue:
    def test_ack_after_publish_and_reclaim(self):
        q = ArenaQueue()
        q.enqueue(partition(_spec()))
        assert q.depth() == 2
        eid, item = q.next("w1")
        assert item.provider == "good"
        # w1 crashes (no ack); w2 reclaims after idle
        claimed = q.reclaim("w2", idle_s=0.0)
        assert [i.id for _, i in claimed] == [item.id]
        q.ack(claimed[0][0])
        assert q.depth() == 1

    def test_poison_item_dead_letters_with_error_result(self):
        q = ArenaQueue(max_deliveries=2)
        q.enqueue([WorkItem(job="j", scenario={"name": "s"}, provider="p")])
        q.next("w1")
        for _ in range(3):
            q.reclaim("w2", idle_s=0.0)
        assert len(q.dead_letters) == 1
        assert q.depth() == 0  # dead-lettered items leave the backlog
        # an error result is published so the job can still finalize
        results = q.consume_results()
        assert len(results) == 1
        assert "dead-lettered" in results[0].error
        assert results[0].job == "j" and results[0].scenario == "s"

    def test_dead_lettered_job_still_finalizes(self):
        ctrl = ArenaJobController(ArenaQueue(max_deliveries=1))
        ctrl.submit(_spec(providers=("good",)))
        eid, item = ctrl.queue.next("w1")  # w1 "crashes"
        ctrl.queue.reclaim("w2", idle_s=0.0)
        ctrl.queue.reclaim("w2", idle_s=0.0)  # exceeds max_deliveries
        status = ctrl.reconcile("job1")
        assert status.phase == JobPhase.FAILED  # not stuck Running


class TestDirectWorker:
    def test_drain_and_aggregate(self):
        q = ArenaQueue()
        q.enqueue(partition(_spec()))
        runner = DirectRunner(load_pack(PACK), _registry())
        worker = ArenaWorker(q, runner, cost_calculator=CostCalculator(0, 2.0))
        n = worker.run_until_empty()
        assert n == 2
        agg = Aggregator()
        for r in q.consume_results():
            agg.add(r)
        verdict = agg.evaluate(Threshold(min_pass_rate=1.0))
        assert not verdict["passed"]  # 'bad' provider fails
        cells = {(c["provider"]): c for c in verdict["cells"]}
        assert cells["good"]["pass_rate"] == 1.0
        assert cells["bad"]["pass_rate"] == 0.0
        assert cells["good"]["cost_usd"] > 0

    def test_multi_turn_scenario_keeps_history(self):
        spec = ArenaJobSpec(
            name="multi",
            scenarios=[EvalScenario(name="s", turns=[
                ScenarioTurn(user="remember the code word is otter"),
                ScenarioTurn(user="what is the code word?",
                             checks=[Check(kind="contains", value="otter")]),
            ])],
            providers=["echoer"],
        )
        reg = ProviderRegistry()
        reg.register(ProviderSpec(name="echoer", type="mock", options={"scenarios": [
            {"pattern": r"otter.*what is the code word", "reply": "the code word is otter",
             "match": "prompt"},  # deliberately asserts history retention
            {"pattern": ".", "reply": "ok"}]}))
        q = ArenaQueue()
        q.enqueue(partition(spec))
        ArenaWorker(q, DirectRunner(load_pack(PACK), reg)).run_until_empty()
        results = q.consume_results()
        assert results[0].passed, results[0]

    def test_budget_stops_worker(self):
        q = ArenaQueue()
        q.enqueue(partition(_spec(repeats=50)))
        runner = DirectRunner(load_pack(PACK), _registry())
        budget = BudgetTracker(max_tokens=30)
        worker = ArenaWorker(q, runner, budget=budget)
        n = worker.run_until_empty()
        assert n < 100  # stopped early
        assert q.depth() > 0  # remaining work left for other workers


class TestJudge:
    def _judge(self, reply):
        return Judge(lambda prompt: reply)

    def test_parses_json_verdict(self):
        v = self._judge('{"score": 0.9, "reason": "polite"}').score("r", "u", "a")
        assert v.score == 0.9 and v.reason == "polite"

    def test_unparseable_fails_safe(self):
        v = self._judge("garbage").score("r", "u", "a")
        assert v.score == 0.0

    def test_score_clamped(self):
        assert self._judge('{"score": 7}').score("r", "u", "a").score == 1.0

    def test_judge_check_in_worker(self):
        spec = ArenaJobSpec(
            name="judged",
            scenarios=[EvalScenario(name="s", turns=[
                ScenarioTurn(user="hi", checks=[
                    Check(kind="judge", rubric="is helpful", min_score=0.5, name="helpful")])])],
            providers=["good"],
        )
        q = ArenaQueue()
        q.enqueue(partition(spec))
        worker = ArenaWorker(
            q, DirectRunner(load_pack(PACK), _registry()),
            judge=Judge(lambda p: '{"score": 0.8, "reason": "ok"}'),
        )
        worker.run_until_empty()
        r = q.consume_results()[0]
        assert r.passed and r.checks[0].score == 0.8

    def test_sampler_rate_and_cap(self):
        s = Sampler(rate=1.0, per_session_cap=2)
        assert s.should_sample("a") and s.should_sample("a")
        assert not s.should_sample("a")  # capped
        assert s.should_sample("b")
        never = Sampler(rate=0.0)
        assert not never.should_sample("x")

    def test_budget_tracker(self):
        b = BudgetTracker(max_cost_usd=1.0)
        b.charge(cost_usd=0.6)
        with pytest.raises(BudgetExceeded):
            b.charge(cost_usd=0.6)
        assert not b.exhausted
        b.charge(cost_usd=0.4)
        assert b.exhausted


class TestAggregator:
    def test_threshold_latency_gate(self):
        agg = Aggregator()
        for i, lat in enumerate((0.1, 0.2, 5.0)):
            agg.add(WorkResult(work_id=f"w{i}", job="j", scenario="s", provider="p",
                               repeat=0, latency_s=lat))
        out = agg.evaluate(Threshold(min_pass_rate=1.0, max_p95_latency_s=1.0))
        assert not out["passed"]
        assert any("p95" in f for f in out["failures"])


class TestRealtime:
    def test_judges_sampled_assistant_events(self):
        events = Stream()
        published = []
        prompts = []

        def complete(p):
            prompts.append(p)
            return '{"score": 1.0, "reason": "fine"}'

        worker = RealtimeEvalWorker(
            events,
            judge=Judge(complete),
            rubrics=[{"name": "tone", "rubric": "polite", "min_score": 0.5}],
            publish=published.append,
        )
        # real session-api event shape: separate user/assistant message
        # records, no in_reply_to field
        events.add({"type": "message", "session_id": "s1",
                    "payload": {"role": "user", "content": "what is the sla?"}})
        events.add({"type": "message", "session_id": "s1",
                    "payload": {"role": "assistant", "content": "99.9% uptime"}})
        events.add({"type": "session_ensured", "session_id": "s1", "payload": {}})
        worker.run_once()
        assert len(published) == 1
        assert published[0]["name"] == "tone" and published[0]["passed"]
        assert published[0]["source"] == "realtime"
        # the judge prompt pairs the assistant reply with the user question
        assert "what is the sla?" in prompts[0]
        assert "99.9% uptime" in prompts[0]

    def test_bad_event_never_wedges_loop(self):
        events = Stream()
        calls = []

        def explode(prompt):
            calls.append(prompt)
            raise RuntimeError("judge down")

        worker = RealtimeEvalWorker(
            events, judge=Judge(explode),
            rubrics=[{"name": "r", "rubric": "x"}], publish=lambda d: None,
        )
        events.add({"type": "message", "session_id": "s",
                    "payload": {"role": "assistant", "content": "a"}})
        events.add({"type": "message", "session_id": "s",
                    "payload": {"role": "assistant", "content": "b"}})
        assert worker.run_once() == 2  # both acked despite judge failure
        assert len(events.pending("eval-workers")) == 0


class TestArenaJob:
    def test_full_lifecycle_with_worker_pool(self):
        ctrl = ArenaJobController()
        spec = _spec(providers=("good",), repeats=3,
                     threshold=Threshold(min_pass_rate=1.0))
        status = ctrl.submit(spec)
        assert status.phase == JobPhase.RUNNING and status.total == 3
        runner = DirectRunner(load_pack(PACK), _registry())
        workers = [ArenaWorker(ctrl.queue, runner, name=f"w{i}") for i in range(2)]
        threads = [threading.Thread(target=w.run_until_empty) for w in workers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            status = ctrl.reconcile("job1")
            if status.phase != JobPhase.RUNNING:
                break
            time.sleep(0.05)
        assert status.phase == JobPhase.SUCCEEDED, status.to_dict()
        assert status.completed == 3
        assert status.verdict["passed"]

    def test_failing_threshold_fails_job(self):
        ctrl = ArenaJobController()
        ctrl.submit(_spec(providers=("bad",)))
        ArenaWorker(ctrl.queue, DirectRunner(load_pack(PACK), _registry())).run_until_empty()
        status = ctrl.reconcile("job1")
        assert status.phase == JobPhase.FAILED


class TestFleetMode:
    def test_fleet_runner_against_live_facade(self):
        from omnia_tpu.facade.server import FacadeServer
        from omnia_tpu.runtime.server import RuntimeServer

        reg = _registry()
        runtime = RuntimeServer(pack=load_pack(PACK), providers=reg, provider_name="good")
        rport = runtime.serve("localhost:0")
        facade = FacadeServer(runtime_target=f"localhost:{rport}", agent_name="eval-agent")
        fport = facade.serve()
        try:
            spec = _spec(providers=("eval-agent",))
            spec.mode = "fleet"
            q = ArenaQueue()
            q.enqueue(partition(spec))
            runner = FleetRunner(lambda agent: f"ws://localhost:{fport}/ws")
            worker = ArenaWorker(q, runner)
            assert worker.run_until_empty() == 1
            r = q.consume_results()[0]
            assert r.passed, r
            assert r.tokens > 0
        finally:
            facade.shutdown()
            runtime.shutdown()


class TestFleetLoad:
    def test_64_concurrent_vus_within_slo(self):
        """BASELINE config 3 as a TEST (VERDICT r4 #4): 64 virtual users
        drive a live facade (mock engine) concurrently through the VU
        pool; every scenario completes, per-turn latency histograms land
        in WorkResults, and p50/p95 are measured, finite and in order.
        No absolute latency is judged: on a machine shared with the
        suite's other workers a mock turn read 5,000 ms in whole runs and
        100 ms alone, and the pool's own ``timeout_s`` bounds the case."""
        from omnia_tpu.facade.auth import AuthChain, HmacValidator
        from omnia_tpu.facade.server import FacadeServer
        from omnia_tpu.runtime.server import RuntimeServer

        secret = b"fleet-load-secret"
        reg = _registry()
        runtime = RuntimeServer(pack=load_pack(PACK), providers=reg,
                                provider_name="good")
        rport = runtime.serve("localhost:0")
        # Authenticated facade: each VU is a DISTINCT virtual user with
        # its own rate-limit bucket — unauthenticated, all 64 share one
        # per-address bucket and the facade correctly 4429s the flood.
        facade = FacadeServer(runtime_target=f"localhost:{rport}",
                              agent_name="eval-agent",
                              auth_chain=AuthChain([HmacValidator(secret)]))
        fport = facade.serve()
        try:
            spec = _spec(providers=("eval-agent",), repeats=64)
            spec.mode = "fleet"
            q = ArenaQueue()
            n_items = q.enqueue(partition(spec))
            assert n_items == 64
            runner = FleetRunner(
                lambda agent: f"ws://localhost:{fport}/ws",
                token_for=lambda sid: HmacValidator.mint(
                    secret, subject=f"vu-{sid}"),
            )
            worker = ArenaWorker(q, runner)
            stats = worker.run_fleet(concurrency=64, ramp_up_s=0.2,
                                     timeout_s=120.0)
            assert stats["executed"] == 64, stats
            assert stats["errors"] == 0, stats
            # the pool genuinely ran many users at once (not serialized)
            assert stats["max_active"] >= 8, stats
            lat = stats["latency"]
            assert lat["count"] == 64
            # the point is the MEASUREMENT machinery, not the number
            assert 0 < lat["p50_ms"] <= lat["p95_ms"] < float("inf"), lat
            results = q.consume_results(count=200)
            assert len(results) == 64
            assert all(r.passed for r in results)
            assert all(r.turn_latency_ms and r.latency_hist["count"] >= 1
                       for r in results)
        finally:
            facade.shutdown()
            runtime.shutdown()

    def test_fleet_budget_stops_pool_and_leaves_items_reclaimable(self):
        """Budget exhaustion mid-fleet stops the WHOLE pool (same
        contract as the direct loop): no bogus error results, remaining
        items stay claimable by a post-budget worker."""
        q = ArenaQueue()
        q.enqueue(partition(_spec(providers=("good",), repeats=40)))
        runner = DirectRunner(load_pack(PACK), _registry())
        worker = ArenaWorker(q, runner, budget=BudgetTracker(max_tokens=25))
        stats = worker.run_fleet(concurrency=8, timeout_s=60.0)
        assert stats["executed"] < 40
        results = q.consume_results(count=100)
        assert all(not r.error for r in results)  # no budget-as-error
        assert q.depth() > 0  # unfinished work remains claimable

    def test_load_profile_ramp(self):
        from omnia_tpu.evals.vu_pool import LoadProfile

        lp = LoadProfile(10, ramp_up_s=10.0)
        lp.start()
        lp._started_at -= 5.0  # halfway through the ramp
        assert lp.allowed() == 5
        lp._started_at -= 10.0  # past the ramp
        assert lp.allowed() == 10
        # pending-aware ramp-down, but full allowance at drain (pending=0)
        assert lp.allowed(pending=3) == 3
        assert lp.allowed(pending=0) == 10

    def test_latency_histogram_percentiles(self):
        from omnia_tpu.evals.vu_pool import LatencyHistogram

        h = LatencyHistogram()
        for ms in (4, 8, 20, 40, 90, 200, 400, 900, 2000, 4000):
            h.record(ms)
        assert h.total == 10
        assert h.percentile(50) in (50.0, 100.0)
        assert h.percentile(95) >= 2500.0
        # round-trip through the WorkResult dict form
        h2 = LatencyHistogram.from_dict(h.to_dict())
        assert h2.to_dict() == h.to_dict()
        merged = LatencyHistogram()
        merged.merge(h2)
        merged.merge(h2)
        assert merged.total == 20


class TestSelfPlayCapture:
    def test_capture_replays_as_pinned_scenarios(self, tmp_path):
        """Fleet self-play (reference selfplay_capture.go): live turns
        become scenarios whose checks pin the observed replies — and the
        captured scenarios PASS when replayed against the same agent."""
        from omnia_tpu.evals.selfplay import SelfPlayCapture

        runner = DirectRunner(load_pack(PACK), _registry())
        capture = SelfPlayCapture(runner)
        q = ArenaQueue()
        q.enqueue(partition(_spec(providers=("good",), repeats=2)))
        worker = ArenaWorker(q, capture)
        assert worker.run_until_empty() == 2
        # transcripts recorded per session
        ts = capture.transcripts()
        assert len(ts) == 2
        assert all(t[0]["reply"] for t in ts.values())
        # captured → scenario docs with contains checks
        path = str(tmp_path / "selfplay.json")
        n = capture.save(path)
        assert n == 2
        doc = json.loads(open(path).read())
        chk = doc["scenarios"][0]["turns"][0]["checks"][0]
        assert chk["kind"] == "contains" and "refund" in chk["value"]
        # replay the captured scenarios against the same agent: all pass
        spec2 = ArenaJobSpec(
            name="replay", providers=["good"],
            scenarios=[EvalScenario.from_dict(s) for s in doc["scenarios"]],
            threshold=Threshold(min_pass_rate=1.0),
        )
        q2 = ArenaQueue()
        q2.enqueue(partition(spec2))
        ArenaWorker(q2, DirectRunner(load_pack(PACK), _registry())).run_until_empty()
        agg = Aggregator()
        for r in q2.consume_results():
            agg.add(r)
        verdict = agg.evaluate(Threshold(min_pass_rate=1.0))
        assert verdict["passed"], verdict


class TestAtLeastOnceDedup:
    def test_duplicate_results_do_not_skew_job(self):
        ctrl = ArenaJobController()
        ctrl.submit(_spec(providers=("good",)))
        worker = ArenaWorker(ctrl.queue, DirectRunner(load_pack(PACK), _registry()))
        worker.run_until_empty()
        # simulate at-least-once double delivery of the same result
        results = ctrl.queue.consume_results()
        for r in results:
            ctrl.queue.publish_result(r)
            ctrl.queue.publish_result(r)
        status = ctrl.reconcile("job1")
        assert status.completed == 1  # deduped on work_id
        assert status.phase == JobPhase.SUCCEEDED
        assert status.verdict["cells"][0]["runs"] == 1

    def test_two_realtime_workers_still_pair_user_messages(self):
        events = Stream()
        prompts = []

        def complete(p):
            prompts.append(p)
            return '{"score": 1.0}'

        published = []
        w1 = RealtimeEvalWorker(events, judge=Judge(complete),
                                rubrics=[{"name": "r", "rubric": "x"}],
                                publish=published.append, name="w1")
        w2 = RealtimeEvalWorker(events, judge=Judge(complete),
                                rubrics=[{"name": "r", "rubric": "x"}],
                                publish=published.append, name="w2")
        # w1 consumes the user record from the shared group; the assistant
        # record lands on w2 — pairing must still work via broadcast groups
        events.add({"type": "message", "session_id": "s1",
                    "payload": {"role": "user", "content": "the question"}})
        w1.run_once()
        events.add({"type": "message", "session_id": "s1",
                    "payload": {"role": "assistant", "content": "the answer"}})
        w2.run_once()
        assert len(published) == 1
        assert any("the question" in p and "the answer" in p for p in prompts), prompts
