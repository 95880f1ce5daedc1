"""Sessionful serving: cross-turn KV reuse, chunked extend, host paging.

The correctness bar everywhere: a turn served with prefix reuse must
produce EXACTLY the tokens a fresh engine produces for the same full
prompt (greedy), no matter how the KV got there — resident rows, a
restore from host, or a divergence-triggered rebuild.
"""

import jax
import numpy as np
import pytest

from omnia_tpu.engine import (
    EngineConfig,
    FinishReason,
    InferenceEngine,
    SamplingParams,
)
from omnia_tpu.models import get_config

GREEDY = SamplingParams(temperature=0.0, max_tokens=6)


def _engine(num_slots=2, max_seq=64, max_sessions=64, **kw):
    return InferenceEngine(
        get_config("test-tiny"),
        EngineConfig(
            num_slots=num_slots, max_seq=max_seq, prefill_buckets=(8, 16),
            dtype="float32", max_sessions=max_sessions, **kw,
        ),
        seed=0,
    )


def _turn(eng, prompt, sid=None, sp=GREEDY):
    handle = eng.submit(prompt, sp, session_id=sid)
    if eng._thread is None:
        toks = []
        while True:
            eng.step()
            import queue as q

            try:
                while True:
                    ev = handle._queue.get_nowait()
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                    if ev.is_final:
                        return toks, ev
            except q.Empty:
                pass
    return handle.collect_tokens(timeout=60)


class TestPrefixReuse:
    def test_turn2_cost_is_new_tokens_only(self):
        """The multi-turn contract: turn 2 prefills O(new tokens) — its
        extend covers only the suffix past the reused prefix."""
        eng = _engine()
        p1 = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
        t1, _ = _turn(eng, p1, sid="s")
        # Turn 2 prompt = turn 1 prompt + the assistant tokens + new user text.
        p2 = p1 + t1 + [11, 12, 13]
        reuse_before = eng.metrics["prefix_reuse_tokens"]
        t2, fin = _turn(eng, p2, sid="s")
        assert fin.finish_reason == FinishReason.LENGTH
        reused = eng.metrics["prefix_reuse_tokens"] - reuse_before
        # Conservative validity drops the last emitted token; everything
        # else of turn 1 must be reused.
        assert reused >= len(p1) + len(t1) - 2
        assert eng.metrics["extend_steps"] >= 1

    def test_reused_turn_matches_fresh_engine(self):
        """Gold equivalence: same greedy tokens with and without reuse."""
        eng = _engine()
        p1 = [1, 2, 3, 4, 5, 6, 7, 8]
        t1, _ = _turn(eng, p1, sid="s")
        p2 = p1 + t1 + [20, 21, 22]
        t2, _ = _turn(eng, p2, sid="s")

        fresh = _engine()
        t2_fresh, _ = _turn(fresh, p2)
        assert t2 == t2_fresh

    def test_divergent_history_rebuilds(self):
        eng = _engine()
        p1 = [1, 2, 3, 4, 5, 6, 7, 8]
        _turn(eng, p1, sid="s")
        # Same session, completely different prompt (e.g. post-compaction).
        p2 = [40, 41, 42, 43]
        reuse_before = eng.metrics["prefix_reuse_tokens"]
        t2, _ = _turn(eng, p2, sid="s")
        assert eng.metrics["prefix_reuse_tokens"] == reuse_before  # no reuse
        fresh = _engine()
        t2_fresh, _ = _turn(fresh, p2)
        assert t2 == t2_fresh

    def test_sessionless_requests_unaffected(self):
        eng = _engine()
        p = [1, 2, 3, 4]
        a, _ = _turn(eng, p)
        b, _ = _turn(eng, p)
        assert a == b
        assert eng.metrics["extend_steps"] == 0

    def test_release_session_forgets_rows(self):
        eng = _engine()
        p1 = [1, 2, 3, 4, 5, 6, 7, 8]
        t1, _ = _turn(eng, p1, sid="s")
        eng.release_session("s")
        p2 = p1 + t1 + [20]
        reuse_before = eng.metrics["prefix_reuse_tokens"]
        _turn(eng, p2, sid="s")
        assert eng.metrics["prefix_reuse_tokens"] == reuse_before


class TestHostPaging:
    def test_sessions_beyond_slots_page_to_host(self):
        """More logical sessions than slots: idle sessions offload to host
        and restore on their next turn, with exact results."""
        eng = _engine(num_slots=2)
        prompts = {f"s{i}": [10 + i, 11 + i, 12 + i, 13 + i, 14 + i] for i in range(6)}
        turn1 = {}
        for sid, p in prompts.items():
            turn1[sid], _ = _turn(eng, p, sid=sid)
        assert eng.metrics["session_offloads"] >= 4  # 6 sessions, 2 slots
        # Second turn on the OLDEST session — certainly paged out by now.
        sid = "s0"
        p2 = prompts[sid] + turn1[sid] + [99, 98]
        t2, _ = _turn(eng, p2, sid=sid)
        assert eng.metrics["session_restores"] >= 1
        fresh = _engine()
        t2_fresh, _ = _turn(fresh, p2)
        assert t2 == t2_fresh

    def test_64_sessions_on_4_slots(self):
        """BASELINE config 3 shape: 64 logical sessions on a small fixed
        device cache, every turn correct."""
        eng = _engine(num_slots=4, max_sessions=64)
        rng = np.random.default_rng(0)
        prompts = {
            f"u{i}": [int(x) for x in rng.integers(1, 200, size=6)] for i in range(64)
        }
        replies = {}
        for sid, p in prompts.items():
            replies[sid], _ = _turn(
                eng, p, sid=sid, sp=SamplingParams(temperature=0.0, max_tokens=3)
            )
        assert len(eng._sessions) == 64
        # Turn 2 on a spread of sessions, each checked against a fresh engine.
        fresh = _engine(num_slots=4)
        for sid in ("u0", "u31", "u63"):
            p2 = prompts[sid] + replies[sid] + [7, 8, 9]
            t2, _ = _turn(eng, p2, sid=sid, sp=SamplingParams(temperature=0.0, max_tokens=3))
            t2_fresh, _ = _turn(fresh, p2, sp=SamplingParams(temperature=0.0, max_tokens=3))
            assert t2 == t2_fresh, sid

    def test_session_cap_drops_lru(self):
        eng = _engine(num_slots=2, max_sessions=3)
        for i in range(5):
            _turn(eng, [10 + i, 11 + i, 12 + i], sid=f"s{i}")
        assert len(eng._sessions) <= 3
        assert "s4" in eng._sessions  # newest kept


class TestChunkedExtend:
    def test_long_suffix_multi_chunk(self):
        """A suffix longer than the largest bucket extends in pieces."""
        eng = _engine(max_seq=64)
        p1 = [1, 2, 3, 4]
        t1, _ = _turn(eng, p1, sid="s", sp=SamplingParams(temperature=0.0, max_tokens=2))
        suffix = list(range(50, 50 + 30))  # 30 > largest bucket 16
        p2 = p1 + t1 + suffix
        t2, _ = _turn(eng, p2, sid="s")
        fresh = _engine(max_seq=64)
        t2_fresh, _ = _turn(fresh, p2)
        assert t2 == t2_fresh

    def test_extend_near_cache_end_single_steps(self):
        """Near max_seq the padded bucket write would cross the cache end
        (clamped writes corrupt earlier rows) — single-token steps instead."""
        eng = _engine(max_seq=32)
        p1 = list(range(1, 17))  # 16 rows
        t1, _ = _turn(eng, p1, sid="s", sp=SamplingParams(temperature=0.0, max_tokens=2))
        p2 = p1 + t1 + list(range(60, 60 + 10))  # lands in the 25..30 range
        t2, fin = _turn(eng, p2, sid="s", sp=SamplingParams(temperature=0.0, max_tokens=2))
        fresh = _engine(max_seq=32)
        t2_fresh, _ = _turn(fresh, p2, sp=SamplingParams(temperature=0.0, max_tokens=2))
        assert t2 == t2_fresh


class TestSessionsOnMesh:
    def test_sessionful_engine_on_dp_tp_mesh(self):
        """The serving engine itself on a dp×tp mesh (VERDICT weak #3):
        submit→stream with KV reuse and host paging under GSPMD."""
        cfg = get_config("test-tiny")
        eng = InferenceEngine(
            cfg,
            EngineConfig(
                num_slots=4, max_seq=64, prefill_buckets=(8, 16),
                dtype="float32", dp=2, tp=2, max_sessions=8,
            ),
            seed=0,
            devices=jax.devices()[:4],
        )
        p1 = [1, 2, 3, 4, 5, 6]
        t1, _ = _turn(eng, p1, sid="m")
        p2 = p1 + t1 + [30, 31]
        t2, _ = _turn(eng, p2, sid="m")
        fresh = _engine(num_slots=2)
        t1f, _ = _turn(fresh, p1)
        assert t1 == t1f
        t2f, _ = _turn(fresh, p2)
        assert t2 == t2f

    def test_mesh_equals_single_device(self):
        """Sharded and unsharded engines produce identical greedy tokens."""
        cfg = get_config("test-tiny")
        mesh_eng = InferenceEngine(
            cfg,
            EngineConfig(
                num_slots=4, max_seq=64, prefill_buckets=(8, 16),
                dtype="float32", dp=2, tp=2,
            ),
            seed=0,
            devices=jax.devices()[:4],
        )
        single = _engine(num_slots=4)
        for p in ([1, 2, 3], [5, 6, 7, 8, 9, 10, 11, 12, 13]):
            a, _ = _turn(mesh_eng, p)
            b, _ = _turn(single, p)
            assert a == b, p


class TestWarmupCoversSessionPrograms:
    def test_no_compiles_after_warmup(self):
        """Extend/offload/restore must all be AOT-compiled by warmup: a
        sessionful turn sequence right after warmup triggers zero new
        compilations (the TTFT discipline)."""
        eng = _engine(num_slots=2, max_seq=64)
        eng.warmup()
        import jax as _jax

        with _jax.log_compiles():
            import io
            import logging as _logging

            stream = io.StringIO()
            handler = _logging.StreamHandler(stream)
            # The whole "jax" tree: this JAX logs "Compiling jit(...)"
            # from interpreters.pxla and "Finished XLA compilation" from
            # dispatch; listening on one module alone hears nothing.
            logger = _logging.getLogger("jax")
            logger.addHandler(handler)
            try:
                p1 = [1, 2, 3, 4, 5]
                t1, _ = _turn(eng, p1, sid="w")
                p2 = p1 + t1 + [9, 9, 9]
                _turn(eng, p2, sid="w")
                # force paging both ways
                _turn(eng, [4, 5, 6], sid="w2")
                _turn(eng, [5, 6, 7], sid="w3")
                _turn(eng, p2 + [1], sid="w")
            finally:
                logger.removeHandler(handler)
            logged = stream.getvalue()
        assert "Compiling" not in logged, logged
        assert "Finished XLA compilation" not in logged, logged


class TestLongContextServing:
    """sp axis: long prompts prefill via ring attention (VERDICT weak #8 —
    ring attention wired into the serving path, not a standalone demo)."""

    def _eng(self, sp, thresh=16):
        return InferenceEngine(
            get_config("test-tiny"),
            EngineConfig(
                num_slots=2, max_seq=64, prefill_buckets=(8, 32),
                dtype="float32", dp=1, tp=2, sp=sp,
                long_prefill_threshold=thresh,
            ),
            seed=0,
        )

    def test_ring_prefill_matches_dense_engine(self):
        long_prompt = [int(x) for x in
                       np.random.default_rng(0).integers(1, 200, size=20)]
        want, _ = self._eng(sp=1).generate(long_prompt, GREEDY)
        eng = self._eng(sp=2)
        got, fin = eng.generate(long_prompt, GREEDY)
        assert fin.finish_reason == FinishReason.LENGTH
        assert got == want

    def test_short_prompts_skip_the_ring(self):
        """Below the threshold the dense program serves (no ring latency
        tax on short prompts)."""
        eng = self._eng(sp=2, thresh=16)
        short = [1, 2, 3]  # bucket 8 < threshold 16
        want, _ = self._eng(sp=1).generate(short, GREEDY)
        got, _ = eng.generate(short, GREEDY)
        assert got == want

    def test_sessionful_reuse_with_sp_mesh(self):
        eng = self._eng(sp=2)
        p1 = [int(x) for x in np.random.default_rng(1).integers(1, 200, size=18)]
        a, _ = _turn(eng, p1, sid="lc-1")
        p2 = p1 + a + [7]
        want, _ = self._eng(sp=1).generate(p2, GREEDY)
        got, _ = _turn(eng, p2, sid="lc-1")
        assert got == want
        assert eng.metrics["prefix_reuse_tokens"] > 0


class TestEngineCoordinator:
    """Multi-pod serving front (SURVEY §7): one submit() surface, session
    affinity, load balance, failover."""

    def _coord(self, n=2):
        from omnia_tpu.engine.coordinator import EngineCoordinator

        workers = [_engine(num_slots=2) for _ in range(n)]
        return EngineCoordinator(workers), workers

    def _drive(self, coord, workers, handle):
        toks = []
        while True:
            for w in workers:
                w.step()
            try:
                while True:
                    ev = handle._queue.get_nowait()
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                    if ev.is_final:
                        return toks, ev
            except Exception:
                pass

    def test_session_affinity_reuses_kv(self):
        coord, workers = self._coord()
        p1 = [1, 2, 3, 4, 5, 6]
        h = coord.submit(p1, GREEDY, session_id="s-aff")
        t1, _ = self._drive(coord, workers, h)
        first = coord.worker_for("s-aff")
        h2 = coord.submit(p1 + t1 + [9], GREEDY, session_id="s-aff")
        self._drive(coord, workers, h2)
        assert coord.worker_for("s-aff") == first
        assert workers[first].metrics["prefix_reuse_tokens"] > 0

    def test_fresh_sessions_balance(self):
        coord, workers = self._coord()
        # Submit without driving: queue depths grow, the picker spreads.
        for i in range(4):
            coord.submit([1, 2, 3], GREEDY, session_id=f"bal-{i}")
        spread = {coord.worker_for(f"bal-{i}") for i in range(4)}
        assert spread == {0, 1}
        for w in workers:
            while w.step():
                pass

    def test_failover_on_unhealthy_worker(self):
        coord, workers = self._coord()
        h = coord.submit([5, 5, 5], GREEDY, session_id="s-fo")
        self._drive(coord, workers, h)
        pinned = coord.worker_for("s-fo")
        workers[pinned]._healthy = False  # worker dies
        h2 = coord.submit([5, 5, 5], GREEDY, session_id="s-fo")
        toks, fin = self._drive(coord, workers, h2)
        assert fin.finish_reason == FinishReason.LENGTH
        assert coord.worker_for("s-fo") != pinned
        assert coord.metrics["failovers"] == 1
        # Correctness preserved: same greedy tokens as a fresh engine.
        want, _ = _engine().generate([5, 5, 5], GREEDY)
        assert toks == want

    def test_all_workers_down_is_honest_error(self):
        coord, workers = self._coord()
        for w in workers:
            w._healthy = False
        ev = coord.submit([1], GREEDY).get_event(timeout=5)
        assert ev.finish_reason == FinishReason.ERROR
        assert "no healthy" in ev.error

    def test_aggregate_signals(self):
        coord, workers = self._coord()
        coord.submit([1, 2], GREEDY)
        assert coord.queue_depth() >= 1
        assert coord.healthy()
        for w in workers:
            while w.step():
                pass
