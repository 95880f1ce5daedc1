"""Cross-session shared-prefix KV pool (engine/prefix_cache.py).

The correctness bar is the same as sessionful serving: a turn served by
seeding shared rows from the pool must produce EXACTLY the tokens a
fresh engine produces for the same prompt (greedy), whether the rows
came from the device pool or the host-paged tier. On top of that: the
second session of a pack must prefill ONLY its suffix, refcounted rows
must never be freed under a resident seeder, and `prefix_cache_slots=0`
must be a true no-op.
"""

import importlib
import os
import pkgutil
import queue as queue_mod

import pytest

from omnia_tpu.engine import (
    EngineConfig,
    FinishReason,
    InferenceEngine,
    SamplingParams,
)
from omnia_tpu.engine.prefix_cache import PrefixPool
from omnia_tpu.models import get_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GREEDY = SamplingParams(temperature=0.0, max_tokens=4)

SYS = list(range(100, 112))  # 12-token shared "pack system prefix"


def _engine(num_slots=2, max_seq=64, max_sessions=8, **kw):
    kw.setdefault("prefix_cache_min_tokens", 4)
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
            try:
                while True:
                    ev = handle._queue.get_nowait()
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                    if ev.is_final:
                        return toks, ev
            except queue_mod.Empty:
                pass
    return handle.collect_tokens(timeout=60)


class TestRadixPool:
    """Host-side radix/bookkeeping unit tests (no device work)."""

    def _pool(self, slots=4, host=4):
        return PrefixPool(slots, host, clock=lambda: 0.0)

    def test_longest_full_match_wins(self):
        pool = self._pool()
        idx, _ = pool.acquire_slot()
        pool.insert(tuple(SYS[:6]), 8, idx)
        idx, _ = pool.acquire_slot()
        deep = pool.insert(tuple(SYS), 16, idx)
        entry, matched = pool.match(SYS + [1, 2])
        assert entry is deep and matched == len(SYS)

    def test_partial_match_against_deeper_entry(self):
        pool = self._pool()
        idx, _ = pool.acquire_slot()
        pool.insert(tuple(SYS), 16, idx)
        # Prompt diverges inside the entry: the shared head still counts.
        entry, matched = pool.match(SYS[:7] + [999, 998])
        assert entry is not None and matched == 7

    def test_observe_reports_lcp_at_threshold(self):
        pool = self._pool()
        assert pool.observe(SYS + [1, 2], threshold=2) == 0  # first sight
        got = pool.observe(SYS + [3, 4], threshold=2)
        assert got == len(SYS)  # the LCP has now been seen twice

    def test_acquire_never_victimizes_referenced(self):
        pool = self._pool(slots=1)
        idx, _ = pool.acquire_slot()
        entry = pool.insert(tuple(SYS), 16, idx)
        pool.incref(entry)
        assert pool.acquire_slot() == (None, None)
        pool.decref(entry.key)
        idx2, victim = pool.acquire_slot()
        assert idx2 == idx and victim is entry

    def test_registered_candidate_allows_partial(self):
        pool = self._pool()
        pool.register(tuple(SYS))
        assert pool.registered_candidate(SYS + [5]) == len(SYS)
        assert pool.registered_candidate(SYS[:9] + [999]) == 9


class TestSharedPrefixServing:
    def test_second_session_of_pack_prefills_suffix_only(self):
        """The acceptance bar: session 2 of the same pack prefills
        exactly (prompt length − matched prefix) tokens, with greedy
        tokens identical to a fresh engine."""
        eng = _engine(prefix_cache_slots=2)
        eng.register_prefix(SYS)
        p1 = SYS + [50, 51]
        _turn(eng, p1, sid="u1")  # session 1 publishes the pack prefix
        assert eng.metrics["prefix_cache_insertions"] == 1

        p2 = SYS + [60, 61, 62]
        before = dict(eng.metrics)
        t2, fin = _turn(eng, p2, sid="u2")
        assert fin.finish_reason == FinishReason.LENGTH
        matched = eng.metrics["prefix_cache_hit_tokens"] - before["prefix_cache_hit_tokens"]
        prefilled = eng.metrics["prefill_tokens"] - before["prefill_tokens"]
        assert matched == len(SYS)
        assert prefilled == len(p2) - matched
        # Gold equivalence: seeded rows serve the same greedy tokens.
        fresh = _engine()
        t2_fresh, _ = _turn(fresh, p2)
        assert t2 == t2_fresh

    def test_seen_twice_heuristic_publishes_lcp(self):
        """Without registration, the radix LCP of two fresh prompts
        publishes; the third session hits."""
        eng = _engine(prefix_cache_slots=2)
        for i in range(2):
            _turn(eng, SYS + [10 + i, 20 + i])
        assert eng.metrics["prefix_cache_insertions"] == 1
        assert eng.metrics["prefix_cache_hit_tokens"] == 0
        before = eng.metrics["prefill_tokens"]
        p3 = SYS + [30, 31]
        t3, _ = _turn(eng, p3)
        assert eng.metrics["prefix_cache_hit_tokens"] == len(SYS)
        assert eng.metrics["prefill_tokens"] - before == len(p3) - len(SYS)
        fresh = _engine()
        t3_fresh, _ = _turn(fresh, p3)
        assert t3 == t3_fresh

    def test_host_tier_hit_is_exact(self):
        """A demoted entry serves from host RAM through the restore
        program — slower, still token-identical."""
        pa, pb = SYS, list(range(200, 212))
        eng = _engine(prefix_cache_slots=1, prefix_cache_host_entries=4)
        eng.register_prefix(pa)
        eng.register_prefix(pb)
        _turn(eng, pa + [1])          # publish A (device)
        _turn(eng, pb + [2])          # publish B → demotes A to host
        assert eng.metrics["prefix_cache_evictions"] >= 1
        got, _ = _turn(eng, pa + [3, 4])
        assert eng.metrics["prefix_cache_host_hits"] == 1
        assert eng.metrics["prefix_cache_hit_tokens"] == len(pa)
        fresh = _engine()
        want, _ = _turn(fresh, pa + [3, 4])
        assert got == want

    def test_release_session_decrefs_seed(self):
        eng = _engine(prefix_cache_slots=1)
        eng.register_prefix(SYS)
        _turn(eng, SYS + [1])                    # publish
        _turn(eng, SYS + [2], sid="s1")          # session seeds
        (entry,) = eng._prefix_pool.entries()
        assert entry.refs == 1
        eng.release_session("s1")
        while eng.step():
            pass
        assert entry.refs == 0

    def test_eviction_never_frees_rows_under_resident_seeder(self):
        """Publish pressure with every pool slot pinned: the referenced
        entry keeps its device rows; the new prefix is simply not
        published (skip, not steal)."""
        eng = _engine(prefix_cache_slots=1)
        eng.register_prefix(SYS)
        _turn(eng, SYS + [1])
        _turn(eng, SYS + [2], sid="pin")         # session pins the entry
        (entry,) = eng._prefix_pool.entries()
        assert entry.refs == 1 and entry.on_device
        other = list(range(200, 212))
        eng.register_prefix(other)
        _turn(eng, other + [9])                  # wants a pool slot
        assert entry.on_device, "pinned entry lost its device rows"
        assert len(eng._prefix_pool.entries()) == 1  # publish skipped
        # Unpin → the next publish may recycle the slot.
        eng.release_session("pin")
        _turn(eng, other + [8])
        keys = {e.tokens for e in eng._prefix_pool.entries() if e.on_device}
        assert tuple(other) in keys

    def test_session_cap_drop_decrefs(self):
        """_enforce_session_cap dropping an idle session releases its
        seed pin (the satellite's release/cap interaction)."""
        eng = _engine(prefix_cache_slots=1, max_sessions=2)
        eng.register_prefix(SYS)
        _turn(eng, SYS + [1])                    # publish (sessionless)
        _turn(eng, SYS + [2], sid="a")           # seeds, refs=1
        (entry,) = eng._prefix_pool.entries()
        assert entry.refs == 1
        _turn(eng, [60, 61, 62], sid="b")
        _turn(eng, [70, 71, 72], sid="c")        # cap 2 → LRU drops "a"
        assert "a" not in eng._sessions
        assert entry.refs == 0

    def test_offload_elision_when_pool_covers(self):
        """A session whose valid rows are fully covered by the pool skips
        the host offload (rebuilt by a device seed next turn) — and the
        rebuilt turn is exact."""
        eng = _engine(num_slots=2, prefix_cache_slots=2, max_sessions=8)
        prefix = SYS + [50, 51]
        eng.register_prefix(prefix + [0] * 20)   # covers beyond any turn
        sp1 = SamplingParams(temperature=0.0, max_tokens=1)
        _turn(eng, prefix, sid="cov", sp=sp1)    # publishes prefix rows
        # token_ids for "cov" = prefix (last emitted excluded) — covered.
        _turn(eng, [60, 61, 62], sid="x1", sp=sp1)
        _turn(eng, [70, 71, 72], sid="x2", sp=sp1)  # 2 slots → evicts "cov"
        assert eng.metrics["prefix_cache_offload_elisions"] >= 1
        p2 = prefix + [90, 91]
        got, _ = _turn(eng, p2, sid="cov")
        fresh = _engine()
        want, _ = _turn(fresh, p2)
        assert got == want

    def test_recovery_drops_device_entries_keeps_host(self):
        pa, pb = SYS, list(range(200, 212))
        eng = _engine(prefix_cache_slots=1, prefix_cache_host_entries=4)
        eng.register_prefix(pa)
        eng.register_prefix(pb)
        _turn(eng, pa + [1])
        _turn(eng, pb + [2])                     # A → host, B device
        eng._recover("injected")
        entries = eng._prefix_pool.entries()
        assert all(not e.on_device for e in entries)
        assert any(e.host_k is not None for e in entries)  # A survived
        # Serving still works and host entry still hits exactly.
        got, _ = _turn(eng, pa + [3])
        fresh = _engine()
        want, _ = _turn(fresh, pa + [3])
        assert got == want


class TestKVQuantPool:
    """int8 KV edition (EngineConfig.kv_quant): the pool, its host tier,
    and the seed→suffix-prefill path move int8 rows + scales VERBATIM —
    the copy itself adds zero requantization drift. Token equality with
    a fresh engine is bounded rather than structural here, unlike the
    fp32 pool tests above: the pooled arm's suffix extend attends the
    int8 prefix rows while the fresh arm's single-bucket prefill attends
    the original float rows, so suffix logits carry ~0.4% quantization
    noise between the arms and a near-tie argmax flip is legal (though
    these 4-token turns sit deep inside the measured exact regime —
    free-running divergence starts ~token 75, tests/test_quant.py)."""

    @staticmethod
    def _assert_tokens_close(got, want):
        assert len(got) == len(want), (got, want)
        assert got[:2] == want[:2], (got, want)      # near-term greedy head
        agree = sum(int(x == y) for x, y in zip(got, want))
        assert agree >= len(got) - 1, (got, want)    # ≤1 near-tie tail flip

    def test_seed_suffix_prefill_round_trip(self):
        eng = _engine(prefix_cache_slots=2, kv_quant="int8")
        eng.register_prefix(SYS)
        _turn(eng, SYS + [50, 51], sid="u1")     # publish from slot rows
        assert eng.metrics["prefix_cache_insertions"] == 1
        p2 = SYS + [60, 61, 62]
        before = dict(eng.metrics)
        t2, fin = _turn(eng, p2, sid="u2")       # device seed + suffix
        assert fin.finish_reason == FinishReason.LENGTH
        assert (
            eng.metrics["prefix_cache_hit_tokens"]
            - before["prefix_cache_hit_tokens"] == len(SYS)
        )
        fresh = _engine(kv_quant="int8")
        t2_fresh, _ = _turn(fresh, p2)
        self._assert_tokens_close(t2, t2_fresh)

    def test_host_tier_round_trip(self):
        pa, pb = SYS, list(range(200, 212))
        eng = _engine(prefix_cache_slots=1, prefix_cache_host_entries=4,
                      kv_quant="int8")
        eng.register_prefix(pa)
        eng.register_prefix(pb)
        _turn(eng, pa + [1])                     # publish A (device)
        _turn(eng, pb + [2])                     # publish B → A to host
        got, _ = _turn(eng, pa + [3, 4])
        assert eng.metrics["prefix_cache_host_hits"] == 1
        fresh = _engine(kv_quant="int8")
        want, _ = _turn(fresh, pa + [3, 4])
        self._assert_tokens_close(got, want)

    def test_pool_bytes_halved(self):
        fp = _engine(prefix_cache_slots=2)
        q8 = _engine(prefix_cache_slots=2, kv_quant="int8")
        ratio = (
            q8.metrics["kv_quant_device_bytes"]
            / fp.metrics["kv_quant_device_bytes"]
        )
        assert ratio <= 0.55, f"slot+pool bytes ratio {ratio}"


class TestAdmissionOrder:
    def test_seedable_request_admits_first_within_window(self):
        from omnia_tpu.engine.types import Request, RequestHandle

        eng = _engine(prefix_cache_slots=2)
        eng.register_prefix(SYS)
        _turn(eng, SYS + [1])                    # publish
        long_cold = Request("r-cold", list(range(1, 17)), GREEDY)
        seedable = Request("r-seed", SYS + [9, 9], GREEDY)
        waiting = [
            (long_cold, RequestHandle("r-cold")),
            (seedable, RequestHandle("r-seed")),
        ]
        ordered = eng._admission_order(waiting)
        assert ordered[0][0].request_id == "r-seed"
        # FIFO is restored once the head request ages past the window.
        long_cold.submitted_at -= 10.0
        ordered = eng._admission_order(waiting)
        assert ordered[0][0].request_id == "r-cold"

    def test_disabled_pool_keeps_fifo(self):
        from omnia_tpu.engine.types import Request, RequestHandle

        eng = _engine()
        waiting = [
            (Request("a", list(range(1, 17)), GREEDY), RequestHandle("a")),
            (Request("b", [1, 2, 3], GREEDY), RequestHandle("b")),
        ]
        assert eng._admission_order(waiting) is waiting


class TestCoordinatorPrefixAffinity:
    def _coord(self, n=2, **kw):
        from omnia_tpu.engine.coordinator import EngineCoordinator

        workers = [_engine(num_slots=2, prefix_cache_slots=2) for _ in range(n)]
        kw.setdefault("prefix_route_min_tokens", 8)
        return EngineCoordinator(workers, **kw), workers

    def _drive(self, workers, handle):
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
            except queue_mod.Empty:
                pass

    def test_fresh_sessions_of_pack_share_a_worker(self):
        coord, workers = self._coord()
        coord.register_prefix(SYS)
        picks = set()
        for i in range(4):
            h = coord.submit(SYS + [40 + i], GREEDY, session_id=f"fs{i}")
            self._drive(workers, h)
            picks.add(coord.worker_for(f"fs{i}"))
        assert len(picks) == 1, picks
        w = workers[picks.pop()]
        assert w.metrics["prefix_cache_hit_tokens"] > 0
        assert coord.metrics["prefix_routed"] >= 3

    def test_short_prompts_keep_least_loaded_balance(self):
        coord, workers = self._coord()
        for i in range(4):
            coord.submit([1, 2, 3], GREEDY, session_id=f"bal-{i}")
        spread = {coord.worker_for(f"bal-{i}") for i in range(4)}
        assert spread == {0, 1}
        for w in workers:
            while w.step():
                pass

    def test_prefix_failover_rebuilds_on_healthy_worker(self):
        """The satellite: an unhealthy worker's fresh-session prefix
        affinity falls back to a clean re-prefill elsewhere — a latency
        cost, never a correctness one."""
        coord, workers = self._coord()
        coord.register_prefix(SYS)
        h = coord.submit(SYS + [1], GREEDY, session_id="fo1")
        self._drive(workers, h)
        pinned = coord.worker_for("fo1")
        workers[pinned]._healthy = False  # worker (and its pool) dies
        h2 = coord.submit(SYS + [2], GREEDY, session_id="fo2")
        toks, fin = self._drive(workers, h2)
        assert fin.finish_reason == FinishReason.LENGTH
        other = coord.worker_for("fo2")
        assert other != pinned
        assert coord.metrics["prefix_failovers"] == 1
        want, _ = _engine().generate(SYS + [2], GREEDY)
        assert toks == want

    def test_spill_past_load_threshold(self):
        coord, workers = self._coord(prefix_spill_load=0)
        coord.register_prefix(SYS)
        # Pin the prefix to worker 0 and pile load on it WITHOUT driving.
        for i in range(3):
            coord.submit(SYS + [30 + i], GREEDY, session_id=f"sp{i}")
        # sp0 pinned the prefix to one worker and loaded it; sp1 then
        # spilled to the other (the pin itself survives).
        assert coord.metrics["prefix_spills"] >= 1
        assert coord.worker_for("sp1") != coord.worker_for("sp0")
        for w in workers:
            while w.step():
                pass


class TestPoolDisabledNoop:
    """CI/tooling satellite: every engine module imports, and the engine
    constructs and serves under JAX_PLATFORMS=cpu with the pool enabled
    AND disabled — prefix_cache_slots=0 is a true no-op path."""

    def test_all_engine_modules_import(self):
        import omnia_tpu.engine as pkg

        for mod in pkgutil.iter_modules(pkg.__path__):
            importlib.import_module(f"omnia_tpu.engine.{mod.name}")

    def test_disabled_pool_is_true_noop(self):
        eng = _engine()  # prefix_cache_slots defaults to 0
        assert eng._prefix_pool is None
        assert eng._pk is None and eng._pv is None
        assert eng._prefix_store_fn is None
        assert eng._prefix_seed_fn is None
        assert eng._prefix_offload_fn is None
        eng.register_prefix(SYS)  # accepted, ignored
        _turn(eng, SYS + [1])
        _turn(eng, SYS + [2], sid="s")
        for key, val in eng.metrics.items():
            if key.startswith("prefix_cache_"):
                assert val == 0, (key, val)

    def test_enabled_pool_constructs_and_serves(self):
        eng = _engine(prefix_cache_slots=2)
        assert eng._pk is not None
        toks, fin = _turn(eng, SYS + [1])
        assert fin.finish_reason == FinishReason.LENGTH and toks


class TestMetricsKeyStability:
    """Dashboard/doctor read these names — renaming one is a breaking
    change and must show up here, not in a broken panel. The three set
    literals below are ALSO the machine-readable registries the static
    metrics-conformance checker (omnia_tpu/analysis/metricscheck.py)
    cross-checks against every metrics-write site and the
    docs/serving.md tables — keep them as plain string-set literals."""

    EXPECTED = {
        "requests_submitted", "requests_finished", "tokens_generated",
        "prefill_steps", "decode_steps", "extend_steps", "prefill_tokens",
        "prefill_tokens_blocked", "extend_tokens",
        "decode_dispatches", "decode_dispatches_single",
        "decode_dispatches_blocked", "decode_slot_steps", "decode_kv_blocks",
        "decode_window_rows",
        "decode_steps_sampling", "decode_steps_filtering",
        "moe_assignments_held", "moe_experts_hit", "decode_kda_slots",
        "decode_delta_slots", "decode_mamba_slots",
        "pipeline_flushes", "placements_deferred",
        "programs_compiled_serving",
        "prefix_reuse_tokens", "session_offloads", "session_restores",
        "session_exports", "session_imports",
        "decode_dispatch_s", "decode_sync_s", "prefill_dispatch_s",
        "spec_steps", "spec_proposed", "spec_accepted",
        "spec_gate_state", "spec_accept_ema", "spec_index_bytes",
        "prefix_cache_hit_tokens", "prefix_cache_insertions",
        "prefix_cache_evictions", "prefix_cache_host_hits",
        "prefix_cache_offload_elisions",
        "grammar_compile_hits", "grammar_compile_misses",
        "masked_logit_fraction", "grammar_rejections_avoided",
        "kv_quant_enabled", "kv_quant_bytes_per_token",
        "kv_quant_device_bytes",
        "kv_pages_total", "kv_pages_free", "kv_page_fragmentation",
        "kv_page_cow_copies",
        "requests_shed", "deadline_exceeded", "watchdog_trips",
        "recoveries",
        "mixed_steps", "interleaved_prefill_tokens", "decode_stall_steps",
        "flight_enabled",
        "compile_cache_enabled", "warmup_phase",
        "warmup_programs_total", "warmup_programs_done",
        "warmup_manifest_hits", "warmup_manifest_misses",
        "warmup_cache_hits", "warmup_cache_misses",
        "weights_bytes_total", "weights_bytes_loaded",
    }

    # MockEngine-private keys (beyond its EXPECTED mirror): the host-side
    # int8-KV round-trip evidence the real cache cannot report.
    MOCK_ONLY = {
        "kv_quant_rows_written", "kv_quant_roundtrip_rel_err",
    }

    # EngineCoordinator's fleet-routing ledger (+ the elastic-fleet
    # membership/migration books engine/fleet.py drives).
    COORDINATOR = {
        "routed", "failovers", "affinity_evictions",
        "prefix_routed", "prefix_failovers", "prefix_spills",
        "shed", "resubmits", "retirement_relays",
        "fleet_workers", "sessions_migrated", "migration_fallbacks",
        "scale_events",
        # Disaggregated serving (engine/disagg.py): tier-size gauges,
        # the sampled decode-slot occupancy, and the handoff ledger
        # (handoffs == handoff_fallbacks + sessions imported).
        "prefill_tier_workers", "decode_tier_workers",
        "decode_slots_active", "handoffs", "handoff_fallbacks",
    }

    def test_engine_metric_keys_are_stable(self):
        eng = _engine()
        assert set(eng.metrics) == self.EXPECTED

    def test_mock_metric_keys_are_stable(self):
        from omnia_tpu.engine.mock import MockEngine

        keys = set(MockEngine().metrics)
        assert self.MOCK_ONLY <= keys
        assert keys - self.MOCK_ONLY <= self.EXPECTED, (
            keys - self.MOCK_ONLY - self.EXPECTED
        )

    def test_coordinator_metric_keys_are_stable(self):
        from omnia_tpu.engine.coordinator import EngineCoordinator
        from omnia_tpu.engine.mock import MockEngine

        coord = EngineCoordinator([MockEngine()])
        assert set(coord.metrics) == self.COORDINATOR

    def test_docs_cover_every_metric_key(self):
        with open(os.path.join(REPO, "docs", "serving.md")) as f:
            doc = f.read()
        everything = self.EXPECTED | self.MOCK_ONLY | self.COORDINATOR
        missing = [k for k in everything if f"`{k}`" not in doc]
        assert not missing, f"docs/serving.md missing metric keys: {missing}"


class TestWarmupCoversPoolPrograms:
    def test_no_compiles_after_warmup_with_pool(self):
        """Seed/store/demote and the seeded-extend path must all be
        AOT-compiled by warmup (the TTFT discipline, pool edition)."""
        eng = _engine(prefix_cache_slots=2)
        eng.register_prefix(SYS)
        eng.warmup()
        import io
        import logging as _logging

        import jax as _jax

        with _jax.log_compiles():
            stream = io.StringIO()
            handler = _logging.StreamHandler(stream)
            logger = _logging.getLogger("jax._src.dispatch")
            logger.addHandler(handler)
            try:
                _turn(eng, SYS + [1, 2])         # publish (store program)
                _turn(eng, SYS + [3, 4])         # device seed + extend
            finally:
                logger.removeHandler(handler)
            logged = stream.getvalue()
        assert "Compiling" not in logged, logged


class TestBenchHeartbeat:
    """bench.py satellite: the accelerator child aborts within the init
    sub-deadline when backend init shows no progress (the BENCH_r05
    silent 390 s hang), and the abort reason lands in the trace."""

    def test_init_stalled_decision(self):
        import bench

        assert bench._init_stalled(False, 91.0, 90.0)
        assert not bench._init_stalled(False, 10.0, 90.0)
        # Once the backend-up marker was seen, long compiles are fine.
        assert not bench._init_stalled(True, 500.0, 90.0)

    def test_marker_matches_child_log_line(self):
        import bench

        # The child logs f"backend up: {platform} ..." — keep the marker
        # in sync with that line or the watchdog kills healthy children.
        with open(os.path.join(REPO, "bench.py")) as f:
            src = f.read()
        assert f'_log(f"{bench._BACKEND_UP_MARKER} ' in src

    def test_bench_has_prefix_cache_scenario(self):
        import bench

        assert callable(bench._bench_prefix_cache)

    @pytest.mark.slow
    def test_cpu_child_emits_prefix_cache_aux(self):
        import json
        import subprocess
        import sys

        env = dict(os.environ)
        env.update(OMNIA_BENCH_CHILD="1", OMNIA_BENCH_CHILD_DEADLINE_S="400",
                   JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            env=env, capture_output=True, timeout=420,
        )
        line = [ln for ln in out.stdout.decode().splitlines() if ln.startswith("{")][-1]
        aux = json.loads(line)["aux"]
        assert aux["prefix_cache"]["hit_tokens"] > 0
        # Grammar scenario rides the same child run (aux.grammar).
        assert aux["grammar"]["compile_cache_hit_rate"] > 0
        assert "mask_apply_us_per_step" in aux["grammar"]
