"""Cold-start suite (ISSUE 13): tracker/manifest units, warmup-knob
guards, parallel-vs-serial warmup equivalence, staged readiness.

Module layout follows tests/test_spec_decode.py: everything importable
at module top is jax-free (ColdStartTracker, WarmupManifest, the mock
parity layer, the bench phase heartbeat, the flight init events), so
the CI analysis job runs that subset under its poisoned jax stub; the
engine-backed equivalence battery importorskips jax and runs in tier-1.
"""

from __future__ import annotations

import json
import os

import pytest

from omnia_tpu.engine.coldstart import (
    BACKEND_EVENT,
    CACHE_HIT_EVENT,
    CACHE_MISS_EVENT,
    LOWER_EVENT,
    PHASE_CODES,
    PHASES,
    PROGRAM_SUMS,
    STAGES,
    TRACE_EVENT,
    ColdStartTracker,
    WarmupManifest,
    manifest_bookkeeping,
    manifest_dir,
    record_stages,
)

pytestmark = pytest.mark.coldstart

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Tracker (jax-free)
# ---------------------------------------------------------------------------


class TestColdStartTracker:
    def test_phase_codes_cover_phases_in_order(self):
        assert list(PHASE_CODES) == list(PHASES)
        assert [PHASE_CODES[p] for p in PHASES] == list(range(len(PHASES)))

    def test_phase_spans_and_current_phase(self):
        t = [0.0]
        cs = ColdStartTracker(clock=lambda: t[0])
        assert cs.current_phase() == "idle"
        cs.begin_phase("backend_init")
        t[0] = 2.0
        assert cs.current_phase() == "backend_init"
        assert cs.end_phase("backend_init") == 2.0
        # Between phases: latest FINISHED phase, never back to idle.
        assert cs.current_phase() == "backend_init"
        cs.begin_phase("warmup_compile")
        t[0] = 5.0
        snap = cs.snapshot()
        assert snap["phase"] == "warmup_compile"
        assert snap["phases_s"] == {"backend_init": 2.0, "warmup_compile": 3.0}
        cs.end_phase("warmup_compile")
        cs.mark_ready()
        assert cs.current_phase() == "ready"
        assert cs.snapshot()["phase_code"] == PHASE_CODES["ready"]

    def test_overlapping_phases_report_latest_begun(self):
        """weights_load and warmup_compile legitimately overlap (the
        streaming/compile overlap is the whole point) — current phase is
        the most recently BEGUN unfinished one."""
        t = [0.0]
        cs = ColdStartTracker(clock=lambda: t[0])
        cs.begin_phase("weights_load")
        t[0] = 1.0
        cs.begin_phase("warmup_compile")
        assert cs.current_phase() == "warmup_compile"
        t[0] = 4.0
        cs.end_phase("warmup_compile")
        assert cs.current_phase() == "weights_load"
        assert cs.end_phase("weights_load") == 4.0

    def test_end_without_begin_is_zero(self):
        cs = ColdStartTracker()
        assert cs.end_phase("backend_init") == 0.0
        assert cs.current_phase() == "idle"

    def test_unknown_phase_rejected(self):
        with pytest.raises(ValueError):
            ColdStartTracker().begin_phase("nope")

    def test_weights_progress_is_monotone(self):
        cs = ColdStartTracker()
        cs.note_weights(100, 1000)
        cs.note_weights(50, 1000)  # a racing late callback can't regress
        snap = cs.snapshot()
        assert snap["weights_bytes_loaded"] == 100
        assert snap["weights_bytes_total"] == 1000

    def test_program_counter(self):
        cs = ColdStartTracker()
        cs.set_programs_total(3)
        assert cs.note_program() == 1
        assert cs.note_program(2) == 3
        snap = cs.snapshot()
        assert (snap["programs_done"], snap["programs_total"]) == (3, 3)

    def test_rewarmup_never_reports_done_over_total(self):
        """A second warmup on the same engine (sessions=False then a
        full warmup is a public sequence) re-declares its total, resets
        the done counter, and un-readies the phase — probes must never
        read 'programs 4/3' or a stale 'ready'."""
        cs = ColdStartTracker()
        cs.set_programs_total(2)
        cs.note_program(2)
        cs.mark_ready()
        cs.begin_phase("warmup_compile")
        assert cs.current_phase() == "warmup_compile"  # not stale "ready"
        cs.set_programs_total(3)
        assert cs.note_program() == 1
        snap = cs.snapshot()
        assert (snap["programs_done"], snap["programs_total"]) == (1, 3)


# ---------------------------------------------------------------------------
# Program records (jax-free): synthetic (event, t, d) on a fake clock
# ---------------------------------------------------------------------------


def _stage_sum(record):
    return sum(record[f"{name}_s"] for name in STAGES)


#: One program as JAX reports it: an inner jit traced inside the outer
#: trace, lowering, then the backend. Times are the events' arrivals.
_ONE_PROGRAM = [
    (TRACE_EVENT, 12.0, 1.0),    # inner jit: [11, 12]
    (TRACE_EVENT, 13.0, 3.0),    # its caller: [10, 13], holds the inner one
    (LOWER_EVENT, 15.0, 2.0),    # [13, 15]
    (BACKEND_EVENT, 19.0, 4.0),  # [15, 19]
]


class TestRecordStages:
    def test_stages_equal_the_wall(self):
        r = record_stages(10.0, 19.5, _ONE_PROGRAM)
        assert r == {"trace_s": 3.0, "lower_s": 2.0, "compile_s": 4.0,
                     "cache_load_s": 0.0, "run_s": 0.5, "cache": "miss"}
        assert _stage_sum(r) == 19.5 - 10.0

    def test_a_nested_trace_is_not_counted_twice(self):
        """Adding the two trace durations would say 4 s of a 3 s trace."""
        assert record_stages(10.0, 19.5, _ONE_PROGRAM)["trace_s"] == 3.0
        assert record_stages(10.0, 19.5, _ONE_PROGRAM[1:])["trace_s"] == 3.0

    def test_a_backend_interval_with_a_hit_inside_is_a_load(self):
        hit = _ONE_PROGRAM[:3] + [(CACHE_HIT_EVENT, 18.9, 0.0), _ONE_PROGRAM[3]]
        r = record_stages(10.0, 19.5, hit)
        assert (r["cache_load_s"], r["compile_s"], r["cache"]) == (4.0, 0.0, "hit")
        # A miss is reported as its entry is written: still a compile.
        miss = _ONE_PROGRAM[:3] + [(CACHE_MISS_EVENT, 18.9, 0.0), _ONE_PROGRAM[3]]
        r = record_stages(10.0, 19.5, miss)
        assert (r["cache_load_s"], r["compile_s"], r["cache"]) == (0.0, 4.0, "miss")

    def test_a_hit_belongs_to_the_interval_it_lies_in(self):
        """A task that loads an operand's eager program and then
        compiles its step program: one load, one compile, one record."""
        events = [
            (LOWER_EVENT, 1.5, 0.5), (CACHE_HIT_EVENT, 1.9, 0.0),
            (BACKEND_EVENT, 2.0, 0.5),
            (TRACE_EVENT, 4.0, 1.0), (LOWER_EVENT, 5.0, 1.0),
            (BACKEND_EVENT, 8.0, 3.0),
        ]
        r = record_stages(0.0, 9.0, events)
        assert r == {"trace_s": 3.0, "lower_s": 1.5, "compile_s": 3.0,
                     "cache_load_s": 0.5, "run_s": 1.0, "cache": "miss"}
        assert _stage_sum(r) == 9.0

    def test_no_compile_asked_is_all_run(self):
        """The process already held the program: no event arrives."""
        r = record_stages(3.0, 3.25, [])
        assert (r["run_s"], r["cache"]) == (0.25, "none")
        assert _stage_sum(r) == 0.25

    def test_intervals_are_cut_to_the_record(self):
        """An event that began before the record (it cannot, on one
        thread; a clock's slack can say so) never makes a stage longer
        than the wall."""
        r = record_stages(10.0, 12.0, [(BACKEND_EVENT, 11.0, 5.0)])
        assert (r["compile_s"], r["run_s"]) == (1.0, 1.0)
        assert _stage_sum(r) == 2.0


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _feed(cs, clock, rec, events):
    for event, t, d in events:
        clock.t = t
        cs.note_stage_event(rec, event, d)


class TestProgramRecords:
    def _warming(self):
        clock = _FakeClock()
        cs = ColdStartTracker(clock=clock)
        clock.t = 9.0
        cs.begin_phase("warmup_compile")
        cs.begin_programs()
        return cs, clock

    def test_a_record_is_its_task_and_its_stages(self):
        cs, clock = self._warming()
        clock.t = 10.0
        rec = cs.begin_program("decode", "chunk8")
        _feed(cs, clock, rec, _ONE_PROGRAM)
        clock.t = 19.5
        record = cs.end_program(rec)
        assert set(record) == {
            "family", "key", "thread", "t0", "t1", "trace_s", "lower_s",
            "compile_s", "cache_load_s", "run_s", "cache",
        }
        assert (record["family"], record["key"]) == ("decode", "chunk8")
        assert (record["t0"], record["t1"]) == (10.0, 19.5)
        assert _stage_sum(record) == record["t1"] - record["t0"]
        assert cs.program_records() == [record]

    def test_sums_ride_in_both_phase_readers_and_tile_the_span(self):
        cs, clock = self._warming()
        clock.t = 10.0
        rec = cs.begin_program("decode", "chunk8")
        _feed(cs, clock, rec, _ONE_PROGRAM)
        clock.t = 19.5
        cs.end_program(rec)
        cs.note_drain(0.5)
        clock.t = 20.0
        cs.end_phase("warmup_compile")
        phases = cs.phase_seconds()
        assert phases == cs.snapshot()["phases_s"]
        assert [k for k in phases if k.startswith("programs.")] == list(PROGRAM_SUMS)
        assert phases["programs.trace"] == 3.0
        assert phases["programs.run"] == 0.5
        assert phases["programs.drain"] == 0.5
        tile = sum(phases[k] for k in PROGRAM_SUMS if k != "programs.after")
        # The record began a second into the span: nothing claims it.
        assert tile == phases["warmup_compile"] - 1.0

    def test_no_new_key_starts_with_warmup(self):
        """benchmark/layer_metrics/programs.warmup_s.py adds every key
        with that prefix."""
        assert not [k for k in PROGRAM_SUMS if k.startswith("warmup")]
        assert set(PROGRAM_SUMS) & set(PHASES) == set()
        cs, _clock = self._warming()
        assert [k for k in cs.phase_seconds() if k.startswith("warmup")] == [
            "warmup_compile"
        ]

    def test_no_records_no_keys(self):
        """The mock's warm-up keeps no records: phases only."""
        cs = ColdStartTracker()
        cs.begin_phase("warmup_compile")
        cs.end_phase("warmup_compile")
        assert list(cs.phase_seconds()) == ["warmup_compile"]
        snap = cs.snapshot()
        assert (snap["programs_cache_hits"], snap["programs_cache_misses"]) == (0, 0)

    def test_two_open_records_do_not_cross(self):
        """Two workers' records are open at once; each is fed on its own
        thread, and neither's events reach the other."""
        cs, clock = self._warming()
        clock.t = 10.0
        a = cs.begin_program("decode", "chunk8")
        b = cs.begin_program("prefill", "bucket64")
        _feed(cs, clock, a, [(LOWER_EVENT, 12.0, 2.0)])
        _feed(cs, clock, b, [(CACHE_HIT_EVENT, 13.5, 0.0), (BACKEND_EVENT, 14.0, 3.0)])
        ra, rb = cs.end_program(a), cs.end_program(b)
        assert (ra["lower_s"], ra["cache_load_s"], ra["cache"]) == (2.0, 0.0, "none")
        assert (rb["lower_s"], rb["cache_load_s"], rb["cache"]) == (0.0, 3.0, "hit")
        snap = cs.snapshot()
        assert (snap["programs_cache_hits"], snap["programs_cache_misses"]) == (1, 0)

    def test_an_event_with_no_record_is_other_then_after_then_nobodys(self):
        cs, clock = self._warming()
        _feed(cs, clock, None, [(TRACE_EVENT, 11.0, 1.0), (BACKEND_EVENT, 12.0, 1.5),
                                (CACHE_MISS_EVENT, 12.0, 0.0)])
        clock.t = 13.0
        cs.end_phase("warmup_compile")
        # The slot programs and the restore: outside the span, inside warmup().
        _feed(cs, clock, None, [(BACKEND_EVENT, 14.0, 0.25)])
        cs.end_programs()
        _feed(cs, clock, None, [(BACKEND_EVENT, 20.0, 5.0), (CACHE_HIT_EVENT, 20.0, 0.0)])
        phases = cs.phase_seconds()
        assert phases["programs.other"] == 2.0  # [10, 11] and [10.5, 12] as one
        assert phases["programs.after"] == 0.25
        snap = cs.snapshot()
        assert (snap["programs_cache_hits"], snap["programs_cache_misses"]) == (0, 1)

    def test_a_second_warmup_starts_its_records_over(self):
        cs, clock = self._warming()
        rec = cs.begin_program("decode", "chunk8")
        clock.t = 10.0
        cs.end_program(rec)
        cs.begin_programs()
        assert cs.program_records() == []
        assert cs.phase_seconds()["programs.run"] == 0.0

    def test_slowest_programs_longest_first(self):
        cs, clock = self._warming()
        for key, wall in (("a", 1.0), ("b", 3.0), ("c", 2.0)):
            rec = cs.begin_program("prefill", key)
            clock.t += wall
            cs.end_program(rec)
        assert [r["key"] for r in cs.slowest_programs(2)] == ["b", "c"]


# ---------------------------------------------------------------------------
# Manifest (jax-free)
# ---------------------------------------------------------------------------


class TestWarmupManifest:
    def test_key_is_stable_and_content_sensitive(self):
        a = {"model": {"layers": 2}, "engine": {"max_seq": 128}}
        assert WarmupManifest.manifest_key(a) == WarmupManifest.manifest_key(
            {"engine": {"max_seq": 128}, "model": {"layers": 2}}
        )
        b = {"model": {"layers": 3}, "engine": {"max_seq": 128}}
        assert WarmupManifest.manifest_key(a) != WarmupManifest.manifest_key(b)

    def test_store_load_roundtrip_and_merge(self, tmp_path):
        d = str(tmp_path)
        assert WarmupManifest.load(d, "k") is None
        assert WarmupManifest.store(d, "k", ["decode:chunk8", "prefill:bucket64"])
        assert WarmupManifest.load(d, "k") == [
            "decode:chunk8", "prefill:bucket64",
        ]
        # sessions=False warmups must not erase a full warmup's families.
        assert WarmupManifest.store(d, "k", ["decode:chunk8", "session:rows64"])
        assert WarmupManifest.load(d, "k") == [
            "decode:chunk8", "prefill:bucket64", "session:rows64",
        ]

    def test_unwritable_dir_degrades_without_raising(self, tmp_path):
        # A regular file where the manifest dir should be: every write
        # attempt is an OSError (works even when the suite runs as root,
        # where a chmod-0o500 dir would still be writable).
        blocked = tmp_path / "not_a_dir"
        blocked.write_text("x")
        assert WarmupManifest.store(str(blocked), "k", ["a:b"]) is False

    def test_corrupt_manifest_reads_as_absent(self, tmp_path):
        path = WarmupManifest._path(str(tmp_path), "k")
        with open(path, "w") as f:
            f.write("{not json")
        assert WarmupManifest.load(str(tmp_path), "k") is None

    def test_bookkeeping_hits_and_misses(self, tmp_path):
        d = str(tmp_path)
        cs = ColdStartTracker()
        hits, misses = manifest_bookkeeping(d, "k", ["a:1", "b:2"], cs)
        assert (hits, misses) == (0, 2)
        cs2 = ColdStartTracker()
        hits, misses = manifest_bookkeeping(d, "k", ["a:1", "b:2", "c:3"], cs2)
        assert (hits, misses) == (2, 1)
        assert cs2.snapshot()["manifest_hits"] == 2
        # No directory: in-memory cold accounting, nothing persisted.
        hits, misses = manifest_bookkeeping(None, "k", ["a:1"], ColdStartTracker())
        assert (hits, misses) == (0, 1)

    def test_manifest_dir_env_override(self, tmp_path, monkeypatch):
        monkeypatch.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path))
        assert manifest_dir() == str(tmp_path)


# ---------------------------------------------------------------------------
# compile_cache placement (jax-free satellite)
# ---------------------------------------------------------------------------


class TestCompileCacheDir:
    def test_standard_variable_wins(self, monkeypatch):
        from omnia_tpu.utils import compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/persistent")
        assert compile_cache.default_cache_dir() == "/somewhere/persistent"

    def test_unwritable_dir_is_an_error_not_a_move(self, monkeypatch, tmp_path):
        """A cache directory that cannot be written fails loudly with
        the path in the message; the cache is never moved somewhere
        else (the path is part of the cache key)."""
        from omnia_tpu.utils import compile_cache

        target = tmp_path / "ro" / ".jax_cache"

        def refuse(path, exist_ok=False):
            raise OSError(30, "Read-only file system", path)

        monkeypatch.setattr(compile_cache.os, "makedirs", refuse)
        with pytest.raises(RuntimeError, match="not writable") as exc:
            compile_cache._require_writable(str(target))
        assert str(target) in str(exc.value)
        assert not target.exists()

    def test_default_is_fixed_dot_dir_in_checkout(self, monkeypatch):
        from omnia_tpu.utils import compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.default_cache_dir() == os.path.join(
            REPO, ".jax_cache"
        )

    def test_standard_variable_means_no_directory_set_in_code(
        self, monkeypatch, tmp_path
    ):
        """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the
        module lowers the floors and sets no directory of its own."""
        import jax

        from omnia_tpu.utils import compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(compile_cache, "_enabled_dir", None)
        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.append(k)
        )
        assert compile_cache.enable_compilation_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
        assert "jax_persistent_cache_min_compile_time_secs" in updates


# ---------------------------------------------------------------------------
# Flight init-phase events (jax-free)
# ---------------------------------------------------------------------------


class TestInitPhaseFlightEvents:
    def test_init_events_are_in_the_closed_vocabulary(self):
        from omnia_tpu.engine.flight import EVENTS, INIT_EVENTS

        assert INIT_EVENTS <= EVENTS
        assert INIT_EVENTS == {
            "backend_init", "weights_load", "warmup_compile",
            "warmup_restore",
        }

    def test_note_init_phase_rejects_non_init_kinds(self):
        from omnia_tpu.engine.flight import FlightRecorder

        rec = FlightRecorder(16)
        with pytest.raises(AssertionError):
            rec.note_init_phase("decode_chunk", {})

    def test_chrome_export_renders_init_durations(self):
        """Init events carry `seconds`; the Chrome export must render
        them as duration rows on the engine-steps track AND keep every
        computed start non-negative (they are the longest durations in a
        cold-start dump, recorded at phase END)."""
        from omnia_tpu.engine.flight import FlightRecorder, to_chrome_trace

        rec = FlightRecorder(64)
        rec.note_init_phase("backend_init", {"backend": "cpu", "seconds": 1.5})
        rec.note_init_phase("weights_load", {"bytes": 123, "seconds": 2.0})
        rec.note_init_phase(
            "warmup_compile", {"programs": 7, "threads": 2, "seconds": 4.0}
        )
        rec.note_init_phase("warmup_restore", {"seconds": 0.25})
        doc = to_chrome_trace(rec.events())
        rows = {
            e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"
        }
        assert set(rows) == {
            "backend_init", "weights_load", "warmup_compile",
            "warmup_restore",
        }
        assert rows["warmup_compile"]["dur"] == 4.0 * 1e6
        assert rows["warmup_compile"]["args"]["programs"] == 7
        for e in doc["traceEvents"]:
            if "ts" in e:
                assert e["ts"] >= 0.0, e


# ---------------------------------------------------------------------------
# Bench per-phase heartbeat (jax-free satellite; parent-side code only)
# ---------------------------------------------------------------------------


class TestBenchPhaseHeartbeat:
    def test_phase_marker_folding(self):
        import bench

        assert bench._phase_of("noise", "backend_init") == "backend_init"
        assert bench._phase_of(
            f"[bench +  1.0s] {bench._PHASE_MARKER} weights_load",
            "backend_init",
        ) == "weights_load"
        assert bench._phase_of(
            "[bench +  2.0s] backend up: tpu (v5e)", "backend_init"
        ) == "backend_up"
        # A malformed marker line keeps the previous phase.
        assert bench._phase_of(bench._PHASE_MARKER, "compile") == "compile"

    def test_child_emits_parseable_markers(self):
        """_mark_phase's output must fold back through _phase_of — the
        parent watchdog's stuck-phase attribution depends on it."""
        import bench

        with open(os.path.join(REPO, "bench.py")) as f:
            src = f.read()
        # The child marks every cold-start phase the runbook names.
        for phase in ("backend_init", "weights_load", "warmup_compile", "ready"):
            assert f'_mark_phase("{phase}")' in src, phase

    def test_bench_has_coldstart_scenario(self):
        import bench

        assert callable(bench._bench_coldstart)

    def test_kill_reason_names_stuck_phase(self):
        """The watchdog kill reasons interpolate the last seen phase —
        that string lands in aux.tpu_attempt_trace."""
        with open(os.path.join(REPO, "bench.py")) as f:
            src = f.read()
        assert src.count("stuck phase:") >= 2  # hard deadline + init stall


# ---------------------------------------------------------------------------
# Mock parity (jax-free)
# ---------------------------------------------------------------------------


class TestMockColdStartParity:
    def test_mock_warmup_books_ledger_and_manifest(self, tmp_path, monkeypatch):
        from omnia_tpu.engine.mock import MockEngine

        monkeypatch.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path))
        m = MockEngine()
        assert m.metrics["warmup_phase"] == 0
        m.warmup()
        assert m.metrics["warmup_phase"] == PHASE_CODES["ready"]
        assert m.metrics["warmup_programs_total"] == 1
        assert m.metrics["warmup_programs_done"] == 1
        assert m.metrics["warmup_manifest_misses"] == 1
        # Second mock, same knobs: the REAL manifest machinery reports
        # the restart as a hit.
        m2 = MockEngine()
        m2.warmup()
        assert m2.metrics["warmup_manifest_hits"] == 1
        assert m2.metrics["warmup_manifest_misses"] == 0
        # Different knobs → different key → cold. (prefill_chunk_tokens
        # keeps this constructible under the poisoned-jax CI stub.)
        m3 = MockEngine(prefill_chunk_tokens=7)
        m3.warmup()
        assert m3.metrics["warmup_manifest_hits"] == 0

    def test_mock_warmup_threads_zero_is_true_noop(self, tmp_path, monkeypatch):
        """warmup_threads on the mock is ledger-only: scripted output is
        EXACTLY unchanged across values, and 0 (default) leaves the same
        state as not passing the knob at all."""
        from omnia_tpu.engine.mock import MockEngine, Scenario
        from omnia_tpu.engine.types import SamplingParams

        monkeypatch.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path))
        sp = SamplingParams(max_tokens=32)
        outs = {}
        for threads in (None, 0, 3):
            kwargs = {} if threads is None else {"warmup_threads": threads}
            m = MockEngine([Scenario("hi", "hello-world")], **kwargs)
            m.warmup()
            toks, fin = m.generate(m.tokenizer.encode("hi"), sp)
            outs[threads] = (m.tokenizer.decode(toks), fin.finish_reason.value)
            assert m.warmup_threads == (threads or 0)
        assert outs[None] == outs[0] == outs[3] == ("hello-world", "stop")

    def test_mock_rejects_negative_threads(self):
        from omnia_tpu.engine.mock import MockEngine

        with pytest.raises(ValueError):
            MockEngine(warmup_threads=-1)


# ---------------------------------------------------------------------------
# Operator staged readiness (jax-free: pure helpers + a stubbed probe)
# ---------------------------------------------------------------------------


class TestOperatorStagedReadiness:
    def test_warmup_progress_message(self):
        controller = pytest.importorskip("omnia_tpu.operator.controller")

        msg = controller.warmup_progress_message({
            "phase": "warmup_compile", "programs_done": 12,
            "programs_total": 40, "weights_bytes_loaded": 1_200_000_000,
            "weights_bytes_total": 16_100_000_000,
        })
        assert msg == "phase=warmup_compile, programs 12/40, weights 1.2/16.1 GB"
        assert controller.warmup_progress_message({}) == (
            "phase=unknown (runtime reports no warmup progress)"
        )
        # Partial dicts (no checkpoint → no weight bytes) stay clean.
        assert controller.warmup_progress_message(
            {"phase": "warmup_compile", "programs_total": 0}
        ) == "phase=warmup_compile"

    def test_capability_gate_surfaces_initializing_progress(self, monkeypatch):
        """An initializing runtime must yield (not gated, warming msg) —
        capability absence during warmup is 'not ready', never
        'missing'; a ready runtime keeps the old gate semantics."""
        from types import SimpleNamespace

        controller = pytest.importorskip("omnia_tpu.operator.controller")
        client_mod = pytest.importorskip("omnia_tpu.runtime.client")
        from omnia_tpu.runtime.contract import HealthResponse

        responses = {}

        class FakeClient:
            def __init__(self, addr):
                pass

            def health(self, timeout=None):
                return responses["h"]

            def close(self):
                pass

        monkeypatch.setattr(client_mod, "RuntimeClient", FakeClient)
        fake_self = SimpleNamespace(capability_probe_timeout_s=1.0)
        dep = SimpleNamespace(
            pods=[SimpleNamespace(runtime_port=1)], candidate_pods=[],
            required_capabilities=["text", "streaming"], name="d",
        )
        gate = controller.ControllerManager._capability_gate

        responses["h"] = HealthResponse(
            status="initializing", capabilities=[],
            warmup={"phase": "warmup_compile", "programs_done": 3,
                    "programs_total": 9},
        )
        gated, missing, warming = gate(fake_self, dep)
        assert not gated and missing == []
        assert warming == "phase=warmup_compile, programs 3/9"

        responses["h"] = HealthResponse(status="ok", capabilities=["text"])
        gated, missing, warming = gate(fake_self, dep)
        assert gated and missing == ["streaming"] and warming is None

        responses["h"] = HealthResponse(
            status="ok", capabilities=["text", "streaming"]
        )
        assert gate(fake_self, dep) == (False, [], None)

    def test_health_response_wire_roundtrip_carries_warmup(self):
        from omnia_tpu.runtime.contract import HealthResponse

        h = HealthResponse(status="initializing",
                           warmup={"phase": "weights_load"})
        back = HealthResponse.from_bytes(h.to_bytes())
        assert back.warmup == {"phase": "weights_load"}
        # Legacy wire payloads (no warmup field) stay parseable.
        legacy = dict(json.loads(h.to_bytes()))
        legacy.pop("warmup")
        assert HealthResponse.from_bytes(
            json.dumps(legacy).encode()
        ).warmup == {}


# ---------------------------------------------------------------------------
# Engine-backed battery (skips without jax)
# ---------------------------------------------------------------------------


def _engine(monkeypatch=None, **over):
    jax = pytest.importorskip("jax")  # noqa: F841
    from omnia_tpu.engine import EngineConfig, InferenceEngine
    from omnia_tpu.models import get_config

    base = dict(num_slots=2, max_seq=128, prefill_buckets=(32, 64),
                dtype="float32", max_sessions=4)
    base.update(over)
    return InferenceEngine(get_config("test-tiny"), EngineConfig(**base), seed=3)


def _lowered_decode(eng):
    return eng._decode_fn_single.lower(
        eng.params, eng._ck, eng._cv, eng._tokens, eng._positions,
        eng._active, eng._budget, eng._stop_ids, eng._key_data,
        eng._temp, eng._top_p, eng._top_k,
    ).as_text()


def test_warmup_threads_zero_is_true_noop(tmp_path, monkeypatch):
    """warmup_threads is a host-side compile-concurrency knob: it is
    never read at trace time (byte-identical lowered programs across
    values), 0 builds zero parallel state (no executor, no scratch
    caches — the serial path), and post-warmup engine state is the
    restored pristine allocation either way."""
    pytest.importorskip("jax")
    from omnia_tpu.engine.types import EngineConfig

    monkeypatch.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path))
    assert EngineConfig().warmup_threads == 0  # the guarded default
    off = _engine()
    on = _engine(warmup_threads=3)
    assert _lowered_decode(off) == _lowered_decode(on)
    # Serial warmup allocates no scratch states: the only states list it
    # builds wraps the engine's OWN arrays (worker-0 semantics).
    tasks = off._warmup_tasks(sessions=True)
    states = off._run_warmup_serial(tasks[:1])
    assert len(states) == 1
    with pytest.raises(ValueError):
        _engine(warmup_threads=-1)


@pytest.mark.slow
def test_parallel_warmup_is_bit_identical_to_serial(tmp_path, monkeypatch):
    """Same compiled program set, same traced signatures, same restored
    state: a sampled (seeded) generation after parallel warmup matches
    serial warmup token for token, and the task inventories agree."""
    pytest.importorskip("jax")
    from omnia_tpu.engine.types import SamplingParams

    monkeypatch.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path))
    sp = SamplingParams(temperature=0.9, top_p=0.9, top_k=20,
                        max_tokens=12, seed=11)
    outs = {}
    inventories = {}
    for threads in (0, 3):
        eng = _engine(warmup_threads=threads, prefix_cache_slots=2,
                      prefill_chunk_tokens=32)
        inventories[threads] = [
            (fam, key) for fam, key, _fn in eng._warmup_tasks(sessions=True)
        ]
        eng.warmup()
        assert eng.metrics["warmup_programs_done"] == (
            eng.metrics["warmup_programs_total"]
        ) == len(inventories[threads])
        toks, fin = eng.generate(list(range(1, 40)), sp)
        outs[threads] = (toks, fin.finish_reason)
    assert inventories[0] == inventories[3]
    assert outs[0] == outs[3]


def test_manifest_keying_and_second_engine_hit(tmp_path, monkeypatch):
    """Second engine in-process with the same config: every program is a
    manifest hit (compiles should be persistent-cache restores on a pod
    restart). Changing model config / bucket set / kv_quant / kv_pages
    produces DISTINCT manifest keys; host-side knobs do not."""
    pytest.importorskip("jax")
    import dataclasses

    from omnia_tpu.engine import EngineConfig, InferenceEngine
    from omnia_tpu.models import get_config

    monkeypatch.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path))
    e1 = _engine()
    e1.warmup()
    total = e1.metrics["warmup_programs_total"]
    assert total > 0
    assert e1.metrics["warmup_manifest_misses"] == total

    e2 = _engine()
    assert e2._warmup_manifest_key() == e1._warmup_manifest_key()
    e2.warmup()
    assert e2.metrics["warmup_manifest_hits"] == total
    assert e2.metrics["warmup_manifest_misses"] == 0

    keys = {e1._warmup_manifest_key()}
    for over in (
        dict(prefill_buckets=(32,)),          # bucket set
        dict(kv_quant="int8"),                # KV representation
        dict(kv_pages=8, kv_page_tokens=32),  # paged layout
        dict(max_seq=64),                     # cache shape
    ):
        keys.add(_engine(**over)._warmup_manifest_key())
    assert len(keys) == 5, "every shape-relevant change must re-key"
    # Model config re-keys too.
    mc = dataclasses.replace(get_config("test-tiny"), num_layers=3)
    alt = InferenceEngine(
        mc, EngineConfig(num_slots=2, max_seq=128, prefill_buckets=(32, 64),
                         dtype="float32", max_sessions=4), seed=3,
    )
    assert alt._warmup_manifest_key() not in keys
    # Host-side knobs share the key (a restart that only tunes them
    # still reads its manifest).
    assert _engine(
        warmup_threads=3, flight_events=64, max_queue=8,
    )._warmup_manifest_key() == e1._warmup_manifest_key()


def test_warmup_progress_metrics_and_init_flight_events(tmp_path, monkeypatch):
    """After warmup: phase=ready, done==total, manifest books mirrored;
    the flight ring holds the init-phase events with their durations and
    they survive the Chrome export."""
    pytest.importorskip("jax")
    from omnia_tpu.engine.flight import to_chrome_trace

    monkeypatch.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path))
    eng = _engine(flight_events=128)
    eng.warmup()
    m = eng.metrics
    assert m["warmup_phase"] == PHASE_CODES["ready"]
    assert m["warmup_programs_total"] > 0
    assert m["warmup_programs_done"] == m["warmup_programs_total"]
    kinds = [e.kind for e in eng._flight.events()]
    assert kinds.count("backend_init") == 1
    assert kinds.count("warmup_compile") == 1
    assert kinds.count("warmup_restore") == 1
    compile_ev = eng._flight.events("warmup_compile")[0]
    assert compile_ev.attrs["programs"] == m["warmup_programs_total"]
    assert compile_ev.attrs["seconds"] > 0
    assert compile_ev.attrs["threads"] == 0
    doc = to_chrome_trace(eng._flight.events())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"warmup_compile", "warmup_restore"} <= names

    snap = eng._coldstart.snapshot()
    assert snap["phase"] == "ready"
    assert snap["phases_s"]["warmup_compile"] > 0


def test_checkpoint_loader_streams_with_progress_and_overlap(
    tmp_path, monkeypatch
):
    """The engine accepts a params LOADER: weights stream under the
    weights_load phase with per-tensor byte progress (metrics mirror +
    flight event), the param-free families compile on the overlap
    thread, and generation matches an engine built from the same
    checkpoint's preloaded params."""
    pytest.importorskip("jax")
    pytest.importorskip("safetensors")
    import jax.numpy as jnp

    from omnia_tpu.engine import EngineConfig, InferenceEngine, SamplingParams
    from omnia_tpu.models import checkpoint as ckpt_io
    from omnia_tpu.models import get_config, llama

    monkeypatch.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path / "man"))
    cfg = get_config("test-tiny")
    import jax

    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    ckpt = str(tmp_path / "ckpt")
    ckpt_io.save_params(params, cfg, ckpt)

    calls = []

    def loader(progress_cb=None):
        def meter(loaded, total):
            calls.append((loaded, total))
            if progress_cb is not None:
                progress_cb(loaded, total)
        return ckpt_io.load_params(ckpt, cfg, dtype=jnp.float32,
                                   progress_cb=meter)

    ecfg = EngineConfig(num_slots=2, max_seq=128, prefill_buckets=(32, 64),
                        dtype="float32", max_sessions=4)
    eng = InferenceEngine(cfg, ecfg, params=loader, seed=3,)
    assert calls, "loader must stream with per-tensor progress"
    loaded, total = calls[-1]
    assert loaded == total == ckpt_io.expected_param_bytes(cfg, jnp.float32)
    assert eng.metrics["weights_bytes_loaded"] == total
    assert eng.metrics["weights_bytes_total"] == total
    snap = eng._coldstart.snapshot()
    assert "weights_load" in snap["phases_s"]

    ref = InferenceEngine(cfg, ecfg,
                          params=ckpt_io.load_params(ckpt, cfg,
                                                     dtype=jnp.float32),
                          seed=3)
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    assert eng.generate([5, 6, 7], sp)[0] == ref.generate([5, 6, 7], sp)[0]


def test_runtime_forwards_warmup_threads(monkeypatch):
    """Providers forward the knob to tpu AND mock engines (the runtime
    options surface the operator's Provider CR exposes)."""
    pytest.importorskip("jax")
    from omnia_tpu.runtime.providers import ProviderSpec, build_engine

    mock = build_engine(ProviderSpec(
        name="m", type="mock", options={"warmup_threads": 2},
    ))
    assert mock.warmup_threads == 2
    tpu = build_engine(ProviderSpec(
        name="t", type="tpu", model="test-tiny",
        options={"num_slots": 2, "max_seq": 64, "prefill_buckets": [8],
                 "dtype": "float32", "warmup_threads": 3},
    ))
    assert tpu.cfg.warmup_threads == 3


# -- program records on a real warm-up --------------------------------------


def _warmup_s_reader():
    """The accepted reader of set-up's one per-layer number, by its file."""
    import importlib.util

    path = os.path.join(REPO, "benchmark", "layer_metrics", "programs.warmup_s.py")
    spec = importlib.util.spec_from_file_location("programs_warmup_s", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def warmed(tmp_path_factory):
    """One serial warm-up of the tiny engine, recorder on."""
    pytest.importorskip("jax")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path_factory.mktemp("manifest")))
        eng = _engine(flight_events=128)
        tasks = [(f, k) for f, k, _fn in eng._warmup_tasks(sessions=True)]
        eng.warmup()
    return eng, tasks


def test_one_record_a_task_and_its_stages_equal_its_wall(warmed):
    eng, tasks = warmed
    records = eng._coldstart.program_records()
    assert [(r["family"], r["key"]) for r in records] == tasks
    for r in records:
        assert _stage_sum(r) == pytest.approx(
            r["t1"] - r["t0"], abs=1e-9)
        assert min(r[f"{s}_s"] for s in STAGES) >= 0.0
        # A first start of a shape in this process: something was asked
        # of the compiler, and a serial warm-up runs on the caller's thread.
        assert r["cache"] in ("hit", "miss")
        assert r["trace_s"] > 0 and r["lower_s"] > 0
    assert len({r["thread"] for r in records}) == 1
    assert all(a["t1"] <= b["t0"] for a, b in zip(records, records[1:]))


def test_the_seven_sums_tile_warmup_compile(warmed):
    eng, _tasks = warmed
    phases = eng._coldstart.phase_seconds()
    assert phases == eng._coldstart.snapshot()["phases_s"]
    tile = sum(phases[k] for k in PROGRAM_SUMS if k != "programs.after")
    assert tile == pytest.approx(phases["warmup_compile"], rel=0.01)
    # The slot programs and the restore's allocations compile after the
    # span: counted, and outside the tile.
    assert phases["programs.after"] > 0
    assert phases["programs.other"] == 0.0


def test_the_accepted_warmup_reader_reads_what_it_read(warmed):
    """`programs.warmup_s` adds every phase key that starts with
    `warmup`: the stage sums must not reach it."""
    eng, _tasks = warmed
    phases = eng._coldstart.phase_seconds()
    assert _warmup_s_reader()({"setup": {"phases": phases}}) == pytest.approx(
        phases["warmup_compile"] + phases["warmup_restore"])
    assert [k for k in phases if k.startswith("warmup")] == [
        "warmup_compile", "warmup_restore"]


def test_cache_counts_are_mirrored_and_the_flight_event_carries_the_stages(warmed):
    eng, tasks = warmed
    snap = eng._coldstart.snapshot()
    m = eng.metrics
    assert m["warmup_cache_hits"] == snap["programs_cache_hits"]
    assert m["warmup_cache_misses"] == snap["programs_cache_misses"]
    # tests/conftest.py names a cache directory: every program asked it.
    assert m["warmup_cache_hits"] + m["warmup_cache_misses"] >= len(tasks)
    attrs = eng._flight.events("warmup_compile")[0].attrs
    assert list(attrs["stages_s"]) == [k for k in PROGRAM_SUMS if k != "programs.after"]
    assert sum(attrs["stages_s"].values()) == pytest.approx(attrs["seconds"], rel=0.01)
    walls = [r["t1"] - r["t0"] for r in attrs["slowest"]]
    assert len(walls) == min(5, len(tasks)) and walls == sorted(walls, reverse=True)
    json.dumps(attrs)  # the recorder's dump and the Chrome export carry it


def test_parallel_warmup_records_on_its_workers_threads(tmp_path, monkeypatch):
    """Under `warmup_threads` JAX still reports a stage on the thread
    that asked, so every record's stages equal its wall; the sums are
    thread-seconds."""
    monkeypatch.setenv("OMNIA_WARMUP_MANIFEST_DIR", str(tmp_path))
    eng = _engine(warmup_threads=2)
    eng.warmup()
    records = eng._coldstart.program_records()
    assert len(records) == eng.metrics["warmup_programs_total"]
    assert all(r["thread"].startswith("omnia-warmup") for r in records)
    for r in records:
        assert _stage_sum(r) == pytest.approx(
            r["t1"] - r["t0"], abs=1e-9)


def test_one_pair_of_jax_listeners_a_process(warmed):
    pytest.importorskip("jax")
    from jax._src import monitoring

    from omnia_tpu.engine import warmup

    _engine()  # a second engine registers nothing more
    assert monitoring.get_event_listeners().count(warmup._on_jax_event) == 1
    assert monitoring.get_event_duration_listeners().count(warmup._on_jax_event) == 1


def test_the_listener_routes_by_the_calling_thread():
    """A stage event goes to the record open on the thread it arrives
    on; with none, to the warm-up's `other` on warmup()'s own thread;
    and a compile request counts as a serving compile only on a thread
    with no warm-up at work."""
    pytest.importorskip("jax")
    import threading

    from omnia_tpu.engine import warmup

    class Served:  # what _on_jax_event touches of a warmed engine
        _thread = None

        def __init__(self):
            self.metrics = {"programs_compiled_serving": 0}

    served = Served()
    warmup._watch_serving_compiles(served)
    cs = ColdStartTracker()
    cs.begin_phase("warmup_compile")
    both_open = threading.Barrier(2, timeout=30)
    both_fed = threading.Barrier(2, timeout=30)

    def worker(key, event):
        rec = cs.begin_program("decode", key)
        warmup._at_work.cur = (cs, rec)
        try:
            both_open.wait()
            warmup._on_jax_event(event, duration=0.0)
            warmup._on_jax_event(warmup._COMPILE_EVENT)
            both_fed.wait()
        finally:
            warmup._at_work.cur = None
            cs.end_program(rec)

    with warmup._warming(cs):
        threads = [
            threading.Thread(target=worker, args=("a", CACHE_HIT_EVENT)),
            threading.Thread(target=worker, args=("b", CACHE_MISS_EVENT)),
        ]
        for t in threads:
            t.start()
        warmup._on_jax_event(BACKEND_EVENT, duration=0.5)  # no record here
        warmup._on_jax_event(warmup._COMPILE_EVENT)
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert served.metrics["programs_compiled_serving"] == 0
        assert cs.phase_seconds()["programs.other"] == pytest.approx(0.5)
    snap = cs.snapshot()
    assert (snap["programs_cache_hits"], snap["programs_cache_misses"]) == (1, 1)
    assert sorted(r["key"] for r in cs.program_records()) == ["a", "b"]
    assert getattr(warmup._at_work, "cur", None) is None
    warmup._on_jax_event(BACKEND_EVENT, duration=1.0)  # after warmup(): nobody's
    warmup._on_jax_event(warmup._COMPILE_EVENT)
    assert served.metrics["programs_compiled_serving"] == 1
    assert cs.phase_seconds()["programs.after"] == 0.0
