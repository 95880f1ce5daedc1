"""Cold-start instrumentation: submit-to-ready phases, a record a
warm-up program, and the warmup manifest.

Cold start is the repo's worst number (BENCH_r01: 97.5 s of warmup against
a 99 ms TTFT) and the direct blocker for scale-to-zero — a pod is useless
until every serving shape is compiled. Phases say which part of bring-up
the seconds fall in; inside the longest one, ``warmup_compile``, a record
a program says which of five costs they are. Jax-free pieces:

- :class:`ColdStartTracker` — a thread-safe record of the bring-up
  phases (``backend_init`` → ``weights_load`` → ``warmup_compile`` →
  ``warmup_restore`` → ready), with byte-level weight-streaming progress
  and a compiled-programs counter. Phases may OVERLAP (weight streaming
  runs while param-free programs compile — the whole point); the tracker
  keeps one span per phase and reports the most recently begun
  unfinished phase as "current". The engine mirrors every snapshot field
  into its stable metrics, the runtime Health response carries it while
  the server reports "initializing", and the operator capability gate
  turns it into a status condition — the next r02-style hang is
  attributed to a phase, not a 390 s timeout.

- **A record a warm-up program** (``begin_program`` / ``end_program``,
  :func:`record_stages`): ``{family, key, thread, t0, t1, trace_s,
  lower_s, compile_s, cache_load_s, run_s, cache}`` on the tracker's
  clock. The five stages are cut from the intervals that JAX's own
  duration events cover (engine/warmup.py owns the ``jax.monitoring``
  listener and hands each event to the record open on its thread), so
  they equal ``t1 - t0`` exactly and a nested ``jit``'s trace is counted
  once. Their sums ride in ``phase_seconds()`` / ``snapshot()["phases_s"]``
  under ``programs.*`` keys — sums, not phases — beside
  ``programs_cache_hits`` / ``programs_cache_misses``: what the
  persistent compile cache did, where the manifest's counts below say
  only what the last start listed.

- :class:`WarmupManifest` — a persisted list of every (program family,
  shape) the engine compiled on first start, keyed by a content hash of
  (model config, mesh, bucket set, KV knobs). A restarting pod loads the
  manifest for its key and knows — before compiling anything — exactly
  which programs the persistent XLA compile cache should serve, so the
  ``warmup_manifest_hits`` / ``warmup_manifest_misses`` metrics say
  whether this start is a warm restore or a cold compile. A config
  change produces a different key and an all-miss start, by design.

Jax-free by contract (enforced by the ``jaxfree`` analysis rule): the
tracker also backs :class:`~omnia_tpu.engine.mock.MockEngine` parity and
the CI analysis job's poisoned-jax subset.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Callable, Optional

logger = logging.getLogger(__name__)

#: Bring-up phases, in nominal order. ``PHASE_CODES`` maps each to the
#: integer exported through the ``warmup_phase`` metric (dashboards get
#: a monotone gauge; 0 = not started, len-1 = ready).
PHASES = (
    "idle",            # 0: engine object exists, nothing begun
    "backend_init",    # 1: accelerator backend/runtime coming up
    "weights_load",    # 2: checkpoint streaming to device
    "warmup_compile",  # 3: AOT-compiling the serving program set
    "warmup_restore",  # 4: restoring pristine device state post-warmup
    "ready",           # 5: submit-to-ready complete
)
PHASE_CODES = {name: i for i, name in enumerate(PHASES)}

#: The ``jax.monitoring`` names a program's record is built from
#: (engine/warmup.py's listener hands them over; nothing here imports
#: jax). Three report a duration as the stage ends, on the thread that
#: asked; the two cache events arrive inside a backend interval.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: Spans ``compile_or_get_cached``: the backend's compile on a miss, the
#: read and load of the executable on a hit.
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
STAGE_EVENTS = frozenset({
    TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT, CACHE_HIT_EVENT, CACHE_MISS_EVENT,
})

#: A record's stages, in the order they tile its wall.
STAGES = ("trace", "lower", "compile", "cache_load", "run")
#: The sums' keys in ``phase_seconds()`` / ``snapshot()["phases_s"]``.
#: Sums, not phases, and none may start with ``warmup``: the benchmark's
#: ``programs.warmup_s`` adds every key with that prefix. All but the
#: last tile the ``warmup_compile`` span of a serial warm-up (under
#: ``warmup_threads`` the stages are thread-seconds): ``drain`` is the
#: closing wait for the device, ``other`` what stage events covered
#: inside the span with no record open on their thread. ``after`` is the
#: same between the span's end and ``warmup()``'s return (the slot
#: programs, the restore's allocations), outside the tile.
PROGRAM_SUMS = tuple(
    f"programs.{name}" for name in STAGES + ("drain", "other", "after")
)


def record_stages(t0: float, t1: float, events: list) -> dict:
    """The five stages of one program record ``[t0, t1]`` from the
    ``(event, t, d)`` it was fed: ``{trace_s, lower_s, compile_s,
    cache_load_s, run_s, cache}``. Pure.

    Built from intervals, not by adding durations (an inner ``jit``'s
    trace is reported inside its caller's). A duration event that
    arrives at ``t`` covers ``[t - d, t]``. A backend interval with a
    cache hit inside it is a load, else a compile; lowering intervals
    are ``lower_s``; the wall up to the end of the last interval that
    neither covers is ``trace_s`` (tracing and the operands' Python),
    and what follows to ``t1`` is ``run_s``. So the five add up to
    ``t1 - t0``, whatever a task compiles on the way (eager operands
    are programs too; their intervals add into the same record).
    ``cache``: ``miss`` if anything was compiled, else ``hit`` if
    anything was loaded, else ``none`` (no compile asked: the process
    already held the program)."""
    hits = [t for event, t, _d in events if event == CACHE_HIT_EVENT]
    cut = t0
    cover = []  # (start, end, stage)
    for event, t, d in events:
        if event not in (TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT):
            continue
        a, b = max(t0, t - d), min(t1, t)
        if b <= a:
            continue
        cut = max(cut, b)
        if event == BACKEND_EVENT:
            hit = any(t - d <= h <= t for h in hits)
            cover.append((a, b, "cache_load" if hit else "compile"))
        elif event == LOWER_EVENT:
            cover.append((a, b, "lower"))
    # Where two overlap (they should not), the backend's interval wins.
    cover.sort(key=lambda c: c[2] == "lower")
    out = dict.fromkeys(STAGES, 0.0)
    edges = sorted({t0, cut, *(x for a, b, _s in cover for x in (a, b))})
    for a, b in zip(edges, edges[1:]):
        stage = next((s for ca, cb, s in cover if ca <= a and b <= cb), "trace")
        out[stage] += b - a
    out["run"] = t1 - cut
    stages = {s for _a, _b, s in cover}
    cache = ("miss" if "compile" in stages
             else "hit" if "cache_load" in stages else "none")
    return {**{f"{name}_s": out[name] for name in STAGES}, "cache": cache}


def _union_s(intervals: list) -> float:
    """Seconds that some interval of ``[(start, end)]`` covers."""
    total, hi = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > hi:
            total += b - max(a, hi)
            hi = b
    return total


def _phases_s(now: float, spans: dict, stage_sums: Optional[dict],
              unowned: dict) -> dict:
    """phase -> wall seconds (a running phase up to ``now``), then the
    :data:`PROGRAM_SUMS` where a warm-up kept records (pure; caller
    holds the lock; tens of intervals to union)."""
    out = {
        name: round((span[1] if span[1] is not None else now) - span[0], 6)
        for name, span in spans.items()
    }
    if stage_sums is not None:
        sums = {**stage_sums, **{k: _union_s(v) for k, v in unowned.items()}}
        out.update(
            (key, round(sums[key.partition(".")[2]], 6)) for key in PROGRAM_SUMS
        )
    return out


class _OpenProgram:
    """A program record while its task runs. Only the thread that opened
    it feeds it, so ``events`` needs no lock."""

    __slots__ = ("family", "key", "thread", "t0", "events")

    def __init__(self, family: str, key: str, thread: str, t0: float):
        self.family, self.key, self.thread, self.t0 = family, key, thread, t0
        self.events: list = []


def _pick_phase(ready: bool, spans: dict) -> str:
    """Current phase from the span table (pure; caller holds the lock):
    the latest begun-and-unfinished phase, else the latest finished one
    (a between-phases probe never reads "idle" mid-bring-up)."""
    if ready:
        return "ready"
    current = "idle"
    for name, span in spans.items():
        if span[1] is None:
            current = name  # latest begun, still running
    if current == "idle" and spans:
        current = list(spans)[-1]
    return current


class ColdStartTracker:
    """Thread-safe bring-up progress: phase spans, weight bytes, and the
    compiled-programs counter.

    Writers are the engine's init/warmup seams (possibly several threads
    when weight streaming overlaps compilation); readers are the metrics
    mirror, the runtime Health handler, and bench — every mutation and
    snapshot runs under one internal lock, held only for O(1) work.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        # phase -> [start_mono, end_mono | None]; insertion order is
        # begin order, which is what "current phase" reads back.
        self._spans: dict[str, list] = {}  # guarded-by: _lock
        self._weights_loaded = 0  # guarded-by: _lock
        self._weights_total = 0  # guarded-by: _lock
        self._programs_total = 0  # guarded-by: _lock
        self._programs_done = 0  # guarded-by: _lock
        self._manifest_hits = 0  # guarded-by: _lock
        self._manifest_misses = 0  # guarded-by: _lock
        self._ready = False  # guarded-by: _lock
        # Program records (one warm-up's; begin_programs() starts over).
        # None until a warm-up keeps records: the mock's never does, and
        # its phases_s carry no ``programs.*`` key.
        self._stage_sums: Optional[dict] = None  # guarded-by: _lock
        self._records: list[dict] = []  # guarded-by: _lock
        self._unowned: dict[str, list] = {"other": [], "after": []}  # guarded-by: _lock
        self._programs_open = False  # guarded-by: _lock
        self._cache_hits = 0  # guarded-by: _lock
        self._cache_misses = 0  # guarded-by: _lock

    # -- writers ---------------------------------------------------------

    def begin_phase(self, name: str) -> None:
        if name not in PHASE_CODES:
            raise ValueError(f"unknown cold-start phase {name!r}")
        with self._lock:
            self._spans[name] = [self._clock(), None]
            # Re-entering a phase (a second warmup on a live engine)
            # un-readies the tracker so probes read the phase actually
            # running, not a stale "ready".
            self._ready = False

    def end_phase(self, name: str) -> float:
        """Close the phase span; returns its duration in seconds (0.0
        for a phase that was never begun — callers stay unconditional)."""
        with self._lock:
            span = self._spans.get(name)
            if span is None:
                return 0.0
            if span[1] is None:
                span[1] = self._clock()
            return span[1] - span[0]

    def note_weights(self, loaded_bytes: int, total_bytes: int) -> None:
        """Weight-streaming progress (monotone; the checkpoint loader's
        ``progress_cb`` lands here, once per streamed tensor)."""
        with self._lock:
            self._weights_loaded = max(self._weights_loaded, int(loaded_bytes))
            self._weights_total = max(self._weights_total, int(total_bytes))

    def set_programs_total(self, n: int) -> None:
        """Declare THIS warmup's task count; resets the done counter so
        a re-warmup (warmup(sessions=False) then a full warmup()) can
        never report done > total."""
        with self._lock:
            self._programs_total = int(n)
            self._programs_done = 0

    def note_program(self, n: int = 1) -> int:
        """One warmup task compiled+executed; returns the running count."""
        with self._lock:
            self._programs_done += n
            return self._programs_done

    def note_manifest(self, hits: int, misses: int) -> None:
        with self._lock:
            self._manifest_hits = int(hits)
            self._manifest_misses = int(misses)

    def mark_ready(self) -> None:
        with self._lock:
            self._ready = True

    # -- writers: program records ----------------------------------------

    def now(self) -> float:
        """The tracker's clock (``time.monotonic``: the flight
        recorder's, so records lie on its axis)."""
        return self._clock()

    def begin_programs(self) -> None:
        """A warm-up starts keeping records (after it began
        ``warmup_compile``). Until ``end_programs()`` a stage event with
        no record open counts under ``other`` or ``after``."""
        with self._lock:
            self._stage_sums = dict.fromkeys(STAGES + ("drain",), 0.0)
            self._records = []
            self._unowned = {"other": [], "after": []}
            self._cache_hits = self._cache_misses = 0
            self._programs_open = True

    def end_programs(self) -> None:
        with self._lock:
            self._programs_open = False

    def begin_program(self, family: str, key: str) -> _OpenProgram:
        """Open the record of one warm-up task on the calling thread."""
        return _OpenProgram(
            family, key, threading.current_thread().name, self._clock()
        )

    def note_stage_event(self, rec: Optional[_OpenProgram], event: str,
                         d: float = 0.0) -> None:
        """One of :data:`STAGE_EVENTS` as it arrives (``d`` its duration,
        0 for a cache event), for the record open on the thread or, with
        none, for the warm-up's ``other`` / ``after``."""
        t = self._clock()
        if rec is not None:
            rec.events.append((event, t, d))
            return
        with self._lock:
            if not self._programs_open:
                return
            if event == CACHE_HIT_EVENT:
                self._cache_hits += 1
            elif event == CACHE_MISS_EVENT:
                self._cache_misses += 1
            else:
                span = self._spans.get("warmup_compile")
                inside = span is not None and span[1] is None
                self._unowned["other" if inside else "after"].append((t - d, t))

    def end_program(self, rec: _OpenProgram) -> dict:
        """Close ``rec``: cut its stages, keep it, add it to the sums."""
        t1 = self._clock()
        record = {
            "family": rec.family, "key": rec.key, "thread": rec.thread,
            "t0": rec.t0, "t1": t1, **record_stages(rec.t0, t1, rec.events),
        }
        hits = sum(1 for event, _t, _d in rec.events if event == CACHE_HIT_EVENT)
        misses = sum(1 for event, _t, _d in rec.events if event == CACHE_MISS_EVENT)
        with self._lock:
            self._records.append(record)
            self._cache_hits += hits
            self._cache_misses += misses
            if self._stage_sums is not None:
                for name in STAGES:
                    self._stage_sums[name] += record[f"{name}_s"]
        return record

    def note_drain(self, seconds: float) -> None:
        """The closing wait for the device over the warm-up's states."""
        with self._lock:
            if self._stage_sums is not None:
                self._stage_sums["drain"] += seconds

    # -- readers ---------------------------------------------------------

    def current_phase(self) -> str:
        with self._lock:
            return _pick_phase(self._ready, self._spans)

    def phase_seconds(self) -> dict:
        """phase -> wall seconds (running phases measured up to now),
        then the :data:`PROGRAM_SUMS` once a warm-up kept records."""
        with self._lock:
            return _phases_s(
                self._clock(), self._spans, self._stage_sums, self._unowned
            )

    def program_records(self) -> list[dict]:
        """The closed records of the last warm-up, in closing order."""
        with self._lock:
            return [dict(r) for r in self._records]

    def slowest_programs(self, n: int = 5) -> list[dict]:
        """The ``n`` records with the longest wall, longest first."""
        return sorted(
            self.program_records(), key=lambda r: r["t0"] - r["t1"]
        )[:n]

    def snapshot(self) -> dict:
        """One consistent progress view — the shape the Health wire, the
        engine metrics mirror, and bench ``aux.coldstart`` all read."""
        with self._lock:
            phase = _pick_phase(self._ready, self._spans)
            return {
                "phase": phase,
                "phase_code": PHASE_CODES[phase],
                "weights_bytes_loaded": self._weights_loaded,
                "weights_bytes_total": self._weights_total,
                "programs_total": self._programs_total,
                "programs_done": self._programs_done,
                "manifest_hits": self._manifest_hits,
                "manifest_misses": self._manifest_misses,
                "programs_cache_hits": self._cache_hits,
                "programs_cache_misses": self._cache_misses,
                "phases_s": _phases_s(
                    self._clock(), self._spans, self._stage_sums, self._unowned
                ),
            }


# ---------------------------------------------------------------------------
# Warmup manifest
# ---------------------------------------------------------------------------


def manifest_dir() -> Optional[str]:
    """Directory warmup manifests persist under: the explicit override
    (``OMNIA_WARMUP_MANIFEST_DIR`` — also what the jax-free tests and the
    mock use), else the enabled persistent compile-cache dir (manifests
    describe that cache's contents, so they live and die with it), else
    None — manifest bookkeeping then runs in memory only (every start is
    an all-miss cold start, honestly reported)."""
    env = os.environ.get("OMNIA_WARMUP_MANIFEST_DIR")
    if env:
        return env
    from omnia_tpu.utils.compile_cache import enabled_dir

    return enabled_dir()


class WarmupManifest:
    """Load/store the per-config list of compiled (family, shape) keys.

    One JSON file per manifest key under :func:`manifest_dir`; writes are
    atomic (tmp + rename) and best-effort — a read-only cache dir
    degrades to cold-start accounting, never to a failed warmup."""

    @staticmethod
    def manifest_key(payload: dict) -> str:
        """Content hash of the config payload (model config, mesh,
        bucket set, KV knobs...). Canonical-JSON sha256, so two
        processes with the same serving config derive the same key with
        no coordination."""
        import hashlib

        blob = json.dumps(payload, sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:32]

    @staticmethod
    def _path(directory: str, key: str) -> str:
        return os.path.join(directory, f"warmup_manifest_{key}.json")

    @classmethod
    def load(cls, directory: Optional[str], key: str) -> Optional[list]:
        """The program-key list persisted for this config key, or None
        (no manifest: first start, different config, or no cache dir)."""
        if not directory:
            return None
        try:
            with open(cls._path(directory, key), encoding="utf-8") as f:
                doc = json.load(f)
            programs = doc.get("programs")
            return list(programs) if isinstance(programs, list) else None
        except (OSError, ValueError):
            return None

    @classmethod
    def store(cls, directory: Optional[str], key: str, programs: list,
              meta: Optional[dict] = None) -> bool:
        """Persist (merging with any existing list — warmup(sessions=
        False) must not erase the sessionful families a previous full
        warmup recorded). Returns False when the dir is unwritable."""
        if not directory:
            return False
        existing = cls.load(directory, key) or []
        merged = sorted(set(existing) | set(programs))
        doc = {
            "key": key,
            "programs": merged,
            "meta": dict(meta or {}),
            "saved_at": time.time(),
        }
        path = cls._path(directory, key)
        try:
            os.makedirs(directory, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
            return True
        except OSError:
            logger.warning("warmup manifest not persisted under %s "
                           "(unwritable?) — next start re-discovers the "
                           "program set", directory, exc_info=True)
            return False


def manifest_bookkeeping(
    directory: Optional[str], key: str, program_keys: list,
    tracker: ColdStartTracker, meta: Optional[dict] = None,
) -> tuple[int, int]:
    """The one manifest transaction both engines run at warmup: load the
    persisted list for this config key, count hits (programs the last
    start already compiled — the persistent compile cache should serve
    them) and misses (new shapes this start must compile), record both
    on the tracker, and persist the current program set. Returns
    (hits, misses)."""
    listed = WarmupManifest.load(directory, key)
    if listed is None:
        hits, misses = 0, len(program_keys)
    else:
        listed_set = set(listed)
        hits = sum(1 for p in program_keys if p in listed_set)
        misses = len(program_keys) - hits
    tracker.note_manifest(hits, misses)
    WarmupManifest.store(directory, key, program_keys, meta=meta)
    return hits, misses
