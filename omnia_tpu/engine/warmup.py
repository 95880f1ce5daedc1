"""AOT warmup: the serving program set compiled before readiness.

The engine's TTFT discipline — no compile on the request path — makes
cold start pay the full compile bill up front, and until this module the
bill was strictly serial: every (program family, bucket shape) compiled
one at a time inside ``InferenceEngine.warmup()``. This mixin owns the
warmup pipeline:

- **One task list, two executions.** ``_warmup_tasks`` enumerates every
  (family, shape) as a self-contained closure over a :class:`_WarmupState`
  (the donated KV operands a warmup call chains through). With
  ``EngineConfig.warmup_threads == 0`` the tasks run in order on the
  caller thread against the engine's own cache arrays — the serial path,
  a guarded true no-op. With ``warmup_threads = N`` they run across a
  bounded thread pool: XLA compilation releases the GIL, so N program
  families compile concurrently. Each concurrent worker chains donated
  operands through its OWN scratch cache copy (``_alloc_kv_state``), so
  donation never sees a buffer twice; all non-donated operands (params,
  the per-slot vectors, grammar tables) are shared read-only. The traced
  signatures are identical either way — jit keys on avals, not on which
  thread dispatched — so serial and parallel warmup produce the same
  compiled program set and the same post-warmup state
  (tests/test_coldstart.py pins both).

- **Manifest + progress.** Every warmup runs the manifest transaction
  (:func:`~omnia_tpu.engine.coldstart.manifest_bookkeeping`): the
  persisted program list for this config key says whether this start is
  a warm restore (persistent compile cache should serve every listed
  shape) or a cold compile, and the ``warmup_*`` metrics mirror the
  tracker so readiness progress is observable mid-warmup.

- **A record a task.** Each task runs under a program record on the
  cold-start tracker (``_run_warmup_task``): this module owns the
  process's one pair of ``jax.monitoring`` listeners and a thread-local
  that says which warm-up, and which record, is at work on the calling
  thread, and hands every trace, lowering, backend-compile and cache
  event JAX reports there to :mod:`~omnia_tpu.engine.coldstart`, which
  cuts the record's five stages from them. After ``warmup()`` returns
  the same listener counts ``programs_compiled_serving``.

- **Param-free overlap.** ``_warmup_paramfree`` warms the families that
  take no model params (session offload/restore, prefix-pool transfers,
  page-run programs) — the engine runs it on a side thread while the
  checkpoint loader streams weights (``_load_params_overlapped``), so a
  checkpoint-backed cold start pays max(weights, KV-program compiles)
  for those families instead of their sum.

Behavior-neutral like the serial warmup always was: all device state and
metrics warmup touched are restored afterwards (``warmup_restore``
phase), so warmup cannot perturb request sampling.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
import time
import weakref
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from omnia_tpu.engine.coldstart import (
    PHASE_CODES,
    PROGRAM_SUMS,
    STAGE_EVENTS,
    STAGES,
    WarmupManifest,
    manifest_bookkeeping,
    manifest_dir,
)
from omnia_tpu.engine.placement import ACTIVATED, RELEASED
from omnia_tpu.engine.types import MAX_DEVICE_STOP_IDS, SamplingParams
from omnia_tpu.models.kv_quant import kv_device, kv_host

logger = logging.getLogger(__name__)

#: What JAX records for every program asked of the compiler while its
#: persistent cache is on, hit or miss (benchmark/harness/compiles.py
#: counts the same event): after warmup() a request is a shape that
#: warm-up did not cover.
_COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
# JAX's listeners are process-wide and cannot be taken off again, so the
# process registers ONE pair (bare events, durations), when its first
# engine is built, and the engines it serves are held weakly.
_warmed: "weakref.WeakSet" = weakref.WeakSet()  # guarded-by: _warmed_lock
_warmed_lock = threading.Lock()
_listening = False  # guarded-by: _warmed_lock
# The warm-up at work on this thread: ``cur = (tracker, record)`` inside a
# task, ``(tracker, None)`` on warmup()'s own thread between tasks, unset
# otherwise. JAX reports a stage on the thread that asked for it, so
# parallel warm-up attributes rightly.
_at_work = threading.local()


def _on_jax_event(event: str, duration: float = 0.0, **_kw) -> None:
    """Both listeners. A stage event goes to the warm-up at work on the
    calling thread (coldstart.py keeps the books); a compile request on
    a thread with none is a warmed engine's serving compile."""
    cur = getattr(_at_work, "cur", None)
    if cur is not None:
        if event in STAGE_EVENTS:
            cur[0].note_stage_event(cur[1], event, duration)
        return
    if event != _COMPILE_EVENT:
        return
    with _warmed_lock:
        engines = list(_warmed)
    me = threading.current_thread()
    for eng in engines:
        # The event does not say who asked. An engine's request path
        # compiles on its own loop thread; an engine stepped inline has
        # none and counts every program the process asks for.
        if eng._thread is None or eng._thread is me:
            eng.metrics["programs_compiled_serving"] += 1


def listen_to_jax() -> None:
    """Register the process's one pair of listeners (idempotent; engine
    construction calls it)."""
    global _listening
    with _warmed_lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_jax_event)
            jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
            _listening = True


def _watch_serving_compiles(engine) -> None:
    """From now on ``programs_compiled_serving`` counts for ``engine``."""
    with _warmed_lock:
        _warmed.add(engine)


@contextlib.contextmanager
def _warming(cs):
    """``cs`` keeps program records until the block ends: what JAX
    reports on this thread outside a task's record meanwhile is this
    warm-up's ``other`` / ``after``."""
    cs.begin_programs()
    _at_work.cur = (cs, None)
    try:
        yield
    finally:
        _at_work.cur = None
        cs.end_programs()


def _program_line(r: dict) -> str:
    """One record for the log: its wall and its stages."""
    stages = " ".join(f"{name}={r[name + '_s']:.2f}" for name in STAGES)
    return (f"{r['family']}:{r['key']} {r['t1'] - r['t0']:.2f}s "
            f"({stages}, cache {r['cache']})")


#: Families whose programs take no model params — compilable while the
#: checkpoint is still streaming (the weight/compile overlap set).
PARAMFREE_FAMILIES = frozenset({"session", "prefix", "pages"})


class _WarmupState:
    """The donated operands one warmup worker chains its calls through:
    the slots' cache (the family's tuple of arrays) and, when the pool
    exists, the prefix-pool pair. Everything else a warmup call takes is
    shared read-only self state. ``ck`` / ``cv`` name the pair family's
    two arrays, for the tasks only that family has."""

    __slots__ = ("cache", "pk", "pv")

    def __init__(self, cache, pk=None, pv=None):
        self.cache, self.pk, self.pv = tuple(cache), pk, pv

    @property
    def ck(self):
        return self.cache[0]

    @ck.setter
    def ck(self, value):
        self.cache = (value,) + self.cache[1:]

    @property
    def cv(self):
        return self.cache[1]

    @cv.setter
    def cv(self, value):
        self.cache = (self.cache[0], value)


class _WarmupMixin:
    """Warmup pipeline methods of :class:`InferenceEngine`."""

    # -- task inventory --------------------------------------------------

    def _warmup_tasks(
        self, sessions: bool, families: Optional[frozenset] = None
    ) -> list[tuple[str, str, Callable]]:
        """The (family, shape-key, closure) inventory for one warmup.
        Closures defer every self-state read to call time, so the
        param-free subset is buildable before device state exists.
        Each closure mirrors the corresponding serial warmup call
        EXACTLY (operand sources and scalar types included — jit caches
        key on weak_type, so a drifted scalar would warm a program the
        request path never dispatches)."""
        cfg = self.cfg
        tasks: list[tuple[str, str, Callable]] = []

        def add(family: str, key: str, fn: Callable) -> None:
            if families is None or family in families:
                tasks.append((family, key, fn))

        def sargs(xp=jnp):
            # First-token sampling operands (the prefill/extend/mixed
            # `*sargs` tail): per-slot key data + greedy scalars, plus
            # the zero grammar bias when support is on (the request
            # path ALWAYS passes the bias operand then). ``xp``: where
            # the caller's host scalars come from (placement.py hands
            # numpy to the prefill and extend programs).
            out = (
                self._key_data[0], xp.float32(0.0), xp.float32(1.0),
                xp.int32(0),
            )
            if self._gr_on:
                out = out + (self._gbias_zero,)
            return out

        def gargs():
            return (
                (self._gstate, self._gtable, self._gactive)
                if self._gr_on else ()
            )

        def decode_task(k):
            def run(st):
                fn = self._decode_fns[k]
                args = (
                    self.params, *st.cache, self._tokens,
                    self._positions, self._active, self._budget,
                    self._stop_ids, self._key_data, self._temp,
                    self._top_p, self._top_k,
                )
                out = fn(*args, *gargs())
                st.cache = tuple(out[:len(st.cache)])
            return run

        for k in sorted(self._decode_fns, reverse=True):
            add("decode", f"chunk{k}", decode_task(k))

        usable = set(cfg.usable_buckets())
        # Suffix prefill after a shared-prefix seed rides the extend
        # family, so an enabled pool warms it even for sessionless
        # serving (the bench's shared-prefix scenario).
        extend_shapes = (
            usable | {1}
            if sessions or cfg.prefix_cache_slots > 0
            else set()
        )

        def bucket_task(b):
            def run(st):
                zero = np.int32(0)
                toks = np.zeros((1, b), np.int32)
                pos = np.arange(b, dtype=np.int32)[None, :]
                if b in usable:
                    *cache, _, _ = self._prefill_insert_fn(
                        self.params, *st.cache, toks, pos, zero,
                        np.int32(b - 1), *sargs(np)
                    )
                    st.cache = tuple(cache)
                    if (
                        self._prefill_ring_fn is not None
                        and b >= cfg.long_prefill_threshold
                        and b % cfg.sp == 0
                    ):
                        last, *chunks = self._prefill_ring_fn(
                            self.params, toks, pos, np.int32(b - 1)
                        )
                        sp = SamplingParams()
                        out = self._insert_fn(
                            *st.cache, *chunks, 0,
                            last, self._sampling_key(0, sp),
                            jnp.float32(sp.temperature),
                            jnp.float32(sp.top_p), jnp.int32(sp.top_k),
                            *self._grammar_args(None, sp),
                        )
                        st.cache = tuple(out[:len(st.cache)])
                if b in extend_shapes:
                    st.cache = tuple(self._extend_nosample_fn(
                        self.params, *st.cache, toks, pos, zero, zero
                    ))
                    *cache, _, _ = self._extend_fn(
                        self.params, *st.cache, toks, pos, zero, zero,
                        zero, *sargs(np)
                    )
                    st.cache = tuple(cache)
            return run

        for b in sorted(usable | extend_shapes):
            add("prefill", f"bucket{b}", bucket_task(b))

        def mixed_task(b):
            def run(st):
                # Fused mixed prefill+decode steps (token-budget
                # interleaving): both variants per piece bucket with
                # the request path's exact operand types.
                zero = jnp.int32(0)
                toks = jnp.zeros((1, b), jnp.int32)
                pos = jnp.arange(b, dtype=jnp.int32)[None, :]

                def common(st):
                    # Re-read st per call: the caches are DONATED, so
                    # the first dispatch consumes the pair the closure
                    # would otherwise have captured.
                    return (
                        self.params, st.ck, st.cv, self._tokens,
                        self._positions, self._active, self._budget,
                        self._stop_ids, self._key_data, self._temp,
                        self._top_p, self._top_k, toks, pos, zero, zero,
                    )

                out = self._mixed_fns[b](*common(st), *gargs())
                st.ck, st.cv = out[0], out[1]
                out = self._mixed_sample_fns[b](
                    *common(st), jnp.int32(b - 1), *sargs(), *gargs()
                )
                st.ck, st.cv = out[0], out[1]
            return run

        for b in cfg.mixed_prefill_buckets():
            add("mixed", f"bucket{b}", mixed_task(b))

        if sessions and cfg.max_sessions > 0:
            # (An engine that keeps no session never offloads or restores
            # one: ``sessions`` then warms the extend family alone, for
            # prompts placed in pieces.)
            def session_task(r):
                def run(st):
                    # The operands eviction and restore dispatch with
                    # (sessions.py): a python-int slot, rows that have
                    # been to the host and back — except across
                    # processes, where no one host can read the rows.
                    k, v = self._offload_fn(st.ck, st.cv, 0, r)
                    if jax.tree.leaves(k)[0].is_fully_addressable:
                        k, v = kv_device(kv_host(k)), kv_device(kv_host(v))
                    st.ck, st.cv = self._restore_fn(st.ck, st.cv, k, v, 0)
                return run

            for r in cfg.restore_buckets():
                add("session", f"rows{r}", session_task(r))

        if cfg.kv_pages > 0:
            # Paged-only programs: page copy (CoW), the fixed-shape
            # table-row sync, and the prefix host-tier page-run
            # transfer buckets. All run against all-trash state; the
            # closing restore rebuilds clean books.
            def page_copy_task(st):
                from omnia_tpu.models.paged_kv import PagedKV

                st.ck, st.cv = self._page_copy_fn(st.ck, st.cv, 0, 0)
                row = jnp.zeros((cfg.num_page_positions(),), jnp.int32)
                st.ck = PagedKV(st.ck.pool, st.ck.table.at[0].set(row))
                st.cv = PagedKV(st.cv.pool, st.cv.table.at[0].set(row))

            add("pages", "copy", page_copy_task)
            if cfg.prefix_cache_slots > 0:
                def page_run_task(b):
                    def run(st):
                        idx = jnp.zeros((b,), jnp.int32)
                        k, v = self._gather_pages_fn(st.ck, st.cv, idx)
                        st.ck, st.cv = self._scatter_pages_fn(
                            st.ck, st.cv, idx,
                            kv_device(kv_host(k)), kv_device(kv_host(v)),
                        )
                    return run

                for b in cfg.page_run_buckets():
                    add("pages", f"run{b}", page_run_task(b))

        if cfg.prefix_cache_slots > 0 and self._prefix_store_fn is not None:
            # Pool transfers per prefix bucket: store (slot→pool), seed
            # (pool→slot), demote (pool→host), and the host-hit restore
            # path with the SAME scalar types placement dispatches
            # (python-int slot/pool indices, static row bucket). Absent
            # under kv_pages — the paged prefix cache is table rewrites
            # plus the page-run programs above.
            def prefix_task(b):
                def run(st):
                    st.pk, st.pv = self._prefix_store_fn(
                        st.pk, st.pv, st.ck, st.cv, 0, 0, b
                    )
                    st.ck, st.cv = self._prefix_seed_fn(
                        st.ck, st.cv, st.pk, st.pv, 0, 0, b
                    )
                    k, v = self._prefix_offload_fn(st.pk, st.pv, 0, b)
                    st.ck, st.cv = self._restore_fn(
                        st.ck, st.cv,
                        kv_device(kv_host(k)), kv_device(kv_host(v)), 0,
                    )
                return run

            for b in cfg.prefix_buckets():
                add("prefix", f"bucket{b}", prefix_task(b))

        if self._verify_fn is not None:
            # Speculative family (spec_decode.py owns the operand set):
            # pure verify + verify+decode fusion in one task, the
            # mixed-spec twins per piece bucket.
            def spec_window_operands():
                B, K1 = cfg.num_slots, cfg.spec_window() + 1
                vtoks = jnp.zeros((B, K1), jnp.int32)
                vpos = jnp.broadcast_to(
                    jnp.arange(K1, dtype=jnp.int32)[None], (B, K1)
                )
                vstart = jnp.zeros((B,), jnp.int32)
                vmask = jnp.zeros((B,), jnp.bool_)
                return vtoks, vpos, vstart, vmask

            def verify_task(st):
                vtoks, vpos, vstart, vmask = spec_window_operands()
                st.ck, st.cv, _ = self._verify_fn(
                    self.params, st.ck, st.cv, vtoks, vpos, vstart, *gargs()
                )
                out = self._verify_decode_fn(
                    self.params, st.ck, st.cv, self._tokens,
                    self._positions, self._active, self._budget,
                    self._stop_ids, self._key_data, self._temp,
                    self._top_p, self._top_k, vtoks, vpos, vstart, vmask,
                    *gargs(),
                )
                st.ck, st.cv = out[0], out[1]

            add("spec", "verify", verify_task)

            def mixed_spec_task(b):
                def run(st):
                    zero = jnp.int32(0)
                    vtoks, vpos, vstart, vmask = spec_window_operands()
                    toks = jnp.zeros((1, b), jnp.int32)
                    pos = jnp.arange(b, dtype=jnp.int32)[None, :]

                    def common(st):
                        # Donated caches: re-read st per call (see
                        # mixed_task above).
                        return (
                            self.params, st.ck, st.cv, self._tokens,
                            self._positions, self._active, self._budget,
                            self._stop_ids, self._key_data, self._temp,
                            self._top_p, self._top_k, toks, pos, zero,
                            zero, vtoks, vpos, vstart, vmask,
                        )

                    out = self._mixed_spec_fns[b](*common(st), *gargs())
                    st.ck, st.cv = out[0], out[1]
                    out = self._mixed_spec_sample_fns[b](
                        *common(st), jnp.int32(b - 1), *sargs(), *gargs(),
                    )
                    st.ck, st.cv = out[0], out[1]
                return run

            for b in sorted(self._mixed_spec_fns):
                add("spec", f"mixed{b}", mixed_spec_task(b))

        return tasks

    # -- worker states ---------------------------------------------------

    def _alloc_warmup_state(self) -> _WarmupState:
        """A fresh scratch state at the engine's exact layout/sharding —
        what each ADDITIONAL parallel warmup worker chains its donated
        operands through (worker 0 steals the engine's own arrays; the
        closing restore reallocates them regardless)."""
        return _WarmupState(*self._alloc_kv_state())

    def _run_warmup_task(self, task, st: _WarmupState) -> None:
        """One task under its record (coldstart.py): the stage events
        JAX reports on this thread meanwhile are the record's."""
        family, key, fn = task
        cs = self._coldstart
        rec = cs.begin_program(family, key)
        _at_work.cur = (cs, rec)
        try:
            fn(st)
        finally:
            _at_work.cur = (cs, None)
            cs.end_program(rec)

    def _run_warmup_serial(self, tasks) -> list[_WarmupState]:
        st = _WarmupState(self._cache, self._pk, self._pv)
        for task in tasks:
            self._run_warmup_task(task, st)
            self.metrics["warmup_programs_done"] = self._coldstart.note_program()
        return [st]

    def _run_warmup_parallel(self, tasks, threads: int) -> list[_WarmupState]:
        """Dispatch the task list over a bounded pool. States are pooled
        through a queue: at most `threads` workers run at once, so at
        most `threads` states (one of them the engine's own arrays) are
        ever allocated — the documented peak-memory bound."""
        import queue as queue_mod
        from concurrent.futures import ThreadPoolExecutor

        states: list[_WarmupState] = [
            _WarmupState(self._cache, self._pk, self._pv)
        ]
        idle: "queue_mod.SimpleQueue[_WarmupState]" = queue_mod.SimpleQueue()
        idle.put(states[0])
        states_lock = threading.Lock()

        def run(task):
            # A fresh state's allocation is this warm-up's too (`other`).
            _at_work.cur = (self._coldstart, None)
            st = None
            try:
                try:
                    st = idle.get_nowait()
                except queue_mod.Empty:
                    st = self._alloc_warmup_state()
                    with states_lock:
                        states.append(st)
                self._run_warmup_task(task, st)
            finally:
                if st is not None:
                    idle.put(st)
                _at_work.cur = None
            self.metrics["warmup_programs_done"] = self._coldstart.note_program()

        with ThreadPoolExecutor(
            max_workers=threads, thread_name_prefix="omnia-warmup"
        ) as pool:
            futures = [pool.submit(run, t) for t in tasks]
            for f in futures:
                f.result()  # propagate the first failure
        return states

    # -- manifest --------------------------------------------------------

    def _warmup_manifest_key(self) -> str:
        """Content key of everything that determines the compiled
        program set and its lowerings: the model config, the mesh
        shape, the bucket sets, and the KV knobs. Host-side-only knobs
        (thread counts, event-ring capacities, admission bounds) are
        excluded — they change no traced program, so a restart that
        only tunes them still reads the same manifest."""
        ecfg = dataclasses.asdict(self.cfg)
        for host_only in (
            "warmup_threads", "flight_events", "max_queue", "watchdog_s",
            "decode_pipeline", "spec_gate_window",
        ):
            ecfg.pop(host_only, None)
        return WarmupManifest.manifest_key({
            "model": dataclasses.asdict(self.model_cfg),
            "engine": ecfg,
            "backend": jax.default_backend(),
        })

    # -- overlap with weight streaming ----------------------------------

    def _warmup_paramfree(self) -> None:
        """Compile the param-free families (session/prefix/page KV
        transfers) on a scratch state — safe before model params exist,
        which is exactly when it runs: on a side thread while the
        checkpoint loader streams weights. The later full warmup()
        re-dispatches these families and finds their jit caches warm."""
        tasks = self._warmup_tasks(
            sessions=self.cfg.max_sessions > 0, families=PARAMFREE_FAMILIES
        )
        if not tasks:
            return
        st = self._alloc_warmup_state()
        for _family, _key, fn in tasks:
            fn(st)
        jax.block_until_ready(st.cache)

    def _load_params_overlapped(self, loader: Callable):
        """Run the params loader with weight-streaming progress tracked,
        overlapping the param-free program compiles on a side thread —
        a checkpoint-backed cold start pays max(weights, KV-transfer
        compiles) for those families instead of their sum. Loaders that
        accept ``progress_cb`` get per-tensor byte progress
        (models/checkpoint.load_params does)."""
        import inspect

        cs = self._coldstart
        cs.begin_phase("weights_load")
        failed: list[Exception] = []

        def overlap():
            try:
                self._warmup_paramfree()
            except Exception as e:  # re-raised on the caller below
                failed.append(e)

        t = threading.Thread(
            target=overlap, name="omnia-warmup-overlap", daemon=True,
        )
        t.start()
        try:
            kwargs = {}
            try:
                if "progress_cb" in inspect.signature(loader).parameters:
                    kwargs["progress_cb"] = cs.note_weights
            except (TypeError, ValueError):
                pass  # builtins/partials without a signature: no progress
            params = loader(**kwargs)
        finally:
            t.join()
        if failed:
            # A program that does not compile here will not compile in
            # warmup() either; fail bring-up where the cause is.
            raise failed[0]
        seconds = cs.end_phase("weights_load")
        if self._flight is not None:
            snap = cs.snapshot()
            self._flight.note_init_phase("weights_load", {
                "seconds": seconds,
                "bytes": snap["weights_bytes_loaded"],
            })
        return params

    # -- orchestrator ----------------------------------------------------

    def warmup(self, sessions: bool = True):
        """AOT-compile decode (all chunk variants) + all usable prefill
        buckets + the sessionful extend/offload/restore programs (called
        before ready — the request path must never hit a compile).
        Behavior-neutral: all device state and metrics it touched are
        restored afterwards.

        sessions=False skips the extend/offload/restore family — only
        valid for serving without session KV reuse AND with every prompt
        fitting the largest prefill bucket (the chunked-prefill path uses
        extend too). The bench uses it to keep warmup inside the driver
        budget on a cold compile cache.

        With ``EngineConfig.warmup_threads > 0`` the compile tasks run
        across a bounded thread pool (same program set, same traced
        signatures, same restored state — just concurrent compiles);
        progress is observable mid-warmup through the ``warmup_*``
        metrics and the cold-start tracker."""
        t0 = time.monotonic()
        cs = self._coldstart
        metrics_before = dict(self.metrics)
        tasks = self._warmup_tasks(sessions)
        cs.set_programs_total(len(tasks))
        cs.begin_phase("warmup_compile")
        self.metrics["warmup_phase"] = PHASE_CODES["warmup_compile"]
        self.metrics["warmup_programs_total"] = len(tasks)
        self.metrics["warmup_programs_done"] = 0

        program_keys = [f"{family}:{key}" for family, key, _fn in tasks]
        hits, misses = manifest_bookkeeping(
            manifest_dir(), self._warmup_manifest_key(), program_keys, cs,
            meta={"model": self.model_cfg.name,
                  "backend": jax.default_backend()},
        )
        self.metrics["warmup_manifest_hits"] = hits
        self.metrics["warmup_manifest_misses"] = misses

        threads = max(int(self.cfg.warmup_threads), 0)
        with _warming(cs):
            if threads <= 0:
                states = self._run_warmup_serial(tasks)
            else:
                states = self._run_warmup_parallel(tasks, threads)
            t_drain = cs.now()
            for st in states:
                # Donated chains may still be executing asynchronously;
                # the compile phase ends when the device is quiesced.
                jax.block_until_ready(st.cache)
            cs.note_drain(cs.now() - t_drain)
            compile_s = cs.end_phase("warmup_compile")
            if self._flight is not None:
                phases_s = cs.phase_seconds()
                self._flight.note_init_phase("warmup_compile", {
                    "seconds": compile_s, "programs": len(tasks),
                    "threads": threads, "manifest_hits": hits,
                    "manifest_misses": misses,
                    # `after` is still filling: the closing log line has it.
                    "stages_s": {k: phases_s[k] for k in PROGRAM_SUMS[:-1]},
                    "slowest": cs.slowest_programs(),
                })

            self._warmup_slot_programs()
            # The warmup states' arrays go before the restore allocates
            # their successors: a cache that fills most of the chip (a
            # 7.6 GB one beside 4.9 GB of weights) cannot stand beside a
            # second one.
            del states, st

            cs.begin_phase("warmup_restore")
            self.metrics["warmup_phase"] = PHASE_CODES["warmup_restore"]
            # Restore everything warmup wrote (cache contents, PRNG
            # streams, positions, metrics) so warmup cannot perturb
            # request sampling.
            self._init_device_state()
            self.metrics.update(metrics_before)
            restore_s = cs.end_phase("warmup_restore")
        cs.mark_ready()
        self._sync_coldstart_metrics()
        if self._flight is not None:
            self._flight.note_init_phase(
                "warmup_restore", {"seconds": restore_s}
            )
        snap = cs.snapshot()
        logger.info(
            "engine warmup done in %.1fs (%d programs, %d decode variants, "
            "threads=%d, manifest %d hit / %d miss, sessions=%s); stage "
            "seconds %s; compile cache %d hit / %d miss; slowest: %s",
            time.monotonic() - t0, len(tasks), len(self._decode_fns),
            threads, hits, misses, sessions,
            " ".join(f"{k.partition('.')[2]}={snap['phases_s'][k]:.2f}"
                     for k in PROGRAM_SUMS),
            snap["programs_cache_hits"], snap["programs_cache_misses"],
            "; ".join(_program_line(r) for r in cs.slowest_programs()),
        )
        _watch_serving_compiles(self)

    def _warmup_slot_programs(self) -> None:
        """The two programs that write a slot's device state
        (programs.py ``activate_slot`` / ``release_slot``), called as
        placement and a finish call them: un-warmed, the first request
        would pay their compile in its TTFT. They donate the per-slot
        vectors every warm-up worker reads, so they run here, after the
        workers, and not as tasks. Operand types MATCH the request path
        (numpy rows of the host's scalars, the prefill's device scalars
        for the token and the key) — jit caches key on them. What else
        still updates a vector op by op is touched too: the mixed step's
        parked position (interleave.py), speculation's re-sync
        (spec_decode.py) and a grammar's attach."""
        self._run_slot_program(
            self._activate_slot_fn, ACTIVATED, jnp.int32(0), self._key_data[0],
            np.asarray([0, 0, 0, 1] + [-1] * MAX_DEVICE_STOP_IDS, np.int32),
            np.asarray([0.0, 1.0], np.float32),
        )
        self._run_slot_program(
            self._release_slot_fn, RELEASED, np.asarray([0, 0], np.int32)
        )
        self._tokens = self._tokens.at[0].set(jnp.int32(0))
        self._positions = self._positions.at[0].set(0)
        if self._gr_on:
            # Grammar placement scatters: FSM state + gate (the exact
            # scalar-set programs placement dispatches). The table
            # upload is NOT warmable here: placement writes [S, V] rows
            # where S is each grammar's own state count — a different
            # scatter shape per grammar — so a [max_states, V] set would
            # trace a program placement never runs while transiently
            # building a multi-GB host array at large vocabularies.
            self._gstate = self._gstate.at[0].set(0)
            self._gactive = self._gactive.at[0].set(True)
        jax.block_until_ready(self._active)

    def _sync_coldstart_metrics(self) -> None:
        """Mirror the tracker into the stable metrics keys (the warmup
        progress surface dashboards and the Health wire read)."""
        snap = self._coldstart.snapshot()
        self.metrics["warmup_phase"] = snap["phase_code"]
        self.metrics["warmup_programs_total"] = snap["programs_total"]
        self.metrics["warmup_programs_done"] = snap["programs_done"]
        self.metrics["warmup_manifest_hits"] = snap["manifest_hits"]
        self.metrics["warmup_manifest_misses"] = snap["manifest_misses"]
        self.metrics["warmup_cache_hits"] = snap["programs_cache_hits"]
        self.metrics["warmup_cache_misses"] = snap["programs_cache_misses"]
        self.metrics["weights_bytes_total"] = snap["weights_bytes_total"]
        self.metrics["weights_bytes_loaded"] = snap["weights_bytes_loaded"]
