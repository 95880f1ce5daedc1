"""Device-resident decode loop: the host half of the token ring.

The compiled side of the ring lives in ``programs.py`` (deadline-step
budget + grammar-EOS masking inside the chunk scan, ``lax.cond``
early-out once every slot is done). THIS module owns everything the
ring needs on the host, jax-free by construction so the analysis CI
job can run its tests under the poisoned-jax stub:

- ``_InflightChunk``: the pipeline entry — dispatched-but-unprocessed
  decode chunks used to be bare 3-tuples; the ring adds the deadline
  mirror and the drain handle, so the entry grew a name.
- ``ChunkDrainer``: ONE long-lived daemon thread per engine that turns
  device→host token readback into an async queue. It replaces BOTH the
  ring's background drain AND the old per-chunk ``omnia-chunk-sync``
  watchdog threads (one short-lived thread per decode chunk — thread
  churn on the hot path).
- ``RingGate``: the online A/B self-gate (the spec-decode ``_SpecGate``
  idiom, PR 10) — probes realized tok/s with async drain permitted vs
  suppressed and disables the ring per engine when it does not pay.
- ``DevLoopState``: the per-engine container. ``decode_ring=0`` with
  no watchdog builds NONE of this (the guarded true no-op).

Threading contract: the drainer thread only ever touches the queue,
the entry boxes, and its own stats; the engine thread owns the
pipeline deque. The stats lock guards counters ONLY — every blocking
call (queue get, sleep, the readback itself, Event waits) happens
outside it (the repo's lock-scope rule, omnia_tpu/analysis/locks.py).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Any, Callable, Optional


def validate_decode_ring(cfg) -> None:
    """Reject unservable ring configs at construction (EngineConfig and
    MockEngine share this): 0 is off, >= 2 is a ring; 1 cannot overlap
    a drain with the next dispatch, so it is a misconfiguration, not a
    degraded mode."""
    ring = getattr(cfg, "decode_ring", 0)
    if ring < 0:
        raise ValueError(f"decode_ring must be >= 0, got {ring}")
    if ring == 1:
        raise ValueError(
            "decode_ring=1 is a one-deep ring (drain can never overlap "
            "dispatch) — use 0 (off) or >= 2"
        )


class _InflightChunk:
    """One dispatched decode chunk awaiting host processing.

    ``toks`` is the device [K, B] token buffer (or the host ndarray in
    the mock), ``active`` the (slot, request_id) snapshot at dispatch,
    ``dispatch_s`` the host dispatch wall time. Ring extras: ``dl_steps``
    mirrors the deadline-step budget the compiled scan was given (host
    emission must finish a slot at the same step the device masked it),
    ``entry`` the drainer handle when the readback was started at
    dispatch (None = the processing path syncs inline)."""

    __slots__ = ("toks", "active", "dispatch_s", "dl_steps", "entry")

    def __init__(self, toks, active, dispatch_s,
                 dl_steps=None, entry: Optional["DrainEntry"] = None):
        self.toks = toks
        self.active = active
        self.dispatch_s = dispatch_s
        self.dl_steps = dl_steps
        self.entry = entry


class DrainEntry:
    """One readback handed to the drainer. ``result`` holds the host
    ndarray on success or the raised exception (the engine thread
    re-raises it — a failed readback must take the same recovery path
    as a failed inline sync); ``done`` flips either way."""

    __slots__ = ("toks", "pre_sleep_s", "on_drained", "result", "done")

    def __init__(self, toks, pre_sleep_s: float = 0.0,
                 on_drained: Optional[Callable[[Any, float], None]] = None):
        self.toks = toks
        self.pre_sleep_s = pre_sleep_s  # fault-injection seam (chaos parity)
        self.on_drained = on_drained
        self.result: Any = None
        self.done = threading.Event()


_STOP = object()


class ChunkDrainer:
    """ONE long-lived ``omnia-chunk-drainer`` daemon thread per engine.

    The engine thread ``submit()``s token buffers; the drainer pulls
    them FIFO, blocks on the device→host readback (``np.asarray`` — the
    only thread that ever does for drained chunks), and flips the
    entry's ``done`` event. ``wait()`` is the watchdog seam: a timeout
    poisons this drainer (the stuck readback thread can never be
    reclaimed — it holds a hung device call), and the owner builds a
    fresh one after recovery.

    Replaces the old per-chunk ``omnia-chunk-sync`` daemon threads the
    watchdog path used to spawn: same timeout semantics, zero thread
    churn on the hot path.

    ``span`` opens the context each readback runs in: the engine passes
    its ``omnia.engine.ring_drain`` profiler phase (engine/phases.py —
    this module stays jax-free), and what it yields is told the tokens
    read when it is truthy."""

    def __init__(self, name: str = "omnia-chunk-drainer",
                 span: Callable[[], Any] = contextlib.nullcontext):
        self._span = span
        self._queue: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
        self.drains = 0         # guarded-by: _lock
        self.drain_s = 0.0      # guarded-by: _lock
        self.poisoned = False   # guarded-by: _lock
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        while True:
            entry = self._queue.get()
            if entry is _STOP:
                return
            t0 = time.monotonic()
            try:
                # Imported here, not at module top: the gate/state/mock
                # units run on the CI analysis job's bare venv (no
                # numpy); only an actual drain needs the readback.
                import numpy as np

                if entry.pre_sleep_s > 0.0:
                    time.sleep(entry.pre_sleep_s)
                with self._span() as sp:
                    arr = np.asarray(entry.toks)
                    if sp:
                        sp.set_metadata(tokens=int(arr.size))
                entry.result = arr
            except Exception as exc:  # noqa: BLE001 - parked for the engine thread
                # A readback can die mid-recovery (the engine freed the
                # donated buffers under us); park the exception — the
                # engine thread re-raises on wait and recovers.
                entry.result = exc
                arr = None
            took = time.monotonic() - t0
            entry.done.set()
            with self._lock:
                self.drains += 1
                self.drain_s += took
            if entry.on_drained is not None:
                try:
                    entry.on_drained(arr, took)
                except Exception:  # noqa: BLE001 - observability must not kill the drainer
                    pass

    def submit(self, toks, pre_sleep_s: float = 0.0,
               on_drained=None) -> DrainEntry:
        """Enqueue a readback; returns immediately with the entry."""
        entry = DrainEntry(toks, pre_sleep_s, on_drained)
        self._queue.put(entry)
        return entry

    def wait(self, entry: DrainEntry,
             timeout: Optional[float] = None) -> Optional[Any]:
        """Block until the entry drains. Returns the host array, raises
        the parked exception, or returns None on timeout — after which
        this drainer is poisoned (its thread is wedged in the hung
        readback) and must be replaced."""
        ok = entry.done.wait(timeout)
        if not ok:
            with self._lock:
                self.poisoned = True
            return None
        if isinstance(entry.result, BaseException):
            raise entry.result
        return entry.result

    def stats(self) -> tuple[int, float]:
        with self._lock:
            return self.drains, self.drain_s

    def stop(self, timeout: float = 5.0) -> None:
        """Shut the thread down (engine stop/drain). A poisoned drainer's
        thread is wedged in a hung device call — don't wait for it."""
        with self._lock:
            poisoned = self.poisoned
        self._queue.put(_STOP)
        if not poisoned:
            self._thread.join(timeout)


class RingGate:
    """Online self-gate for the token ring: duty-cycle probe of realized
    decode throughput with async drain permitted vs suppressed.

    The spec-decode ``_SpecGate`` state machine verbatim (PR 10):
    PROBE_ASYNC(window ticks) → PROBE_SYNC(window) → decide →
    HOLD_ON/HOLD_OFF(window × hold_factor) → re-probe. A tick is one
    processed decode chunk; a phase's rate is tokens/wall-seconds
    across it, so the comparison prices in everything the ring changes
    — drainer handoff, host/device overlap, early-exit savings. Both
    arms run the SAME compiled ring programs (greedy streams stay
    bit-identical); only WHERE the readback blocks differs. Async must
    be at least ``margin`` of the sync rate to stay on. Host-side and
    jax-free; the engine skips ticking under an injected logical clock
    (multihost lockstep), where a wall-clock decision could diverge
    the replicated step streams."""

    PROBE_ASYNC, PROBE_SYNC, HOLD_ON, HOLD_OFF = range(4)
    _NAMES = {PROBE_ASYNC: "probe_async", PROBE_SYNC: "probe_sync",
              HOLD_ON: "on", HOLD_OFF: "off"}

    def __init__(self, window: int, hold_factor: int = 8,
                 margin: float = 0.98):
        self.window = window
        self.hold_factor = hold_factor
        self.margin = margin
        self.state = self.PROBE_ASYNC
        self.ticks = 0
        self.phase_t0: Optional[float] = None
        self.phase_tok0 = 0
        self.rate_async: Optional[float] = None
        self.rate_sync: Optional[float] = None
        self.decisions = 0
        self.disables = 0

    def allows_async(self) -> bool:
        return self.state in (self.PROBE_ASYNC, self.HOLD_ON)

    def state_code(self) -> int:
        """Stable metric encoding: 0 = probing, 1 = on, 2 = off."""
        if self.state == self.HOLD_ON:
            return 1
        if self.state == self.HOLD_OFF:
            return 2
        return 0

    def tick(self, now: float, tokens: int) -> bool:
        """Advance one processed chunk; returns whether async drain is
        permitted for the next dispatch."""
        if self.window <= 0:
            return True
        if self.phase_t0 is None:
            self.phase_t0, self.phase_tok0 = now, tokens
        self.ticks += 1
        probing = self.state in (self.PROBE_ASYNC, self.PROBE_SYNC)
        limit = self.window if probing else self.window * self.hold_factor
        if self.ticks >= limit:
            rate = (tokens - self.phase_tok0) / max(now - self.phase_t0, 1e-9)
            if self.state == self.PROBE_ASYNC:
                self.rate_async = rate
                self.state = self.PROBE_SYNC
            elif self.state == self.PROBE_SYNC:
                self.rate_sync = rate
                self.decisions += 1
                if (self.rate_async or 0.0) >= rate * self.margin:
                    self.state = self.HOLD_ON
                else:
                    self.state = self.HOLD_OFF
                    self.disables += 1
            else:
                # Hold expired: refresh that mode's rate and re-probe.
                if self.state == self.HOLD_ON:
                    self.rate_async = rate
                else:
                    self.rate_sync = rate
                self.state = self.PROBE_ASYNC
            self.ticks = 0
            self.phase_t0, self.phase_tok0 = now, tokens
        return self.allows_async()

    def report(self) -> dict:
        """Bench/debug snapshot (aux.devloop.gate)."""
        r = lambda v: None if v is None else round(v, 2)  # noqa: E731
        return {
            "state": self._NAMES[self.state],
            "rate_async_tok_s": r(self.rate_async),
            "rate_sync_tok_s": r(self.rate_sync),
            "decisions": self.decisions,
            "disables": self.disables,
        }


# RingGate probe phase length, in processed chunks. Fixed (not a knob):
# the spec gate's window is traffic-shaped, but a chunk already
# aggregates decode_chunk steps, so a short window sees plenty of work.
_GATE_WINDOW = 32

# Default per-step seconds for the deadline→steps conversion before the
# first chunk lands (EMA warm-start; ~5 ms is a mid-size CPU step).
_STEP_EMA_INIT = 5e-3


class DevLoopState:
    """Per-engine device-resident-loop state. Exists when the ring is on
    OR a watchdog is configured (the drainer replaces the old per-chunk
    watchdog threads either way); ``decode_ring=0`` with no watchdog
    builds nothing at all."""

    def __init__(self, ring: int, gate: bool = True,
                 drain_span: Callable[[], Any] = contextlib.nullcontext):
        self.ring = ring
        self._drain_span = drain_span
        # Undrained-chunk capacity: the pipeline may hold this many
        # dispatched-but-unprocessed chunks before dispatch must stall
        # (ring_full_stalls). Watchdog-only engines (ring=0) keep the
        # pre-ring pipeline policy untouched.
        self.capacity = max(2, ring) if ring > 0 else 0
        self.gate: Optional[RingGate] = (
            RingGate(_GATE_WINDOW) if ring > 0 and gate else None
        )
        # Host EMA of one decode STEP's wall time, feeding the
        # deadline→remaining-steps conversion for the in-scan deadline
        # budget. Engine-thread-owned.
        self.step_ema_s = _STEP_EMA_INIT
        self._drainer: Optional[ChunkDrainer] = None

    def get_drainer(self) -> ChunkDrainer:
        """The live drainer, replacing a poisoned one (a watchdog trip
        wedges the old thread in the hung readback — recovery needs a
        fresh lane)."""
        d = self._drainer
        if d is None or d.poisoned:
            if d is not None:
                d.stop()
            d = ChunkDrainer(span=self._drain_span)
            self._drainer = d
        return d

    def drainer_if_live(self) -> Optional[ChunkDrainer]:
        d = self._drainer
        if d is None or d.poisoned:
            return None
        return d

    def observe_step_time(self, per_step_s: float) -> None:
        """Fold one chunk's realized per-step wall time into the EMA."""
        self.step_ema_s += 0.2 * (per_step_s - self.step_ema_s)

    def async_engaged(self, wall_clock: bool) -> bool:
        """Whether the NEXT dispatch should hand its readback to the
        drainer. Gate decisions only bind under the wall clock — a
        lockstep engine (injected logical clock) keeps async drain
        unconditionally (deterministic: no wall-clock branch)."""
        if self.ring <= 0:
            return False
        if self.gate is None or not wall_clock:
            return True
        return self.gate.allows_async()

    def stop(self) -> None:
        if self._drainer is not None:
            self._drainer.stop()
            self._drainer = None
