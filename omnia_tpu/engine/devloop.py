"""The decode pipeline's host-side entry and the watchdog's read-back lane.

Jax-free by construction, so the analysis CI job can run its tests
under the poisoned-jax stub:

- ``_InflightChunk``: one dispatched-but-unprocessed decode chunk in the
  scheduler's pipeline (``_inflight``).
- ``ChunkDrainer``: ONE long-lived daemon thread per engine that blocks
  on a chunk's device→host read-back so the engine thread can wait for
  it with a timeout (``EngineConfig.watchdog_s``): one thread for the
  engine's life, not one a chunk.
- ``DevLoopState``: the per-engine holder of that drainer, built only
  when a watchdog is configured.

Threading contract: the drainer thread only ever touches the queue,
and the entry boxes; the engine thread owns the pipeline deque. The
lock guards the ``poisoned`` flag ONLY — every blocking call (queue
get, sleep, the readback itself, Event waits) happens outside it
(the repo's lock-scope rule, omnia_tpu/analysis/locks.py).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Optional


class _InflightChunk:
    """One dispatched decode chunk awaiting host processing.

    ``toks`` is the device [K, B] token buffer, ``active`` the (slot,
    request_id) snapshot at dispatch, ``dispatch_s`` the host dispatch
    wall time.

    A placement's first token rides the same pipeline, in order: ``toks``
    is then the prefill's scalar, ``active`` the one (slot, request_id)
    placed, and ``placement`` (``None`` on a decode chunk) the flight
    recorder's placement note, written when the token is read.

    ``seq`` is the ``seq`` of the ``omnia.engine.decode_dispatch`` span
    the chunk was dispatched under, for the ``.chunk_sync`` and ``.emit``
    that read it (engine/phases.py); ``None`` with no profiler session."""

    __slots__ = ("toks", "active", "dispatch_s", "placement", "seq")

    def __init__(self, toks, active, dispatch_s, placement=None, seq=None):
        self.toks = toks
        self.active = active
        self.dispatch_s = dispatch_s
        self.placement = placement
        self.seq = seq

    @property
    def seq_attr(self) -> dict:
        """``seq`` as an attribute of the spans that read this chunk back."""
        return {} if self.seq is None else {"seq": self.seq}

    @property
    def steps(self) -> int:
        """Decode steps this entry holds for each slot of ``active``: a
        first token stands for one emission, like a step."""
        return 1 if self.placement is not None else int(self.toks.shape[0])


class DrainEntry:
    """One readback handed to the drainer. ``result`` holds the host
    ndarray on success or the raised exception (the engine thread
    re-raises it — a failed readback must take the same recovery path
    as a failed inline sync); ``done`` flips either way."""

    __slots__ = ("toks", "pre_sleep_s", "result", "done")

    def __init__(self, toks, pre_sleep_s: float = 0.0):
        self.toks = toks
        self.pre_sleep_s = pre_sleep_s  # fault-injection seam (chaos parity)
        self.result: Any = None
        self.done = threading.Event()


_STOP = object()


class ChunkDrainer:
    """ONE long-lived ``omnia-chunk-drainer`` daemon thread per engine.

    The engine thread ``submit()``s token buffers; the drainer pulls
    them FIFO, blocks on the device→host readback (``np.asarray``), and
    flips the entry's ``done`` event. ``wait()`` is the watchdog seam:
    a timeout poisons this drainer (the stuck readback thread can never
    be reclaimed — it holds a hung device call), and the owner builds a
    fresh one after recovery."""

    def __init__(self, name: str = "omnia-chunk-drainer"):
        self._queue: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
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
            try:
                # Imported here, not at module top: the state units
                # run on the CI analysis job's bare venv (no numpy);
                # only an actual drain needs the readback.
                import numpy as np

                if entry.pre_sleep_s > 0.0:
                    time.sleep(entry.pre_sleep_s)
                entry.result = np.asarray(entry.toks)
            except Exception as exc:  # noqa: BLE001 - parked for the engine thread
                # A readback can die mid-recovery (the engine freed the
                # donated buffers under us); park the exception — the
                # engine thread re-raises on wait and recovers.
                entry.result = exc
            entry.done.set()

    def submit(self, toks, pre_sleep_s: float = 0.0) -> DrainEntry:
        """Enqueue a readback; returns immediately with the entry."""
        entry = DrainEntry(toks, pre_sleep_s)
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

    def stop(self, timeout: float = 5.0) -> None:
        """Shut the thread down (engine stop/drain). A poisoned drainer's
        thread is wedged in a hung device call — don't wait for it."""
        with self._lock:
            poisoned = self.poisoned
        self._queue.put(_STOP)
        if not poisoned:
            self._thread.join(timeout)


class DevLoopState:
    """Holds the engine's read-back drainer. Exists only when a watchdog
    is configured; the drainer thread is started on first use."""

    def __init__(self):
        self._drainer: Optional[ChunkDrainer] = None

    def get_drainer(self) -> ChunkDrainer:
        """The live drainer, replacing a poisoned one (a watchdog trip
        wedges the old thread in the hung readback — recovery needs a
        fresh lane)."""
        d = self._drainer
        if d is None or d.poisoned:
            if d is not None:
                d.stop()
            d = ChunkDrainer()
            self._drainer = d
        return d

    def stop(self) -> None:
        if self._drainer is not None:
            self._drainer.stop()
            self._drainer = None
