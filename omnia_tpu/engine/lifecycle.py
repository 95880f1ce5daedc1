"""Engine thread lifecycle: loop, graceful drain, crash recovery, health.

The request-lifecycle robustness seam of :class:`InferenceEngine` (same
seam-per-concern layout as the scheduler/session/placement mixins):
starting/stopping the step loop, the housekeeping every step starts with
(cancelled and expired requests reaped), the graceful drain that stops
admission and pages sessions out before shutdown, and the recovery path that turns
a failed (or watchdog-tripped) device step into failed handles plus a
fresh device-state allocation instead of a silently dead engine.
"""

from __future__ import annotations

import logging
import threading
import time

import jax.numpy as jnp

from omnia_tpu.engine.phases import IDLE_SLEEP, phase
from omnia_tpu.engine.types import FinishReason, StreamEvent

logger = logging.getLogger(__name__)


class _LifecycleMixin:
    """Thread-loop / drain / recovery methods of :class:`InferenceEngine`."""

    def start(self):
        if self._thread is not None:
            return
        with self._lock:
            self._draining = False
        self._stop_event.clear()
        self._thread = threading.Thread(
            target=self._loop, name="omnia-engine", daemon=True
        )
        self._thread.start()

    def stop(self, drain: bool = False, drain_timeout_s: float = 30.0):
        """Stop the engine loop. drain=True first performs a graceful
        drain: admission stops (submit sheds OVERLOADED), queued and
        active requests finish — bounded by drain_timeout_s — and the
        idle sessions' KV rows are offloaded to host RAM so a restarted
        engine restores them instead of re-prefilling."""
        if drain:
            with self._lock:
                self._draining = True
            deadline = time.monotonic() + drain_timeout_s
            while time.monotonic() < deadline and self._drain_work_left():
                if self._thread is None:
                    if not self.step():
                        time.sleep(0.001)
                else:
                    time.sleep(0.002)
        wedged = False
        if self._thread is not None:
            self._stop_event.set()
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                # A wedged device step: keep the handle so a retried
                # start() cannot spawn a second loop over the same
                # donated buffers.
                logger.error("engine loop did not stop within 30s; still alive")
                self._healthy = False
                wedged = True
            else:
                self._thread = None
        if drain:
            # Drain-timeout leftovers still get their terminal — even
            # past a wedged join, terminal delivery is pure host-side
            # work and must happen: a client blocked on a handle must
            # never hang past the drain window (the exactly-one-terminal
            # invariant). Queued requests were accepted, so their shed
            # counts as finished; active slots fail with partial counts.
            with self._lock:
                leftover, self._waiting = self._waiting, []
            for req, handle in leftover:
                handle._push(StreamEvent(
                    req.request_id,
                    finish_reason=FinishReason.OVERLOADED,
                    error="engine draining: drain window elapsed while queued",
                    num_prompt_tokens=len(req.prompt_tokens),
                ))
                self.metrics["requests_finished"] += 1
                if self._flight is not None:
                    self._flight.note_terminal(
                        req.request_id, FinishReason.OVERLOADED.value,
                        error="drain window elapsed while queued",
                    )
            if any(s.active for s in self._slots):
                if wedged:
                    # The engine thread is still alive inside a stuck
                    # step and OWNS the active slots: failing them from
                    # this thread could double-push a terminal if the
                    # step unwedges mid-_fail_all. Queued sheds above
                    # are lock-safe; active handles stay with the loop
                    # thread (it delivers terminals if it ever resumes).
                    logger.error(
                        "drain: engine loop wedged with %d active slot(s); "
                        "their handles remain with the stuck loop",
                        sum(1 for s in self._slots if s.active),
                    )
                else:
                    self._fail_all(
                        "engine stopped: drain window elapsed mid-request"
                    )
        if drain and not wedged and self._healthy:
            # The loop has joined (or never ran), so the engine thread's
            # device-state ownership has passed back to this caller.
            self._offload_idle_sessions()
        if self._devloop is not None:
            # Join the watchdog's chunk drainer (engine/devloop.py) —
            # stop() skips a poisoned drainer's thread (it is wedged in
            # the hung readback that tripped the watchdog). A later
            # start() lazily builds a fresh one on first use.
            self._devloop.stop()

    def _drain_work_left(self) -> bool:
        """The drain-wait predicate: queued, mid-placement, or active
        work remains. The queue and the ``_placing`` counter are read in
        ONE critical section — the pre-fix unlocked ``_placing`` read
        could observe a torn claim (queue already popped, counter not
        yet visible) and end the drain with a request in neither
        ledger."""
        with self._lock:
            if self._waiting or self._placing > 0:
                return True
        return self.active_slots() > 0

    def _loop(self):
        while not self._stop_event.is_set():
            try:
                if not self.step():
                    with phase(IDLE_SLEEP):
                        time.sleep(0.001)
            except Exception:  # pragma: no cover - engine must not die silently
                logger.exception("engine step failed")
                self._recover("engine step failed")
                time.sleep(0.1)

    def _housekeeping(self) -> None:
        """What every step does before it schedules: cross-thread queues
        drained, cancelled and expired requests reaped."""
        self._drain_releases()
        self._drain_imports()
        self._drain_prefix_regs()
        self._reap_cancelled()
        self._reap_deadlines()

    def _reap_cancelled(self):
        for i, slot in enumerate(self._slots):
            if slot.active and slot.handle.cancelled:
                self._finish_slot(i, FinishReason.CANCELLED)
        pf = self._prefilling
        if pf is not None and pf.handle.cancelled:
            # Half-prefilled slot (token-budget interleaving): consumed
            # rows stay valid for the session, books are already exact.
            self._abort_prefilling(FinishReason.CANCELLED)
        reaped = []
        with self._lock:
            still = []
            for req, handle in self._waiting:
                if handle.cancelled:
                    handle._push(
                        StreamEvent(req.request_id, finish_reason=FinishReason.CANCELLED)
                    )
                    # A queue-cancelled request is as finished as a slot-
                    # cancelled one: every submit reaches exactly one
                    # terminal event AND one finished count.
                    self.metrics["requests_finished"] += 1
                    reaped.append(req.request_id)
                else:
                    still.append((req, handle))
            self._waiting = still
        if self._flight is not None:
            # Terminal recording ends the request span (tracer export
            # I/O) — never under the engine lock.
            for rid in reaped:
                self._flight.note_terminal(rid, FinishReason.CANCELLED.value)

    def _reap_deadlines(self):
        """Deadline enforcement at the step boundary: queued requests
        past their TTL shed with DEADLINE before placement (they would
        only add latency), and an active slot past its TTL finishes
        early with its partial output (chunk granularity — the boundary
        is checked between dispatches, not inside a compiled chunk).
        Requests without a deadline cost one attribute check here —
        deadline_s=None traffic takes the pre-existing path exactly."""
        now = None
        for i, slot in enumerate(self._slots):
            if slot.active and slot.request.deadline_at is not None:
                now = self.clock() if now is None else now
                if now >= slot.request.deadline_at:
                    self.metrics["deadline_exceeded"] += 1
                    self._finish_slot(i, FinishReason.DEADLINE)
        pf = self._prefilling
        if pf is not None and pf.request.deadline_at is not None:
            now = self.clock() if now is None else now
            if now >= pf.request.deadline_at:
                # Deadline landed mid-prefill (token-budget
                # interleaving): shed with exact partial counts — the
                # pieces consumed so far were metered per dispatch and
                # their rows stay valid for the session.
                self.metrics["deadline_exceeded"] += 1
                self._abort_prefilling(FinishReason.DEADLINE)
        reaped = []
        with self._lock:
            if not any(r.deadline_at is not None for r, _h in self._waiting):
                return
            now = self.clock() if now is None else now
            still = []
            for req, handle in self._waiting:
                if req.deadline_at is not None and now >= req.deadline_at:
                    handle._push(
                        StreamEvent(
                            req.request_id,
                            finish_reason=FinishReason.DEADLINE,
                            num_prompt_tokens=len(req.prompt_tokens),
                        )
                    )
                    # Shed-from-queue is still a terminal: every submit
                    # reaches exactly one final event and one finish.
                    self.metrics["deadline_exceeded"] += 1
                    self.metrics["requests_finished"] += 1
                    reaped.append(req.request_id)
                else:
                    still.append((req, handle))
            self._waiting = still
        if self._flight is not None:
            for rid in reaped:  # span end = I/O, never under the lock
                self._flight.note_terminal(rid, FinishReason.DEADLINE.value)

    def _recover(self, msg: str):
        """Fail in-flight requests and rebuild device state. A raise after
        cache donation leaves self._ck/_cv pointing at deleted arrays, so
        without reallocation every subsequent step would also fail and the
        engine would be permanently dead while looking alive."""
        self._fail_all(msg)
        # In-flight chunk futures share lineage with the dead caches.
        # Entries the drainer is still reading park their exception in
        # the drain box (devloop.ChunkDrainer catches) — dropping them
        # here means nobody ever waits on those boxes again.
        self._inflight.clear()
        # Device-resident session rows died with the caches; host-paged
        # sessions survive (their rows live in host RAM).
        for sess in list(self._sessions.values()):
            if sess.slot is not None:
                self._slots[sess.slot].session_id = None
                sess.slot = None
                sess.token_ids = []
        try:
            self._init_device_state()
            self.metrics["recoveries"] += 1
            # A watchdog trip marks the engine unhealthy before raising;
            # a recovery that actually reallocated device state restores
            # readiness (the platform analog: probe fails during the
            # incident, passes once the pod is serving again).
            self._healthy = True
        except Exception:
            logger.exception("engine recovery failed; marking unhealthy")
            self._healthy = False

    def healthy(self) -> bool:
        """False once recovery itself failed — the readiness signal
        (platform analog of the reference runtime's Health capabilities)."""
        return self._healthy

    def _fail_all(self, msg: str):
        # A half-prefilled placement (token-budget interleaving) is
        # neither queued nor active — fail it explicitly or its handle
        # would hang past recovery/drain.
        self._fail_prefilling(msg)
        for i, slot in enumerate(self._slots):
            if slot.active:
                # Carry the partial progress: a consumer (and the
                # coordinator's resubmit rule) must be able to tell a
                # zero-token death from a mid-stream one.
                slot.handle._push(
                    StreamEvent(
                        slot.request.request_id,
                        finish_reason=FinishReason.ERROR,
                        error=msg,
                        num_prompt_tokens=len(slot.request.prompt_tokens),
                        num_generated_tokens=slot.generated,
                    )
                )
                # An ERROR terminal is as finished as any other — the
                # books must balance for every accepted submit.
                self.metrics["requests_finished"] += 1
                if self._flight is not None:
                    self._flight.note_terminal(
                        slot.request.request_id, FinishReason.ERROR.value,
                        tokens=slot.generated, error=msg,
                        first_token_at=slot.handle.first_token_at,
                    )
                self._release_slot_seed(slot)
                self._free_slot(slot)
        # No request is live any more. One stale positive temperature
        # would hold the sampler's gate open (ops/sampling.py) for every
        # later step of an engine that is started again; written without
        # reading the old array, which recovery may find dead.
        self._temp = jnp.zeros_like(self._temp)
