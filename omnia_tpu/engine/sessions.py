"""Slot and session-KV bookkeeping for the serving engine.

A *slot* is one row of the fixed decode batch; a *session* is a logical
conversation whose KV rows outlive individual requests so the next turn
prefills only the tokens past its longest common prefix with what is
already cached (multi-turn serving cost becomes O(new tokens), SURVEY
§7 — the reference has no analog because its providers re-send full
history upstream every turn, internal/runtime/message.go).

Residency moves through three states: resident in a device slot, paged
out to host RAM (``host_k``/``host_v``), or empty. The engine thread
owns every structure here; cross-thread requests (``release_session``)
are queued under the engine lock and applied at the next step.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

# SessionExport lives in types.py (jax-free home — the mock fleet
# builds payloads without the device stack) and is re-exported here,
# its documented location beside the offload/restore code it rides.
from omnia_tpu.engine.types import Request, RequestHandle, SessionExport
from omnia_tpu.models.kv_quant import kv_device, kv_host


class _Slot:
    __slots__ = (
        "request",
        "handle",
        "length",
        "generated",
        "max_total",
        "stop_ids",
        "session_id",
        "emitted",
        "spec_index",
        "spec_ema",
        "spec_k",
        "spec_cool",
        "seeded_from",
        "grammar",
        "gr_view",
        "gr_state",
        "freed_at",
    )

    def __init__(self):
        self.request: Optional[Request] = None
        self.handle: Optional[RequestHandle] = None
        self.length = 0          # tokens currently in the slot's KV rows
        self.generated = 0
        self.max_total = 0       # generation cap (request max_tokens)
        self.stop_ids: frozenset[int] = frozenset()
        self.session_id: Optional[str] = None  # pinned session (may be idle)
        self.emitted: list[int] = []           # tokens emitted this request
        self.spec_index = None   # lazy per-request n-gram index (spec_decode)
        # Per-slot adaptive speculation depth (spec_decode.py): the
        # accept-rate EMA, the current proposal depth it drives, and
        # the re-probe cooldown once the depth has collapsed to 0.
        # Reset by placement via spec_reset; dead while spec is off.
        self.spec_ema = 0.0
        self.spec_k = 0
        self.spec_cool = 0
        # Shared-prefix pool entry a SESSIONLESS request seeded from —
        # pins the entry until finish (sessionful seeds pin via
        # _SessionKV.seeded_from instead). Engine releases before clear().
        self.seeded_from: Optional[int] = None
        # Grammar-constrained decoding: the request's TokenGrammar, its
        # sampler view for this engine's vocab/stop ids, and the host
        # mirror of the device FSM state (metrics + finish accounting).
        self.grammar = None
        self.gr_view = None
        self.gr_state = 0
        # When the slot last became free on the host's books
        # (time.monotonic()): stamped by ``_free_slot`` while a flight
        # recorder is on, read at the next claim. 0.0, "since the engine's
        # start", for a slot never used.
        self.freed_at = 0.0

    def clear(self):
        self.request = None
        self.handle = None
        self.length = 0
        self.generated = 0
        self.emitted = []
        self.spec_index = None
        self.spec_ema = 0.0
        self.spec_k = 0
        self.spec_cool = 0
        self.seeded_from = None
        self.grammar = None
        self.gr_view = None
        self.gr_state = 0

    def spec_reset(self, spec_decode: int, spec_decode_max: int) -> None:
        """Arm the adaptive-depth controller for a newly placed request:
        depth starts at the configured base and the EMA starts where
        that depth sits on the curve, so the first observations move it
        rather than fight an optimistic prior."""
        if spec_decode_max > 0:
            self.spec_k = min(spec_decode, spec_decode_max)
            self.spec_ema = self.spec_k / spec_decode_max
        else:
            self.spec_k = spec_decode
            self.spec_ema = 1.0
        self.spec_cool = 0

    @property
    def active(self) -> bool:
        return self.request is not None


class _SessionKV:
    """A logical session's KV residency record.

    Exactly one of (slot is not None) / (host_k is not None) / neither
    holds: resident in a device slot, paged out to host RAM, or empty.
    token_ids are the tokens whose KV rows are KNOWN valid — on finish the
    last emitted token is conservatively excluded (its row write is not
    guaranteed when a slot finishes mid-decode-chunk), costing one
    re-prefilled token per turn instead of a correctness proof over chunk
    timing.
    """

    __slots__ = (
        "session_id", "token_ids", "slot", "host_k", "host_v", "last_used",
        "seeded_from",
    )

    def __init__(self, session_id: str, now: Optional[float] = None):
        self.session_id = session_id
        self.token_ids: list[int] = []
        self.slot: Optional[int] = None
        # [L, R, H, D] padded rows; a QuantKV of numpy leaves when the
        # engine runs kv_quant (pages inherit the cache representation).
        self.host_k: Optional[np.ndarray] = None
        self.host_v: Optional[np.ndarray] = None
        self.last_used = time.monotonic() if now is None else now
        # Shared-prefix pool entry this session seeded from: pins the
        # entry's rows for the session's lifetime (dropping the session
        # decrefs — the pool may then evict them).
        self.seeded_from: Optional[int] = None


class _SessionMixin:
    """Session-KV scheduling methods of :class:`InferenceEngine`.

    Mixed into the engine class — operates on the engine's slots, session
    registry, and paging programs. Split out so the session-residency
    policy (slot pick, LRU eviction, host paging, cap enforcement) reads
    as one unit apart from the decode scheduler.
    """

    def _choose_slot(self, request: Request) -> tuple[Optional[int], bool]:
        """The slot a request would get now, and whether taking it evicts
        an idle session: ``(None, False)`` if it must wait. Pure — it
        reads host slot state only, so the scheduler may ask it with
        chunks in flight (``_queued_placeable``).

        Priority: the session's own resident slot (but never while a
        previous request on the same session is still decoding there) →
        a free unpinned slot → the slot of the least-recently-used idle
        session, which the claim pages out to host (``_slot_for``)."""
        sid = request.session_id if self.cfg.max_sessions > 0 else None
        if sid is not None:
            sess = self._sessions.get(sid)
            if sess is not None and sess.slot is not None:
                if self._slots[sess.slot].active:
                    return None, False  # same-session turn still in flight
                return sess.slot, False
        for i, s in enumerate(self._slots):
            if not s.active and s.session_id is None:
                return i, False
        idle_pinned = [
            (self._sessions[s.session_id].last_used, i)
            for i, s in enumerate(self._slots)
            if not s.active and s.session_id is not None
            and s.session_id in self._sessions
        ]
        if idle_pinned:
            return min(idle_pinned)[1], True
        return None, False  # every slot is decoding

    def _free_slot(self, slot: _Slot) -> None:
        """``slot.clear()``: the slot is free on the host's books from
        here, and with a flight recorder on it says since when, for the
        request that takes it next (``LatencyBreakdown.slot_wait_s``)."""
        slot.clear()
        if self._flight is not None:
            slot.freed_at = time.monotonic()

    def _free_since(self, request: Request, slot_idx: int) -> float:
        """Since when the host's books have shown a slot this request
        could take (``t_free`` of its claim, with a flight recorder on):
        its session's own slot if that is where it goes, else the
        longest-free of the slots not decoding. Not the stamp of the slot
        it takes: ``_choose_slot`` takes the lowest-numbered free one,
        which is often the one freed last."""
        sess = self._sessions.get(request.session_id) if request.session_id else None
        if sess is not None and sess.slot == slot_idx:
            return self._slots[slot_idx].freed_at
        return min(s.freed_at for s in self._slots if not s.active)

    def _slot_for(self, request: Request) -> Optional[int]:
        """Claim the slot ``_choose_slot`` picks, evicting the idle
        session pinned there to host if that is the price. Runs a device
        program then, so only with the pipeline flushed."""
        i, evict = self._choose_slot(request)
        if evict:
            self._offload_session(self._sessions[self._slots[i].session_id])
        return i

    def _offload_session(self, sess: _SessionKV) -> None:
        """Page an idle session's valid KV rows to host RAM and unpin its
        slot. Rows move in a fixed restore-bucket shape so the transfer
        program is compile-stable.

        Seeded-length accounting: when the shared-prefix pool fully
        covers the session's valid rows, the host copy is elided — the
        rows are reconstructible by a device-side pool seed (cheaper
        than a host restore), so the session just forgets them and the
        next turn rebuilds through the pool-match path."""
        slot_idx = sess.slot
        valid = len(sess.token_ids)
        if valid > 0 and self._prefix_covered(sess.token_ids):
            sess.token_ids = []
            self.metrics["prefix_cache_offload_elisions"] += 1
        elif valid > 0:
            rows = self.cfg.restore_bucket_for(valid)
            k, v = self._offload_fn(self._ck, self._cv, slot_idx, rows)
            # Host pages keep the cache representation (int8 rows +
            # scales under kv_quant — half the bf16 page bytes and
            # transfer time, restored verbatim with zero extra drift).
            sess.host_k = kv_host(k)
            sess.host_v = kv_host(v)
            self.metrics["session_offloads"] += 1
            if self._flight is not None:
                self._flight.note_offload(sess.session_id, rows)
        # Paged pool: the slot's pages go back to the one free list the
        # moment the rows are on host (or elided) — an offloaded session
        # holds ZERO device pages, which is the whole sessions-per-chip
        # win. No-op on the contiguous layout.
        self._free_slot_pages(slot_idx)
        sess.slot = None
        self._slots[slot_idx].session_id = None

    def _restore_session(self, sess: _SessionKV, slot_idx: int) -> None:
        """Swap a host-paged session's KV rows back into a device slot."""
        # Paged pool: allocate pages covering the host rows and sync the
        # slot's table row FIRST — the restore program scatters through
        # it. No-op on the contiguous layout.
        self._prepare_slot_restore(slot_idx, sess.host_k)
        self._ck, self._cv = self._restore_fn(
            self._ck, self._cv, kv_device(sess.host_k), kv_device(sess.host_v),
            slot_idx,
        )
        sess.host_k = sess.host_v = None
        sess.slot = slot_idx
        self._slots[slot_idx].session_id = sess.session_id
        self.metrics["session_restores"] += 1
        if self._flight is not None:
            self._flight.note_restore(sess.session_id, slot_idx)

    def _drop_session(self, sid: Optional[str]) -> None:
        if not sid:
            return
        sess = self._sessions.pop(sid, None)
        if sess is not None and sess.slot is not None:
            self._slots[sess.slot].session_id = None
        if sess is not None:
            # Unpin the shared-prefix entry this session seeded from.
            self._prefix_decref(sess.seeded_from)

    def release_session(self, session_id: str) -> None:
        """Forget a session's cached KV (conversation ended / TTL expired).
        Thread-safe: the registry is engine-thread-owned, so the release is
        queued and applied at the next step. An in-flight request on the
        session finishes normally."""
        with self._lock:
            self._pending_releases.append(session_id)
        if self._thread is None:
            self._drain_releases()  # synchronous single-threaded use

    def _drain_releases(self) -> None:
        with self._lock:
            released, self._pending_releases = self._pending_releases, []
        for sid in released:
            self._drop_session(sid)

    def export_session(self, session_id: str) -> Optional[SessionExport]:
        """Package one idle session for cross-worker migration
        (scale-down: ``EngineCoordinator.remove_worker(migrate=True)``).

        Callable once the engine loop is stopped (the post-drain moment
        remove_worker calls from) — the registry and device state are
        engine-thread-owned, so a LIVE engine answers None instead of
        racing its own step loop. None also covers: unknown session, a
        request still decoding on it, and rows the shared-prefix pool
        elided (the survivor rebuilds those through its own pool seed —
        nothing portable to carry). Ownership transfers with the
        payload: a successful export forgets the session here."""
        if self._thread is not None:
            return None  # loop owns the registry/device state; drain first
        self._drain_releases()
        sess = self._sessions.get(session_id)
        if sess is None:
            return None
        if sess.slot is not None:
            if self._slots[sess.slot].active:
                return None  # in-flight request still owns the slot
            # Device-resident: page to host first — export rides the
            # exact offload format (int8 + paged pools included).
            self._offload_session(sess)
        if not sess.token_ids or sess.host_k is None:
            return None  # empty or elided: fresh prefill is the recovery
        payload = SessionExport(
            session_id=session_id,
            token_ids=list(sess.token_ids),
            host_k=sess.host_k,
            host_v=sess.host_v,
            kv_quant=self._kv_quant,
            restore_rows=self.cfg.restore_bucket_for(len(sess.token_ids)),
        )
        self._drop_session(session_id)
        self.metrics["session_exports"] += 1
        return payload

    def import_session(self, export: SessionExport) -> None:
        """Adopt a migrated session: validate compatibility NOW (the
        coordinator needs the accept/reject decision synchronously to
        count fresh-prefill fallbacks exactly), then apply the registry
        insert on the engine thread at the next step — the same queued
        cross-thread contract as ``release_session`` — or immediately
        when the loop is down. The imported record is host-paged; the
        session's next turn restores it into a slot and prefills only
        past the LCP, exactly as if it had been offloaded here."""
        if self.cfg.max_sessions <= 0:
            raise ValueError("engine has sessions disabled (max_sessions=0)")
        if export.kv_quant != self._kv_quant:
            raise ValueError(
                f"kv_quant mismatch: payload {export.kv_quant!r} vs "
                f"engine {self._kv_quant!r}"
            )
        n = len(export.token_ids)
        if n <= 0 or export.host_k is None:
            raise ValueError("empty session payload")
        if n > self.cfg.max_seq - 2:
            raise ValueError(
                f"session of {n} tokens exceeds KV capacity "
                f"(max_seq {self.cfg.max_seq} - 2)"
            )
        rows = self.cfg.restore_bucket_for(n)
        shape = tuple(getattr(export.host_k, "shape", ()) or ())
        expect = (
            self.model_cfg.num_layers, rows,
            self.model_cfg.num_kv_heads, self.model_cfg.head_dim,
        )
        if shape != expect:
            raise ValueError(
                f"session KV rows {shape} incompatible with this "
                f"engine's restore shape {expect}"
            )
        with self._lock:
            self._pending_imports.append(export)
        if self._thread is None:
            self._drain_imports()  # synchronous single-threaded use

    def _drain_imports(self) -> None:
        with self._lock:
            imported, self._pending_imports = self._pending_imports, []
        for exp in imported:
            self._drop_session(exp.session_id)  # replace a stale record
            sess = _SessionKV(exp.session_id, now=self.clock())
            sess.token_ids = list(exp.token_ids)
            sess.host_k = exp.host_k
            sess.host_v = exp.host_v
            self._sessions[exp.session_id] = sess
            self.metrics["session_imports"] += 1
            self._enforce_session_cap(protect=exp.session_id)

    def _offload_idle_sessions(self) -> int:
        """Page every idle resident session's KV rows to host RAM — the
        graceful-drain tail (stop(drain=True)): device state is about to
        go away with the process, host pages survive a restart handoff.
        Only callable once the engine loop is not stepping (the caller
        owns device state)."""
        n = 0
        for sess in list(self._sessions.values()):
            if sess.slot is not None and not self._slots[sess.slot].active:
                self._offload_session(sess)
                n += 1
        return n

    def _enforce_session_cap(self, protect: Optional[str] = None) -> None:
        """Drop least-recently-used sessions above max_sessions. Sessions
        with a decoding request — and the one currently being placed
        (`protect`) — are never dropped: evicting the in-placement session
        would leave its slot pinned to a ghost id."""
        while len(self._sessions) > self.cfg.max_sessions:
            victims = [
                (s.last_used, s.session_id)
                for s in self._sessions.values()
                if s.session_id != protect
                and not (s.slot is not None and self._slots[s.slot].active)
            ]
            if not victims:
                return
            _, sid = min(victims)
            self._drop_session(sid)
