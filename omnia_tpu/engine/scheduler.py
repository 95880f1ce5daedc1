"""Decode scheduler for the serving engine.

Scheduling policy, two editions selected by
``EngineConfig.prefill_chunk_tokens``:

- **0 (default): prefill-first** — one monolithic prefill per step,
  then a decode step for all active slots. Favors TTFT, but every
  arriving prompt stalls ALL active decode slots for its full prefill,
  and while a waiting request has a slot to go to the pipeline degrades
  to synchronous single steps.
- **> 0: token-budget mixed steps** (engine/interleave.py) — prefills
  split into budget-sized pieces and every piece FUSES into the same
  dispatch as a one-token decode step for all active slots, so decode
  never stalls for more than one mixed step and the chunk pipeline
  stays at full depth while requests queue. Bit-identical output to
  prefill-first (tests/test_interleave.py).

Steady state keeps up to ``decode_pipeline`` chunks in flight: chunk
N+1 is dispatched on chunk N's output *futures* before N's tokens are
read, so the device never idles through the host's read-RTT +
bookkeeping gap (the dominant per-chunk cost on a remote-dispatch
link). Under prefill-first a non-empty queue has two regimes, told
apart by ``_queued_placeable``. *Placeable* — a waiting request would
get a slot now: everything in flight is flushed, it is placed, and
decode runs as synchronous single steps so a waiting prefill never sits
out a full chunk. *Blocked* — requests wait and none has a slot (a full
engine): still single steps, so a slot's end is seen one step late and
never a chunk late, but the pipeline keeps its depth — step N+1 is
dispatched before N is read, and the read-back, the emit and the loop
run under it. Under the token-budget policy a waiting prefill
piggybacks on the next mixed step instead — requests waiting on a SLOT
get a pipeline flush per step (finish surfacing) but chunks stay
full-size.
"""

from __future__ import annotations

import contextlib
import queue
import time
from typing import Optional

import numpy as np

from omnia_tpu.engine import phases
from omnia_tpu.engine.devloop import _InflightChunk
from omnia_tpu.engine.faults import WatchdogTimeout
from omnia_tpu.engine.phases import phase
from omnia_tpu.engine.placement import RELEASED
from omnia_tpu.engine.types import FinishReason, SamplingParams, StreamEvent
from omnia_tpu.models import decode_counters
from omnia_tpu.ops.attention import decode_block_rows


class _SchedulerMixin:
    """Step-loop and pipeline methods of :class:`InferenceEngine`.

    Mixed into the engine class — operates on the engine's slots, device
    state, and compiled programs. Split out so the dispatch/pipeline
    policy reads as one unit apart from placement and session residency.
    """

    def generate(
        self, prompt_tokens: list[int], params: SamplingParams = SamplingParams()
    ) -> tuple[list[int], StreamEvent]:
        """Synchronous helper: submit and drive steps inline (single-threaded
        use in tests/bench; with the engine thread running, just blocks)."""
        handle = self.submit(prompt_tokens, params)
        if self._thread is None:
            toks: list[int] = []
            while True:
                self.step()
                try:
                    while True:
                        ev = handle._queue.get_nowait()
                        if ev.token_id is not None:
                            toks.append(ev.token_id)
                        if ev.is_final:
                            return toks, ev
                except queue.Empty:
                    pass
        return handle.collect_tokens(timeout=120)

    def live_request_ids(self) -> set:
        """Request ids still queued or decoding (multihost handle-map
        hygiene: live handles must never be evicted)."""
        with self._lock:
            waiting = {req.request_id for req, _h in self._waiting}
        pf = self._prefilling
        if pf is not None:
            waiting.add(pf.request.request_id)  # mid-interleave placement
        return waiting | {
            s.request.request_id for s in self._slots if s.active
        }

    def step(self) -> bool:
        """One scheduling step. Returns True if any work was done."""
        if not (phases.enabled() and self._has_work()):
            self._housekeeping()
            return self._schedule()
        # A profiler session is on and there is work to look at: the step
        # is a span on the profiler's clock, with every phase below nested
        # in it. An idle poll writes none.
        with phase(phases.STEP) as sp:
            if sp:
                sp.set_metadata(
                    mono_ns=time.monotonic_ns(), queued=self.queue_depth(),
                    inflight=len(self._inflight),
                )
            with phase(phases.HOUSEKEEPING):
                self._housekeeping()
            return self._schedule()

    def _has_work(self) -> bool:
        with self._lock:
            if self._waiting or self._pending_releases or self._pending_imports:
                return True
        return (
            bool(self._inflight) or self._prefilling is not None
            or any(s.active for s in self._slots)
        )

    def _schedule(self) -> bool:
        if self._flight is not None:
            # The start of this pass, for the request it may claim: what
            # it waited before is the loop's, what it waits from here the
            # flush's (flight.py LatencyBreakdown).
            self._pass_at = time.monotonic()
        if self._mixed_enabled():
            # Token-budget policy (engine/interleave.py): prefills split
            # into pieces fused with decode steps.
            return self._step_mixed()
        did = False
        queued, placeable = self._queued_placeable()
        if placeable:
            if self._inflight:
                # A waiting request has a slot to go to: surface every
                # in-flight finish now and place it this step (TTFT over
                # pipeline depth).
                self._flush_for_waiting()
                did = True
            # Prefill/extend programs consume self._ck/_cv, which may be
            # futures from in-flight decode chunks — XLA sequences the
            # dependency, but host slot state must be current before
            # placement decisions stick (and an eviction is a device
            # program of its own), so the claim comes after the flush.
            pending, slot_idx = self._claim_pending()
            if pending is not None:
                self._place_pending(slot_idx, *pending)
                did = True
            queued, placeable = self._queued_placeable()
        if any(s.active for s in self._slots):
            # Per-slot speculation (spec_decode.py): greedy slots —
            # grammar-constrained ones included — verify up to W
            # proposals per weight stream while sampled slots ride the
            # exact chunked step fused into the same dispatch; the
            # self-gate and proposal plan decide per step, falling
            # through to the plain lane whenever speculation would not
            # pay (no proposals, gate off, window at the cache end).
            if self._spec_step():
                return True
            # A dispatch-ahead that no slot can still need (everyone's
            # token budget is covered by chunks already in flight) would
            # be pure garbage whose sync delays the NEXT request's
            # placement by a full chunk — drain instead.
            if self._inflight and not self._dispatch_ahead_useful():
                self._process_oldest_chunk()
            else:
                # Nobody waits: full chunks, pipelined. A waiting request
                # can be placed: one step, read back at once, placement
                # next. Blocked (requests wait, none has a slot): one
                # step, but pipelined, so the read-back and emit of step
                # N run while the device computes step N+1. Behind a
                # prefill whose first token is still unread: one step
                # too, since who arrives during that prefill is not known
                # yet, and would wait out a whole chunk before its own.
                self._dispatch_decode(
                    single=queued or self._first_token_unread(),
                    blocked=queued and not placeable,
                )
                depth = 1 if placeable else max(1, self.cfg.decode_pipeline)
                while len(self._inflight) >= depth:
                    self._process_oldest_chunk()
            did = True
        elif self._inflight:
            self._process_oldest_chunk()
            did = True
        return did

    def _queued_placeable(self) -> tuple[bool, bool]:
        """``(queued, placeable)``: whether requests wait, and whether
        one of them would get a slot now — the test ``_claim_pending``
        makes, without its side effects, so it may run with chunks in
        flight. It answers from host slot state, which lags the device
        by those chunks: an end not yet read reads as blocked, and the
        blocked regime's own read of the oldest chunk is what finds it.
        No clock and nothing rank-local is read, so lockstep ranks agree."""
        with self._lock:
            waiting = list(self._waiting)
        return bool(waiting), any(
            self._choose_slot(req)[0] is not None for req, _h in waiting
        )

    def _claim_pending(self):
        """First PLACEABLE waiting request — not just the head: a
        request whose session is still mid-decode must not
        head-of-line-block other sessions' requests while slots sit
        free. The winner is CLAIMED (removed from the queue, ``_placing``
        incremented); returns ``(pending, slot_idx)`` or ``(None, None)``."""
        with self._lock:
            waiting = list(self._waiting)
        if not waiting:
            return None, None
        with phase(phases.CLAIM) as sp:
            pending = None
            slot_idx = None
            for cand in self._admission_order(waiting):
                idx = self._slot_for(cand[0])
                if idx is not None:
                    pending, slot_idx = cand, idx
                    break
            if pending is not None:
                with self._lock:
                    try:
                        self._waiting.remove(pending)
                        self._placing += 1
                    except ValueError:
                        pending = None  # reaped concurrently
            if pending is not None:
                parts = None
                if self._flight is not None:
                    parts = self._flight.note_claim(
                        pending[0].request_id,
                        self._free_since(pending[0], slot_idx), self._pass_at,
                    )
                if sp:
                    sp.set_metadata(
                        request_id=pending[0].request_id,
                        **phases.as_ms(parts),
                    )
        return pending, slot_idx

    def _place_pending(self, slot_idx, request, handle):
        """Monolithic placement with the prefill-failure error surface;
        balances the ``_placing`` claim taken by ``_claim_pending``."""
        try:
            with phase(phases.PLACE) as sp:
                if sp:
                    sp.set_metadata(
                        request_id=request.request_id,
                        n_prompt=len(request.prompt_tokens),
                    )
                self._place_request(slot_idx, request, handle, span=sp)
        except Exception:
            # The request may not be attached to a slot yet, so
            # recovery's _fail_all would never reach its handle —
            # fail it here, then let the loop's recovery rebuild
            # device state.
            self._fail_placement(slot_idx, request, handle, "prefill failed")
            raise
        finally:
            with self._lock:
                self._placing -= 1

    def _fail_placement(self, slot_idx, request, handle, msg: str):
        """Shared placement-failure surface (monolithic except, interleave
        begin/dispatch failures, recovery's half-prefill path): terminal
        ERROR, books balanced, session/seed/slot released. Callers own
        the ``_placing`` release."""
        handle._push(
            StreamEvent(
                request.request_id,
                finish_reason=FinishReason.ERROR,
                error=msg,
                # Accepted-and-placed marker: a nonzero prompt
                # count tells the coordinator this is a worker
                # fault (resubmittable), not a validation
                # rejection that would recur anywhere.
                num_prompt_tokens=len(request.prompt_tokens),
            )
        )
        self.metrics["requests_finished"] += 1
        if self._flight is not None:
            self._flight.note_terminal(
                request.request_id, FinishReason.ERROR.value, error=msg
            )
        self._drop_session(request.session_id)
        self._slots[slot_idx].session_id = None
        self._release_slot_seed(self._slots[slot_idx])
        self._free_slot(self._slots[slot_idx])

    # Admission fairness window: requests older than this keep strict
    # FIFO priority regardless of estimated prefill cost.
    _ADMIT_FAIRNESS_S = 0.5
    # Cost estimation is O(prompt-length radix walk); bound it to the
    # queue head so a deep backlog doesn't tax every step.
    _ADMIT_WINDOW = 8

    def _admission_order(self, waiting):
        """Seeded-length-aware admission: within the young head of the
        queue, place the request with the cheapest estimated prefill
        first — a fresh session whose prompt is mostly covered by the
        shared-prefix pool (or its own session rows) costs a seed-copy
        plus a short suffix, so admitting it ahead of a long cold
        prefill lowers TTFT p50 without starving anyone (requests past
        the fairness window keep strict FIFO)."""
        if len(waiting) < 2 or not self._prefix_enabled():
            return waiting
        if self.clock is not time.monotonic:
            # Replicated engines (multi-host lockstep) must keep the
            # leader's submit order: the fairness age below is measured
            # against each rank's LOCAL submitted_at, so a reorder could
            # differ per rank and diverge the compiled-step streams.
            return waiting
        # Same clock domain as Request.submitted_at (time.monotonic) —
        # NOT self.clock, which may be an injected logical clock.
        now = time.monotonic()
        head = waiting[: self._ADMIT_WINDOW]

        def key(item):
            idx, (req, _h) = item
            if now - req.submitted_at >= self._ADMIT_FAIRNESS_S:
                return (0, idx, 0)
            return (1, self._estimated_prefill_cost(req), idx)

        ordered = [it for _, it in sorted(enumerate(head), key=key)]
        return ordered + waiting[self._ADMIT_WINDOW:]

    def _estimated_prefill_cost(self, req) -> int:
        """Tokens this request would actually prefill: prompt length
        minus the better of its session's resident-row LCP and the
        shared-prefix pool match."""
        prompt = req.prompt_tokens
        covered = self._prefix_match_len(prompt)
        if req.session_id and self.cfg.max_sessions > 0:
            sess = self._sessions.get(req.session_id)
            if sess is not None:
                lcp, limit = 0, min(len(sess.token_ids), len(prompt) - 1)
                while lcp < limit and sess.token_ids[lcp] == prompt[lcp]:
                    lcp += 1
                covered = max(covered, lcp)
        return len(prompt) - min(covered, len(prompt) - 1)

    def _dispatch_ahead_useful(self) -> bool:
        """True if at least one active slot's generation budget extends past
        the decode steps already in flight — i.e. one more chunk does real
        work for someone. Stop-token finishes are unpredictable, so budgets
        are optimistic (max_tokens); the cost of optimism is one garbage
        chunk, the cost of pessimism would be no pipelining for any request
        that carries an EOS id (all real chat traffic)."""
        return self._remaining_work() > 0

    def _fault_sleep_s(self) -> float:
        """Injected hang/slow-sync seconds for the next chunk readback
        (engine/faults.py): consumed at the point the readback STARTS —
        inline, or on the watchdog's drainer thread, where an injected
        hang must look exactly like a hung device sync."""
        fault = self._fault_plan
        if fault is None:
            return 0.0
        return fault.take_hang_s() + fault.slow_sync_s

    def _sync_chunk_host(self, toks) -> np.ndarray:
        """Device→host read of a decode chunk's tokens, optionally under
        the hung-dispatch watchdog. watchdog_s=None is the direct sync
        (no thread); with a watchdog the read rides the engine's ONE
        long-lived drainer thread (engine/devloop.py ChunkDrainer) and
        is awaited with a timeout. A read that outlives watchdog_s
        raises WatchdogTimeout — the loop's recovery path fails
        in-flight handles and reallocates device state, so a hung
        device bounds client latency instead of freezing the engine
        silently."""
        wd = self.cfg.watchdog_s
        if wd is None:
            sleep_s = self._fault_sleep_s()
            if sleep_s > 0.0:
                time.sleep(sleep_s)
            return np.asarray(toks)
        drainer = self._devloop.get_drainer()
        entry = drainer.submit(toks, pre_sleep_s=self._fault_sleep_s())
        host = drainer.wait(entry, timeout=wd)
        if host is None:
            self.metrics["watchdog_trips"] += 1
            self._healthy = False  # readiness flips for the incident;
            # _recover restores it once device state reallocates.
            raise WatchdogTimeout(
                f"decode chunk host sync exceeded watchdog_s={wd}"
            )
        return host

    def _run_decode_step(self, chunk: Optional[int] = None):
        """One chunked decode dispatch → device tokens [K, B]. Position
        advancement AND stop/length deactivation happen on-device inside
        the scan. `chunk` picks an explicit compiled variant (1 is the
        one-step program used while requests wait)."""
        fn = self._decode_fn if chunk is None else self._decode_fns[chunk]
        t_dispatch = time.monotonic()
        args = (
            self.params,
            *self._cache,
            self._tokens,
            self._positions,
            self._active,
            self._budget,
            self._stop_ids,
            self._key_data,
            self._temp,
            self._top_p,
            self._top_k,
        )
        n = len(self._cache)
        if self._gr_on:
            # Grammar edition: per-slot FSM state rides the dispatch and
            # advances on device (programs.decode_chunk_grammar).
            out = fn(*args, self._gstate, self._gtable, self._gactive)
            self._gstate = out[n + 5]
        else:
            out = fn(*args)
        self._cache = tuple(out[:n])
        (
            self._tokens,
            self._positions,
            self._active,
            self._budget,
            self._key_data,
        ) = out[n:n + 5]
        toks = out[-1]
        self.metrics["decode_dispatch_s"] += time.monotonic() - t_dispatch
        return toks

    def _live_kv_blocks(self, live) -> int:
        """Blocks of the decode kernel that the contexts of the ``live``
        slots ``[(slot, request_id)]`` span, by the host's lengths: what
        one step's attention kernel visits, a layer."""
        own = getattr(self.model_module, "decode_block_rows", None)
        rows = own(self.cfg.max_seq) if own else decode_block_rows(
            self.cfg.max_seq,
            self.cfg.kv_page_tokens if self.cfg.kv_pages > 0 else 0,
        )
        return sum(self._slots[i].length // rows + 1 for i, _rid in live)

    def _live_sampling(self, live) -> tuple[bool, bool]:
        """(some ``live`` slot's request samples, some sampling one asks
        for a threshold): the gates the sampler takes on the device for a
        dispatch over these slots (ops/sampling.py ``_gated_sample``), by
        the host's own slot records: a slot's ``_temp`` is its request's
        temperature from placement until its slot is released. A slot
        released between the snapshot and the program call (the paged
        pool ran out, engine/paged.py) holds 0.0 again by then."""
        sampling = False
        for i, _rid in live:
            request = self._slots[i].request
            if request is None:
                continue
            p = request.params
            if p.temperature > 0:
                if p.top_p < 1 or p.top_k > 0:
                    return True, True
                sampling = True
        return sampling, False

    def _count_decode_dispatch(self, steps: int, live,
                               single: bool = False,
                               blocked: bool = False) -> None:
        """One program call that decodes over the ``live`` slots
        ``[(slot, request_id)]``, counted where the batch is
        formed: ``decode_steps / decode_dispatches`` is the realised
        chunk, ``decode_dispatches_single`` the calls of the one-step
        decode program, ``decode_dispatches_blocked`` those made while
        requests waited and none had a slot, and ``decode_slot_steps``
        the slots live at dispatch times the steps asked, so
        ``decode_slot_steps / (decode_steps * num_slots)`` is occupancy
        without reckoning it from tokens. ``decode_kv_blocks`` is
        ``_live_kv_blocks`` at dispatch times the steps asked, so over
        ``decode_steps * num_slots * max_seq / block`` it is the share
        of all (slot, block) pairs the decode kernel visits;
        ``decode_window_rows`` the ring rows a window layer's kernel spans
        (the model module's ``decode_window_rows``, where it has rings).
        ``decode_steps_sampling`` / ``decode_steps_filtering`` are the
        steps asked times ``_live_sampling``: over ``decode_steps``, the
        shares of steps in which the sampler did more than the argmax,
        and in which it also computed thresholds."""
        m = self.metrics
        m["decode_steps"] += steps
        m["decode_dispatches"] += 1
        m["decode_slot_steps"] += len(live) * steps
        m["decode_kv_blocks"] += self._live_kv_blocks(live) * steps
        window_rows = getattr(self.model_module, "decode_window_rows", None)
        if window_rows:
            m["decode_window_rows"] += steps * window_rows(
                self.model_cfg, [self._slots[i].length for i, _rid in live])
        sampling, filtering = self._live_sampling(live)
        m["decode_steps_sampling"] += steps * sampling
        m["decode_steps_filtering"] += steps * filtering
        if single:
            m["decode_dispatches_single"] += 1
        if blocked:
            m["decode_dispatches_blocked"] += 1

    def _remaining_work(self) -> int:
        """Max over active slots of tokens still to emit beyond steps
        already in flight — how many more decode steps could do real work
        for SOMEONE."""
        inflight_steps: dict[int, int] = {}
        for ch in self._inflight:
            for i, _rid in ch.active:
                inflight_steps[i] = inflight_steps.get(i, 0) + ch.steps
        need = 0
        for i, s in enumerate(self._slots):
            if not s.active:
                continue
            rem = min(
                s.max_total - s.generated,
                self.cfg.max_seq - 2 - s.length,
            ) - inflight_steps.get(i, 0)
            need = max(need, rem)
        return need

    def _pick_chunk(self) -> int:
        """Chunk size for the remaining useful work: the full chunk while
        work exceeds it, else the SMALLEST variant covering the remainder.
        Overshoot is preferred to undershoot — the on-device finish mask
        makes overshot steps cheap garbage (~one model step each), while
        an extra dispatch costs a full host round trip."""
        need = max(self._remaining_work(), 1)
        best = max(self._decode_fns)
        for k in sorted(self._decode_fns):
            if k >= need:
                best = k
                break
        return best

    def _dispatch_decode(self, single: bool = False, blocked: bool = False):
        """Dispatch one decode chunk asynchronously: device state advances
        to output futures immediately; the token read is deferred to
        _process_oldest_chunk. The active-slot list is snapshotted at
        dispatch time — a slot that finishes while this chunk is in flight
        is deactivated on-device the same step, so it stops writing rows;
        any rows it DID write past its valid frontier are tolerated by the
        sessionful bookkeeping (garbage only at rows ≥ session length)."""
        with phase(phases.DECODE_DISPATCH) as sp:
            active = [
                (i, s.request.request_id)
                for i, s in enumerate(self._slots) if s.active
            ]
            chunk = 1 if single else self._pick_chunk()
            seq = None
            if sp:
                sampling, filtering = self._live_sampling(active)
                # The count of decode dispatches so far names this one:
                # the chunk carries it to the spans that read it back.
                seq = self.metrics["decode_dispatches"]
                sp.set_metadata(
                    chunk=chunk, active=len(active), single=single,
                    blocked=blocked, inflight=len(self._inflight),
                    kv_blocks=self._live_kv_blocks(active),
                    sampling=sampling, filtering=filtering, seq=seq,
                )
            # Paged pool: extend every active slot's pages past its write
            # frontier BEFORE the chunk dispatches (engine/paged.py) — a
            # decode write must never land through a trash table entry.
            self._prealloc_decode_pages(chunk)
            t_dispatch = time.monotonic()
            toks = self._run_decode_step(chunk=chunk)
            self._count_decode_dispatch(
                chunk, active, single=chunk == 1, blocked=blocked
            )
            # The dispatch wall rides the in-flight entry so the flight
            # recorder can pair it with the (deferred) sync wall into one
            # per-chunk dispatch-vs-sync event.
            self._push_inflight(
                toks, active, time.monotonic() - t_dispatch, seq=seq
            )

    def _push_inflight(self, toks, active, dispatch_s, placement=None,
                       seq=None):
        """Append one dispatched chunk to the pipeline — the shared seam
        for plain decode chunks, mixed interleave steps and a
        placement's first token (``placement``, devloop.py)."""
        self._inflight.append(
            _InflightChunk(toks, active, dispatch_s, placement, seq)
        )

    def _process_oldest_chunk(self):
        ch = self._inflight.popleft()
        if ch.placement is not None:
            return self._process_first_token(ch)
        t_sync = time.monotonic()
        counters = decode_counters(self.model_cfg)
        with phase(phases.CHUNK_SYNC) as sp:
            if sp:
                sp.set_metadata(
                    chunk=int(ch.toks.shape[0]) - len(counters),
                    **ch.seq_attr,
                )
            # [K, B] — ONE sync per chunk.
            host_tokens = self._sync_chunk_host(ch.toks)
        if counters:
            # The model's device counters are the buffer's last rows
            # (programs.py::decode_impl): read with the tokens.
            for name, row in zip(counters, host_tokens[-len(counters):]):
                self.metrics[name] += int(row[0])
            host_tokens = host_tokens[:-len(counters)]
        sync_s = time.monotonic() - t_sync
        self.metrics["decode_sync_s"] += sync_s
        if self._flight is not None:
            self._flight.note_decode_chunk(
                int(host_tokens.shape[0]), ch.dispatch_s, sync_s,
                len(ch.active),
            )
        with self._emit_phase() as sp:
            if sp:
                sp.set_metadata(**ch.seq_attr)
            self._emit_chunk(ch, host_tokens)

    @contextlib.contextmanager
    def _emit_phase(self):
        """The ``emit`` span around what it covers, with the tokens and
        the terminals that went out under it; yields the span, for what
        the caller knows of it."""
        with phase(phases.EMIT) as sp:
            m = self.metrics
            tok0, fin0 = m["tokens_generated"], m["requests_finished"]
            yield sp
            if sp:
                sp.set_metadata(
                    tokens=m["tokens_generated"] - tok0,
                    finished=m["requests_finished"] - fin0,
                )

    def _process_first_token(self, ch) -> None:
        """Read and emit a placement's deferred first token: the read
        waits for the prefill alone, whatever is queued behind it, under
        the watchdog like any chunk's (``chunk=0``: no decode step)."""
        (slot_idx, rid), = ch.active
        note = ch.placement
        with phase(phases.CHUNK_SYNC) as sp:
            if sp:
                sp.set_metadata(chunk=0, request_id=rid)
            t_read0 = time.monotonic() if self._flight is not None else 0.0
            token = int(self._sync_chunk_host(ch.toks))
            if self._flight is not None:
                note = dict(note, t_read0=t_read0, t_read=time.monotonic())
        with self._emit_phase() as sp:
            stages = self._emit_first_token(slot_idx, rid, token, note)
            if sp:
                sp.set_metadata(request_id=rid, **phases.as_ms(stages))

    def _first_token_unread(self) -> bool:
        """A placement's first token is in the pipeline: its prefill may
        still be running."""
        return any(ch.placement is not None for ch in self._inflight)

    def _settle_first_token(self, slot_idx: int) -> None:
        """Read the pipeline through ``slot_idx``'s first token, if it is
        still in flight: a slot ended from outside the emit loop (cancel,
        deadline, the paged pool run dry) has its first token emitted
        first, as when placement read it."""
        while any(
            ch.placement is not None and ch.active[0][0] == slot_idx
            for ch in self._inflight
        ):
            self._process_oldest_chunk()

    def _emit_chunk(self, ch, host_tokens) -> None:
        """The per-step, per-slot emission of one synced chunk [K, B]."""
        K = int(host_tokens.shape[0])
        for k in range(K):
            stepped = False
            for i, rid in ch.active:
                slot = self._slots[i]
                if not slot.active or slot.request.request_id != rid:
                    # Finished earlier in this chunk (rest is garbage) — or
                    # cancelled and re-placed while the chunk was in
                    # flight, in which case these tokens belong to the old
                    # request, never the slot's new occupant.
                    continue
                stepped = True
                slot.length += 1
                self._emit_token(i, int(host_tokens[k, i]))
            if not stepped:
                # Every snapshot slot is finished: the remaining steps'
                # tokens are frozen garbage for all of them.
                break

    def _flush_pipeline(self):
        while self._inflight:
            self._process_oldest_chunk()

    def _flush_for_waiting(self):
        """The flush a waiting request forces: every chunk in flight is
        read now, so that finished slots free up for it."""
        self.metrics["pipeline_flushes"] += 1
        with phase(phases.FLUSH_PIPELINE) as sp:
            if sp:
                sp.set_metadata(chunks=len(self._inflight))
            self._flush_pipeline()

    def _emit_token(self, slot_idx: int, token: int):
        slot = self._slots[slot_idx]
        if not slot.active:
            return
        rid = slot.request.request_id
        if slot.gr_view is not None:
            # Host mirror of the device FSM walk: the state BEFORE this
            # token is what the sampler masked with — its masked row
            # fraction feeds the masked_logit_fraction running mean.
            self._gr_mask_sum += slot.gr_view.masked_fraction(slot.gr_state)
            self._gr_mask_steps += 1
            self.metrics["masked_logit_fraction"] = round(
                self._gr_mask_sum / self._gr_mask_steps, 6
            )
            nxt = slot.gr_view.advance(slot.gr_state, token)
            if nxt >= 0:
                slot.gr_state = nxt
        if token in slot.stop_ids:
            self._finish_slot(slot_idx, FinishReason.STOP)
            return
        slot.generated += 1
        slot.emitted.append(token)
        # Deliberately NO flight-recorder call here: the emit loop is
        # the decode hot path, and handle._push already stamps
        # first_token_at — the terminal carries it to the recorder.
        slot.handle._push(StreamEvent(rid, token_id=token))
        self.metrics["tokens_generated"] += 1
        # max_total caps generated tokens; the cache bound stops a step early
        # so the next decode write can never clamp/corrupt (row max_seq-1 is
        # the last legal write).
        if slot.generated >= slot.max_total or slot.length >= self.cfg.max_seq - 2:
            self._finish_slot(slot_idx, FinishReason.LENGTH)

    def _finish_slot(self, slot_idx: int, reason: FinishReason):
        slot = self._slots[slot_idx]
        self._settle_first_token(slot_idx)
        if not slot.active:
            return  # its first token, read just now, ended it
        rid = slot.request.request_id
        handle = slot.handle
        n_prompt = len(slot.request.prompt_tokens)
        generated = slot.generated
        if (
            slot.gr_view is not None and reason is FinishReason.STOP
            and slot.gr_view.is_accepting(slot.gr_state)
        ):
            # A constrained generation brought to a valid stop: without
            # the grammar this request could have burned a whole decode
            # on unparseable output and retried (bad_response_format).
            self.metrics["grammar_rejections_avoided"] += 1
        # Sessionful: record which rows are valid for the next turn's
        # prefix reuse. The last emitted token's row write is not
        # guaranteed (a slot can finish mid-decode-chunk), so it is
        # conservatively excluded — re-prefilling one token next turn is
        # cheaper than reasoning about chunk timing. The record commits
        # BEFORE the terminal event is pushed: the coordinator relay
        # hands a freshly-prefilled session off at the terminal
        # (engine/disagg.py), so the terminal must never be observable
        # while the registry still holds the previous turn or the slot
        # still reads active.
        quiesce_row = 0
        sid = slot.session_id
        sess = self._sessions.get(sid) if sid else None
        if sess is not None and reason is not FinishReason.ERROR:
            sess.token_ids = list(slot.request.prompt_tokens) + slot.emitted[:-1]
            sess.last_used = self.clock()
            # Idle-pinned slots keep decoding garbage at this frozen row —
            # parking it at the valid-row frontier keeps the invariant that
            # garbage only ever lives at rows ≥ the session's length.
            quiesce_row = len(sess.token_ids)
        elif sess is not None:
            self._drop_session(sid)
        self._release_slot_seed(slot)
        self._free_slot(slot)
        # Paged pool: pages past the quiesce frontier (all of them for
        # an unpinned slot) go back to the one free list; the frozen
        # row's garbage writes land in the kept partial page or the
        # trash page, never in a freed one.
        self._trim_slot_pages(slot_idx, quiesce_row)
        # Quiesce the slot: decode keeps running over it (static shape), but
        # with active=False its position is frozen, so it only ever rewrites
        # one row — row 0 for unpinned slots (the next prefill's insert
        # overwrites it) or the session's length frontier for pinned ones.
        self._run_slot_program(
            self._release_slot_fn, RELEASED,
            np.asarray([slot_idx, quiesce_row], np.int32),
        )
        handle._push(
            StreamEvent(
                rid,
                finish_reason=reason,
                num_prompt_tokens=n_prompt,
                num_generated_tokens=generated,
            )
        )
        self.metrics["requests_finished"] += 1
        if self._flight is not None:
            self._flight.note_terminal(
                rid, reason.value, tokens=generated,
                first_token_at=handle.first_token_at,
            )
