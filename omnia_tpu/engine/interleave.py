"""Stall-free batching: token-budget interleaving of prefill and decode.

With ``EngineConfig.prefill_chunk_tokens > 0`` the scheduler drops the
prefill-first policy for placements that would stall live decode:
the prompt splits into pieces of at most the per-step token budget, and
every piece rides a FUSED device dispatch (the ``mixed`` program family
in programs.py) that also advances all active decode slots by one
token. Decode inter-token latency under arriving traffic is then
bounded by ONE mixed step — never a whole prefill — and the decode
pipeline stays at full depth while requests queue: the old
degrade-to-synchronous-single-steps path is gone entirely. A request
waiting on a SLOT (every slot busy) gets the pipeline flushed each
step so finishes surface promptly, but chunks stay full-size — slot
turnover detection may lag by up to one chunk, the deliberate price
for not cratering decode throughput exactly when the engine is
saturated.

Invariants this module maintains:

- **Bit-exactness.** A piece runs the same extend-seam op graph as the
  monolithic chunked extend, and the fused decode step is the same scan
  body as the chunked decode programs, so interleaved serving emits
  bit-identical tokens and KV rows to prefill-first serving
  (tests/test_interleave.py pins it, including under kv_quant="int8"
  and with grammar slots in the batch).
- **Garbage rows.** The in-placement slot is inactive during every
  mixed step's decode half; its frozen position is parked at the
  piece's END, so the decode garbage write lands at the new frontier —
  overwritten by the next piece or by the first real decode write after
  activation. Garbage only ever lives at rows ≥ the consumed frontier.
- **Exact partial books.** ``prefill_tokens`` /
  ``interleaved_prefill_tokens`` count per consumed piece and a
  session's ``token_ids`` advance with the frontier, so a deadline or
  cancel landing mid-prefill leaves exact counts and genuinely-valid
  reusable rows behind.

At most ONE prefill is in flight at a time (``self._prefilling``); the
knob off means the attribute stays None and every path in this module
is dead — the guarded no-op contract (tests/test_guards.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from omnia_tpu.engine.types import (
    FinishReason,
    Request,
    RequestHandle,
    StreamEvent,
)


@dataclasses.dataclass
class _InflightPrefill:
    """A placement mid-interleave: claimed slot + remaining piece plan."""

    slot_idx: int
    request: Request
    handle: RequestHandle
    sess: Optional[object]          # _SessionKV or None
    pieces: list                    # [(offset, real_len, bucket)]
    next_piece: int = 0
    frontier: int = 0               # rows known valid (reuse/seed + consumed)
    reuse: int = 0                  # session-LCP rows (flight-recorder attrs)
    seeded: int = 0                 # prefix-pool seeded rows

    @property
    def prompt(self) -> list[int]:
        return self.request.prompt_tokens


class _InterleaveMixin:
    """Mixed-step scheduling methods of :class:`InferenceEngine`."""

    def _mixed_enabled(self) -> bool:
        return self.cfg.prefill_chunk_tokens > 0

    def pending_prefill_tokens(self) -> int:
        """Prompt-token backlog: queued prompts plus the unconsumed tail
        of the in-flight interleaved prefill. The coordinator folds this
        into its load signal so four 8k-prompt requests no longer route
        like four 10-token ones."""
        with self._lock:
            backlog = sum(len(r.prompt_tokens) for r, _h in self._waiting)
        pf = self._prefilling
        if pf is not None:
            backlog += max(len(pf.prompt) - pf.frontier, 0)
        return backlog

    # -- step loop ------------------------------------------------------

    def _step_mixed(self) -> bool:
        """One scheduling step under the token-budget policy."""
        did = False
        if self._prefilling is None:
            pending, slot_idx = self._claim_pending()
            if pending is not None:
                did = True
                request, handle = pending
                if any(s.active for s in self._slots):
                    self._begin_interleaved_prefill(slot_idx, request, handle)
                else:
                    # Nothing to stall: monolithic placement is strictly
                    # better (no per-piece dispatch overhead, no garbage
                    # decode forward over an all-idle batch).
                    self._place_pending(slot_idx, request, handle)
        pf = self._prefilling
        if pf is not None:
            # One mixed dispatch: the next prompt piece rides the same
            # program as this step's decode token. Pipelined exactly
            # like decode chunks — the token read is deferred.
            try:
                self._dispatch_mixed(pf)
            except Exception:
                self._fail_prefilling("prefill failed")
                raise
            while len(self._inflight) >= max(1, self.cfg.decode_pipeline):
                self._process_oldest_chunk()
            return True
        if any(s.active for s in self._slots):
            if self._spec_step():
                return True
            with self._lock:
                queued = bool(self._waiting)
            if queued and self._inflight:
                # The queue is waiting on a SLOT here (a placeable
                # request would have begun interleaving above), so
                # surface in-flight finishes promptly — but keep
                # dispatching FULL chunks: prefill waits never degrade
                # the chunk pipeline under the token-budget policy.
                self._flush_for_waiting()
            if self._inflight and not self._dispatch_ahead_useful():
                self._process_oldest_chunk()
            else:
                self._dispatch_decode()
                while len(self._inflight) >= max(1, self.cfg.decode_pipeline):
                    self._process_oldest_chunk()
            return True
        if self._inflight:
            self._process_oldest_chunk()
            return True
        return did

    # -- placement ------------------------------------------------------

    def _budget_pieces(self, start: int, count: int) -> list[tuple[int, int, int]]:
        """Plan (offset, real_len, bucket) pieces covering prompt[start:
        start+count], each consuming at most ``prefill_chunk_tokens``
        prompt tokens — the per-step budget. Same no-write-past-max_seq
        degrade as ``_extend_pieces``: a bucket-padded write must never
        cross the cache end, so the tail degrades to 1-token pieces."""
        buckets = sorted(self.cfg.usable_buckets())
        budget = self.cfg.prefill_chunk_tokens
        S = self.cfg.max_seq
        pieces = []
        pos, left = start, count
        while left > 0:
            take = min(left, budget, buckets[-1])
            b = self.cfg.bucket_for(take)
            if pos + b > S:
                b = 1
                take = 1
            pieces.append((pos, take, b))
            pos += take
            left -= take
        return pieces

    def _begin_interleaved_prefill(
        self, slot_idx: int, request: Request, handle: RequestHandle
    ) -> None:
        """Claim the slot and plan the piece schedule; the per-piece
        dispatches happen one per step in ``_dispatch_mixed``. The
        ``_placing`` claim taken by ``_claim_pending`` is held for the
        WHOLE interleave (queue-invisible, slot-invisible work — drain
        and recovery must see it)."""
        try:
            prompt = request.prompt_tokens
            slot_idx, sess, reuse = self._prepare_session_slot(
                slot_idx, request
            )
            t0 = time.monotonic()
            seeded = 0
            if reuse == 0:
                seeded = self._try_seed_from_pool(slot_idx, prompt, sess)
            self.metrics["prefill_dispatch_s"] += time.monotonic() - t0
            self.metrics["prefix_reuse_tokens"] += reuse
            frontier = reuse or seeded
            if frontier == 0:
                # Paged pool: cold start — stale pages back to the free
                # list before the first piece allocates fresh ones.
                self._free_slot_pages(slot_idx)
            if sess is not None:
                # Truncate to the reuse frontier NOW: the pieces below
                # overwrite rows from `frontier` on, so any longer stale
                # claim (a diverged previous turn) must drop before the
                # first piece lands.
                sess.token_ids = list(prompt[:frontier])
            self._prefilling = _InflightPrefill(
                slot_idx=slot_idx, request=request, handle=handle, sess=sess,
                pieces=self._budget_pieces(frontier, len(prompt) - frontier),
                frontier=frontier, reuse=reuse, seeded=seeded,
            )
        except Exception:
            self._fail_placement(slot_idx, request, handle, "prefill failed")
            with self._lock:
                self._placing -= 1
            raise

    def _dispatch_mixed(self, pf: _InflightPrefill) -> None:
        """One fused dispatch: the next prompt piece + one decode step
        for every active slot. The decode token read is deferred to
        ``_process_oldest_chunk`` like any decode chunk.

        With speculation engaged (spec_decode.py), a verify window
        rides the SAME dispatch via the ``mixed_spec`` program family:
        greedy slots verify their proposals while sampled slots take
        the exact decode step and the prefill piece streams — per-slot
        lanes in one program. Acceptance needs the window's greedy
        tokens on host immediately, so spec-fused mixed steps are
        synchronous (the in-flight pipeline is flushed first); the
        self-gate prices that in."""
        off, take, bucket = pf.pieces[pf.next_piece]
        final = pf.next_piece == len(pf.pieces) - 1
        plan = None
        if self._spec_engaged():
            park = {pf.slot_idx: off + take}
            depths: dict = {}  # one cooldown advance per step (memoized)
            if self._spec_plan(park=park, depths=depths) is not None:
                if self._inflight:
                    # Settled host books before proposing (the same
                    # rule as the standalone verify step).
                    self._flush_pipeline()
                plan = self._spec_plan(park=park, depths=depths)
        active = [
            (i, s.request.request_id)
            for i, s in enumerate(self._slots)
            if s.active and (plan is None or not plan.vmask[i])
        ]
        # Park the in-placement slot's frozen decode-write row at the
        # piece's END: the fused program runs the extend half first, so
        # the decode half's garbage write lands at the NEW frontier —
        # the row the next piece (or the first real decode write after
        # activation) overwrites.
        self._positions = self._positions.at[pf.slot_idx].set(off + take)
        # Paged pool: exclusive pages through the piece's bucket end for
        # the placing slot (the parked garbage row lands inside them),
        # plus one decode row for every active slot.
        self._prepare_slot_write(pf.slot_idx, off, min(off + bucket, self.cfg.max_seq))
        self._prealloc_decode_pages(1)
        spec_args = ()
        mixed_fns, mixed_sample_fns = self._mixed_fns, self._mixed_sample_fns
        if plan is not None:
            # Paged pool: exclusive pages for every active slot's verify
            # window (the scan-lane slots' windows are garbage, but
            # garbage must still land in owned pages, never freed ones).
            W = self.cfg.spec_window()
            for i, s in enumerate(self._slots):
                if s.active:
                    self._prepare_slot_write(
                        i, s.length, min(s.length + W + 1, self.cfg.max_seq)
                    )
            spec_args = (
                jnp.asarray(plan.toks), jnp.asarray(plan.pos),
                jnp.asarray(plan.wstart), jnp.asarray(plan.vmask),
            )
            mixed_fns = self._mixed_spec_fns
            mixed_sample_fns = self._mixed_spec_sample_fns
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :take] = pf.prompt[off:off + take]
        ppos = (off + np.arange(bucket, dtype=np.int32))[None, :]
        args = (
            self.params, self._ck, self._cv, self._tokens, self._positions,
            self._active, self._budget, self._stop_ids, self._key_data,
            self._temp, self._top_p, self._top_k,
            jnp.asarray(toks), jnp.asarray(ppos),
            jnp.int32(pf.slot_idx), jnp.int32(off),
        )
        gargs = (
            (self._gstate, self._gtable, self._gactive) if self._gr_on else ()
        )
        t_dispatch = time.monotonic()
        first_tok = new_pkd = greedy = None
        if final:
            sp = pf.request.params
            kd = self._sampling_key(pf.slot_idx, sp)
            out = mixed_sample_fns[bucket](
                *args, *spec_args,
                jnp.int32(take - 1), kd, jnp.float32(sp.temperature),
                jnp.float32(sp.top_p), jnp.int32(sp.top_k),
                *self._grammar_args(pf.request, sp), *gargs,
            )
            if plan is not None:
                greedy, out = out[-1], out[:-1]
            first_tok, new_pkd = out[-2], out[-1]
            out = out[:-2]
        else:
            out = mixed_fns[bucket](*args, *spec_args, *gargs)
            if plan is not None:
                greedy, out = out[-1], out[:-1]
        if self._gr_on:
            (self._ck, self._cv, self._tokens, self._positions, self._active,
             self._budget, self._key_data, self._gstate, dtoks) = out
        else:
            (self._ck, self._cv, self._tokens, self._positions, self._active,
             self._budget, self._key_data, dtoks) = out
        t_enq = time.monotonic()
        dispatch_s = t_enq - t_dispatch
        self.metrics["decode_dispatch_s"] += dispatch_s
        self._count_decode_dispatch(1, active)
        self.metrics["mixed_steps"] += 1
        self.metrics["interleaved_prefill_tokens"] += take
        self.metrics["prefill_tokens"] += take
        if self._blocked(bucket, False):
            self.metrics["prefill_tokens_blocked"] += take
        if self._flight is not None:
            self._flight.note_mixed_step(
                pf.request.request_id, take, bucket, dispatch_s
            )
        # Mixed steps ride the same pipeline as plain decode chunks
        # (shared seam: _push_inflight).
        self._push_inflight(dtoks, active, dispatch_s)
        if plan is not None:
            # Acceptance decides the verify slots' next inputs — sync
            # the window's greedy tokens now (the piece/decode halves
            # of this dispatch materialize with them; the deferred
            # dtoks read above becomes a cheap ready-array copy).
            t_sync = time.monotonic()
            g = np.asarray(greedy)
            sync_s = time.monotonic() - t_sync
            self.metrics["decode_sync_s"] += sync_s
            self.metrics["spec_steps"] += 1
            self._spec_accept(plan, g, dispatch_s, sync_s)
        pf.next_piece += 1
        pf.frontier = off + take
        if pf.sess is not None:
            # Each consumed piece's rows are genuinely valid prompt KV:
            # recording them incrementally keeps a mid-prefill abort
            # (deadline/cancel) exact — the next turn reuses [0,
            # frontier) instead of re-prefilling the whole prompt.
            pf.sess.token_ids = list(pf.prompt[:pf.frontier])
            pf.sess.last_used = self.clock()
        if final:
            self._complete_interleaved(pf, first_tok, new_pkd, t_enq)

    def _complete_interleaved(self, pf, first_tok, new_pkd, t_enq) -> None:
        """The final piece sampled the first token: activate the slot —
        the back half of ``_place_request``, against the mixed program's
        already-advanced decode state. ``t_enq`` is when that piece was on
        the device's queue."""
        slot_idx, prompt = pf.slot_idx, pf.prompt
        if pf.sess is not None:
            pf.sess.token_ids = list(prompt)
        self._maybe_publish_prefix(slot_idx, prompt)
        # Paged pool: drop the final piece's bucket-padding slack (after
        # publish shared the prefix pages).
        self._trim_slot_pages(slot_idx, len(prompt))
        self.metrics["prefill_steps"] += 1
        self._prefilling = None
        with self._lock:
            self._placing -= 1
        # positions[slot_idx] already sits at n — the final piece's
        # frontier, where the first real decode write lands — and is
        # written there again.
        self._activate_slot(
            slot_idx, pf.request, pf.handle, first_tok, new_pkd,
            dict(reuse=pf.reuse, seeded=pf.seeded, stalled=False,
                 t_enq=t_enq),
        )

    # -- abort / failure ------------------------------------------------

    def _abort_prefilling(self, reason: FinishReason) -> None:
        """Terminal for a half-prefilled request (deadline reap or
        cancel): the consumed rows stay valid for the session — books
        were advanced per piece, so partial counts are already exact —
        and the slot quiesces at the consumed frontier."""
        pf = self._prefilling
        self._prefilling = None
        slot = self._slots[pf.slot_idx]
        pf.handle._push(
            StreamEvent(
                pf.request.request_id,
                finish_reason=reason,
                num_prompt_tokens=len(pf.prompt),
            )
        )
        self.metrics["requests_finished"] += 1
        if self._flight is not None:
            self._flight.note_terminal(pf.request.request_id, reason.value)
        quiesce_row = 0
        if pf.sess is not None:
            # token_ids already reads prompt[:frontier]; the rows below
            # it are genuine prompt KV the next turn can reuse.
            quiesce_row = len(pf.sess.token_ids)
        else:
            self._release_slot_seed(slot)
        self._free_slot(slot)
        # Paged pool: keep only the pages below the consumed frontier
        # (the session's reusable rows); everything else frees.
        self._trim_slot_pages(pf.slot_idx, quiesce_row)
        self._positions = self._positions.at[pf.slot_idx].set(quiesce_row)
        with self._lock:
            self._placing -= 1

    def _fail_prefilling(self, msg: str) -> None:
        """Hard-failure terminal for the in-flight prefill (a raised
        dispatch or recovery/_fail_all): the shared monolithic
        prefill-failure surface, with the accepted-and-placed prompt
        marker so the coordinator resubmits."""
        pf = self._prefilling
        if pf is None:
            return
        self._prefilling = None
        self._fail_placement(pf.slot_idx, pf.request, pf.handle, msg)
        with self._lock:
            self._placing -= 1
