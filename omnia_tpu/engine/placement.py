"""Request placement for the serving engine.

Placement takes a queued request to its first sampled token: fresh
single-bucket prefill when nothing is reusable, chunked incremental
extend from the session/pool reuse frontier otherwise — plus the
grammar-constrained-decoding attach path (per-slot FSM table upload,
start-state bias for the first token, host state mirror).

Mixed into :class:`InferenceEngine` (same seam-per-concern layout as the
scheduler/session/prefix-cache mixins): everything here operates on the
engine's slots, device state, and compiled programs.
"""

from __future__ import annotations

import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from omnia_tpu.engine.family import prefill_blocked
from omnia_tpu.engine.phases import PREFILL_DISPATCH, phase
from omnia_tpu.engine.sessions import _SessionKV
from omnia_tpu.engine.types import (
    MAX_DEVICE_STOP_IDS,
    Request,
    RequestHandle,
    SamplingParams,
)
from omnia_tpu.ops.sampling import _NEG_INF, make_slot_key_data


#: The per-slot device vectors a placement writes (``activate_slot``'s
#: leading operands, in order) and those a finish does (``release_slot``'s).
ACTIVATED = ("_tokens", "_positions", "_active", "_temp", "_top_p", "_top_k",
             "_budget", "_stop_ids", "_key_data")
RELEASED = ("_positions", "_tokens", "_temp", "_active")


class _PlacementMixin:
    """Placement methods of :class:`InferenceEngine`."""

    def _sampling_key(self, slot_idx: int, sp: SamplingParams):
        return (
            jnp.asarray(make_slot_key_data(sp.seed))
            if sp.seed is not None
            else self._key_data[slot_idx]
        )

    # -- grammar-constrained decoding helpers ---------------------------

    def _validate_grammar(self, grammar, sp: SamplingParams) -> Optional[str]:
        """Submit-time rejection with a real error surface (placement
        failures only say 'prefill failed')."""
        if not self._gr_on:
            return "grammar-constrained request on an engine built with grammar=off"
        from omnia_tpu.engine.grammar.fsm import GrammarError

        try:
            # Budget + liveness on the exact [S, V] view placement will
            # upload (memoized), so placement cannot hit a grammar error
            # later — without materializing the padded [max_states, V]
            # table the check never reads.
            grammar.validate(
                self.cfg.grammar_max_states, self.model_cfg.vocab_size,
                sp.stop_token_ids,
            )
        except GrammarError as e:
            return f"grammar rejected: {e}"
        return None

    def _sync_grammar_cache_metrics(self) -> None:
        from omnia_tpu.engine.grammar.cache import stats

        self.metrics["grammar_compile_hits"] = stats["hits"]
        self.metrics["grammar_compile_misses"] = stats["misses"]

    def _grammar_args(self, request: Optional[Request], sp: SamplingParams):
        """Extra first-token sampler operand: the start-state mask bias.
        () when grammar support is off (the programs were traced without
        the operand); a zero bias for ungrammared requests."""
        if not self._gr_on:
            return ()
        g = request.grammar if request is not None else None
        if g is None:
            return (self._gbias_zero,)
        view = g.view(self.model_cfg.vocab_size, sp.stop_token_ids)
        row = view.table[view.start]
        bias = np.where(row < 0, _NEG_INF, 0.0).astype(np.float32)
        return (jnp.asarray(bias),)

    def _attach_grammar(self, slot_idx: int, request: Request,
                        first_tok: int) -> None:
        """Upload the request's transition table + post-first-token FSM
        state into the slot's device rows; mirror the state on the host
        slot (metrics + mock parity)."""
        slot = self._slots[slot_idx]
        g = request.grammar
        if not self._gr_on or g is None:
            return  # the gate was closed by the slot's activation
        sp = request.params
        view = g.view(self.model_cfg.vocab_size, sp.stop_token_ids)
        state0 = view.advance(view.start, first_tok)
        if state0 < 0:  # first token finished the request (stop id)
            state0 = view.start
        # Upload the grammar's rows only when the slot doesn't already
        # hold them (same grammar + same stop-id set — the common case of
        # one schema served across many requests). Keying on the grammar
        # OBJECT when it has no content key pins it alive, so a recycled
        # id() can never alias a stale mirror entry. The upload writes
        # the unpadded [S, V] view: states ≥ S are unreachable (every
        # transition targets a state < S), so stale rows above S from a
        # previous occupant are dead weight, not a hazard — and the
        # padded [max_states, V] host array never gets built.
        gkey = (
            g.key or g,
            tuple(sorted({g.eos_id, *sp.stop_token_ids})),
        )
        if self._gslot_key[slot_idx] != gkey:
            if view.num_states > self.cfg.grammar_max_states:
                from omnia_tpu.engine.grammar.fsm import GrammarTooLarge

                raise GrammarTooLarge(  # submit validates; belt-and-braces
                    f"grammar needs {view.num_states} states, engine "
                    f"grammar_max_states is {self.cfg.grammar_max_states}"
                )
            self._gtable = self._gtable.at[slot_idx, : view.num_states].set(
                jnp.asarray(view.table)
            )
            self._gslot_key[slot_idx] = gkey
        self._gstate = self._gstate.at[slot_idx].set(state0)
        self._gactive = self._gactive.at[slot_idx].set(True)
        slot.grammar = g
        slot.gr_view = view
        slot.gr_state = view.start  # _emit_token advances for first_tok
        if self._flight is not None:
            self._flight.note_grammar_attach(
                request.request_id, view.num_states
            )

    def _run_insert(self, chunks, slot_idx, last_logits, sp=None,
                    request=None):
        """``chunks``: one prefill chunk for each array of the cache."""
        sp = sp or SamplingParams()
        kd = self._sampling_key(slot_idx, sp)
        *cache, tok, new_kd = self._insert_fn(
            *self._cache,
            *chunks,
            slot_idx,
            last_logits,
            kd,
            jnp.float32(sp.temperature),
            jnp.float32(sp.top_p),
            jnp.int32(sp.top_k),
            *self._grammar_args(request, sp),
        )
        return tuple(cache), tok, new_kd

    def _prepare_session_slot(
        self, slot_idx: int, request: Request
    ):
        """Session front-half of placement, shared by the monolithic and
        the interleaved (engine/interleave.py) paths: look up / create
        the session record, compute the resident-row LCP reuse, restore
        a host-paged session, and pin the slot. Returns the (possibly
        re-targeted) ``(slot_idx, sess, reuse)``."""
        prompt = request.prompt_tokens
        n = len(prompt)
        sess = None
        reuse = 0
        if self.cfg.max_sessions > 0 and request.session_id:
            sess = self._sessions.get(request.session_id)
            if sess is None:
                sess = self._sessions[request.session_id] = _SessionKV(
                    request.session_id, now=self.clock()
                )
                self._enforce_session_cap()
            sess.last_used = self.clock()
            # Longest common prefix with the cached rows, capped at n-1 so
            # there is always ≥1 suffix token to produce the next logits.
            limit = min(len(sess.token_ids), n - 1)
            while reuse < limit and sess.token_ids[reuse] == prompt[reuse]:
                reuse += 1
            if sess.slot is None and sess.host_k is not None:
                if reuse > 0:
                    self._restore_session(sess, slot_idx)
                else:
                    sess.host_k = sess.host_v = None  # diverged: page is useless
            if sess.slot is None:
                sess.slot = slot_idx
                self._slots[slot_idx].session_id = sess.session_id
            slot_idx = sess.slot
            if reuse == 0:
                sess.token_ids = []
        return slot_idx, sess, reuse

    def _place_request(self, slot_idx: int, request: Request,
                       handle: RequestHandle, span=None):
        """Prefill a request into a slot: fresh single-bucket prefill when
        there is no reusable prefix and the prompt fits one bucket,
        otherwise chunked incremental extend from the reuse frontier.
        ``span`` is the caller's open ``omnia.engine.place`` phase, if a
        profiler session is on: it is told the slot and the reuse."""
        prompt = request.prompt_tokens
        n = len(prompt)
        slot_idx, sess, reuse = self._prepare_session_slot(slot_idx, request)

        sp = request.params
        usable = self.cfg.usable_buckets()
        t_prefill = time.monotonic()
        # No same-session rows to extend from: longest-prefix-match the
        # cross-session pool and seed-copy the shared rows, so a FRESH
        # session of a known pack prefills only its suffix.
        seeded = 0
        if reuse == 0:
            seeded = self._try_seed_from_pool(slot_idx, prompt, sess)
        frontier = reuse or seeded
        if frontier == 0:
            # Paged pool: a cold start owns no history — return any
            # stale pages (a diverged session's, a dropped pin's) to
            # the free list before the bucket write allocates fresh
            # ones. No-op on the contiguous layout.
            self._free_slot_pages(slot_idx)
        # Prefill-first bookkeeping: every prefill forward dispatched
        # while a decode slot sits live is a stall step — the decode
        # batch idles for the whole dispatch. The token-budget policy
        # (engine/interleave.py) exists to drive this to zero.
        stalled = any(s.active for s in self._slots)
        ext0 = self.metrics["extend_steps"]
        if frontier == 0 and n <= max(usable):
            first_tok, new_kd = self._fresh_prefill(slot_idx, prompt, sp, request)
        else:
            first_tok, new_kd = self._chunked_extend(
                slot_idx, prompt, frontier, sp, request
            )
        if stalled:
            stall_steps = max(self.metrics["extend_steps"] - ext0, 1)
            self.metrics["decode_stall_steps"] += stall_steps
            if self._flight is not None:
                self._flight.note_stall(stall_steps)
        self._maybe_publish_prefix(slot_idx, prompt)
        # Paged pool: the bucket-padded prefill covered rows past the
        # prompt — return that slack now (publish above already shares
        # the prefix pages, so only pad pages free). The next decode
        # write re-allocates its page in the pre-dispatch prealloc.
        self._trim_slot_pages(slot_idx, n)
        # Every piece of the placement is on the device's queue: the
        # host's part of it ends here (LatencyBreakdown.place_s), and what
        # follows to the first token is the device's (prefill_s).
        t_enq = time.monotonic()
        self.metrics["prefill_dispatch_s"] += t_enq - t_prefill
        self.metrics["prefix_reuse_tokens"] += reuse
        self.metrics["prefill_tokens"] += n - frontier
        self.metrics["prefill_steps"] += 1
        if sess is not None:
            sess.token_ids = list(prompt)
        deferred = self._activate_slot(
            slot_idx, request, handle, first_tok, new_kd,
            dict(reuse=reuse, seeded=seeded, stalled=stalled, t_enq=t_enq),
        )
        if span:
            span.set_metadata(
                slot=slot_idx, reuse=reuse, seeded=seeded, deferred=deferred
            )

    def _defers_first_token(self, request: Request) -> bool:
        """Whether a placement may end with its first token unread. The
        host needs the token at placement only to advance a grammar's
        start state (``_attach_grammar``) and to propose from
        ``slot.emitted`` (per-slot speculation); everything else needs the
        prompt and the slot. Reads the request and the configuration
        alone, so lockstep ranks agree."""
        return request.grammar is None and not self.cfg.spec_decode

    def _activate_slot(self, slot_idx: int, request: Request,
                       handle: RequestHandle, first_tok, new_kd,
                       note: dict) -> bool:
        """The tail every placement path shares, once the prefill that
        sampled ``first_tok`` is on the device's queue: the host's slot
        record, the slot's device state in ONE program call, and the
        first token — as an entry of the pipeline, ahead of any decode
        chunk dispatched after it, so that the thread enqueues the step
        that follows the prefill before it reads anything back; read at
        once where the host needs it (``_defers_first_token``). ``note``
        is the rest of the flight recorder's placement note. Returns
        whether the token was deferred."""
        sp = request.params
        n = len(request.prompt_tokens)
        slot = self._slots[slot_idx]
        slot.request = request
        slot.handle = handle
        slot.length = n
        slot.generated = 0
        slot.emitted = []
        slot.max_total = sp.max_tokens
        if self.cfg.spec_decode:
            slot.spec_reset(self.cfg.spec_decode, self.cfg.spec_decode_max)
        ids = list(sp.stop_token_ids)
        if request.grammar is not None and request.grammar.eos_id not in ids:
            # In terminal accepting states the grammar view unmasks ONLY
            # its eos id — the engine must finish on it even when the
            # caller's stop set omits it, or the slot streams raw EOS
            # tokens until the budget runs out (valid JSON + EOS spam,
            # finish_reason LENGTH, and mock/compiled parity broken).
            ids.append(request.grammar.eos_id)
        slot.stop_ids = frozenset(ids)
        # Device-side finish state: decode emissions still allowed after
        # the first token. MUST equal the host's finish schedule exactly
        # (generated >= max_tokens OR length >= max_seq - 2, checked after
        # each emission): a device mask firing EARLIER than the host's
        # would freeze the slot while the host keeps consuming its chunk
        # rows as real tokens. Stop-id row is -1 padded; ids past
        # MAX_DEVICE_STOP_IDS are host-checked only (host-early is safe).
        budget = max(min(sp.max_tokens - 1, self.cfg.max_seq - 2 - n), 0)
        ids = ids[:MAX_DEVICE_STOP_IDS]
        ids += [-1] * (MAX_DEVICE_STOP_IDS - len(ids))
        self._run_slot_program(
            self._activate_slot_fn, ACTIVATED, first_tok, new_kd,
            np.asarray([slot_idx, n, sp.top_k, budget, *ids], np.int32),
            np.asarray([sp.temperature, sp.top_p], np.float32),
        )
        note = dict(note, slot=slot_idx, n_prompt=n)
        rid = request.request_id
        if self._defers_first_token(request):
            self.metrics["placements_deferred"] += 1
            self._push_inflight(first_tok, [(slot_idx, rid)], 0.0, note)
            return True
        t_read0 = time.monotonic() if self._flight is not None else 0.0
        first = int(first_tok)
        if self._flight is not None:
            note = dict(note, t_read0=t_read0, t_read=time.monotonic())
        self._attach_grammar(slot_idx, request, first)
        self._emit_first_token(slot_idx, rid, first, note)
        return False

    def _run_slot_program(self, fn, names, *operands) -> None:
        """Call ``activate_slot`` / ``release_slot`` (programs.py): the
        per-slot vectors ``names`` in, donated, and out, the host's
        ``operands`` between them and a grammar engine's gate."""
        gate = ("_gactive",) if self._gr_on else ()
        out = fn(
            *(getattr(self, name) for name in names), *operands,
            *(getattr(self, name) for name in gate),
        )
        for name, vector in zip(names + gate, out):
            setattr(self, name, vector)

    def _emit_first_token(self, slot_idx: int, rid: str, token: int,
                          note: dict) -> Optional[dict]:
        """A placed request's first token, on the host: its event, under
        the guard ``_emit_chunk`` applies (the slot still holds that
        request). Returns the placement's stages as the flight recorder
        noted them (``note_placement``), or None without one."""
        slot = self._slots[slot_idx]
        if not slot.active or slot.request.request_id != rid:
            return None
        stages = None
        if self._flight is not None:
            # Recorded just BEFORE the first token emits, so that a
            # request this token ends has its placement on the books at
            # its terminal; the breakdown's stages tile the wall: queue
            # (submit→claim) + place (claim→enqueued) + prefill (→first
            # token) + decode (first token→terminal).
            stages = self._flight.note_placement(rid, **note)
        self._emit_token(slot_idx, token)
        return stages

    def _blocked(self, bucket: int, fresh: bool) -> bool:
        """Whether the program of ``bucket`` prompt rows (``fresh``: the
        fresh prefill; else an extend piece or a mixed step) runs its
        attention through the blocked kernel (family.py::prefill_blocked):
        ``prefill_tokens_blocked`` counts the prompt tokens dispatched
        through such a program."""
        return prefill_blocked(self.model_cfg, self.cfg, self._mesh, bucket, fresh)

    def _blocked_buckets(self) -> dict:
        """The buckets whose fresh prefill / extend piece takes the kernel."""
        buckets = sorted(self.cfg.usable_buckets())
        return {"prefill": [b for b in buckets if self._blocked(b, True)],
                "extend": [b for b in buckets if self._blocked(b, False)]}

    def _fresh_prefill(self, slot_idx: int, prompt: list[int],
                       sp: SamplingParams, request: Optional[Request] = None):
        """``(first_tok, new_key_data)``, both still on the device."""
        n = len(prompt)
        bucket = self.cfg.bucket_for(n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = prompt
        # Pad rows sit at positions n..bucket-1, i.e. strictly after every
        # real query position, so the causal mask (key_idx <= q_pos) already
        # excludes them — and decode overwrites each pad row before it first
        # becomes attendable.
        pos = np.arange(bucket, dtype=np.int32)[None, :]
        # Paged pool: the fused prefill writes the whole bucket —
        # exclusive pages must cover it before dispatch.
        self._prepare_slot_write(slot_idx, 0, bucket)
        if (
            self._prefill_ring_fn is not None
            and bucket >= self.cfg.long_prefill_threshold
            and bucket % self.cfg.sp == 0
        ):
            # Ring path: the sp-sharded prefill stays its own program;
            # its KV chunk gathers into the slot via the insert step.
            last, *chunks = self._prefill_ring_fn(
                self.params, toks, pos, np.int32(n - 1)
            )
            self._cache, first_tok, new_kd = self._run_insert(
                chunks, slot_idx, last, sp, request=request,
            )
            return first_tok, new_kd
        kd = self._sampling_key(slot_idx, sp)
        t0 = time.monotonic()
        with phase(PREFILL_DISPATCH) as span:
            if span:
                span.set_metadata(
                    request_id=request.request_id if request else "",
                    take=n, bucket=bucket,
                    seq=next(self._prefill_seq), last=True,
                )
            # Host operands go in as numpy: the call transfers them in
            # one batch, where a ``jnp`` constructor each is a device put
            # of its own on the thread the device is waiting for.
            *cache, first_tok, new_kd = self._prefill_insert_fn(
                self.params, *self._cache, toks, pos,
                np.int32(slot_idx), np.int32(n - 1), kd,
                np.float32(sp.temperature), np.float32(sp.top_p),
                np.int32(sp.top_k),
                *self._grammar_args(request, sp),
            )
            self._cache = tuple(cache)
        if self._blocked(bucket, True):
            self.metrics["prefill_tokens_blocked"] += n
        if self._flight is not None and request is not None:
            self._flight.note_prefill_piece(
                request.request_id, n, bucket, time.monotonic() - t0
            )
        return first_tok, new_kd

    def _extend_pieces(self, start: int, count: int) -> list[tuple[int, int, int]]:
        """Plan (offset, real_len, bucket) chunks covering prompt[start:
        start+count]. Bucket-padded writes must never cross max_seq (a
        clamped dynamic_update_slice would corrupt earlier rows), so near
        the cache end chunks degrade to single-token steps."""
        buckets = sorted(self.cfg.usable_buckets())
        S = self.cfg.max_seq
        pieces = []
        pos, left = start, count
        while left > 0:
            b = buckets[-1] if left >= buckets[-1] else self.cfg.bucket_for(left)
            if pos + b > S:
                b = 1
            take = min(left, b)
            pieces.append((pos, take, b))
            pos += take
            left -= take
        return pieces

    def _chunked_extend(
        self, slot_idx: int, prompt: list[int], reuse: int,
        sp: SamplingParams, request: Optional[Request] = None,
    ):
        """Incremental prefill of prompt[reuse:] against the slot's resident
        rows; only the final chunk samples: ``(first_tok, new_key_data)``,
        both still on the device."""
        pieces = self._extend_pieces(reuse, len(prompt) - reuse)
        slot_arr = np.int32(slot_idx)

        def chunk_arrays(off, take, b):
            # numpy, like every host operand below (see _fresh_prefill)
            toks = np.zeros((1, b), np.int32)
            toks[0, :take] = prompt[off:off + take]
            return toks, (off + np.arange(b, dtype=np.int32))[None, :]

        rid = request.request_id if request is not None else ""
        for off, take, b in pieces[:-1]:
            toks, pos = chunk_arrays(off, take, b)
            # Paged pool: each bucket-padded piece write needs exclusive
            # pages through its end — the first piece after a seed also
            # copy-on-writes the shared boundary page here.
            self._prepare_slot_write(slot_idx, off, off + b)
            t0 = time.monotonic()
            with phase(PREFILL_DISPATCH) as span:
                if span:
                    span.set_metadata(
                        request_id=rid, take=take, bucket=b,
                        seq=next(self._prefill_seq), last=False,
                    )
                self._cache = self._extend_nosample_fn(
                    self.params, *self._cache, toks, pos, slot_arr,
                    np.int32(off),
                )
            if self._flight is not None and rid:
                self._flight.note_prefill_piece(
                    rid, take, b, time.monotonic() - t0
                )
        off, take, b = pieces[-1]
        toks, pos = chunk_arrays(off, take, b)
        self._prepare_slot_write(slot_idx, off, off + b)
        kd = self._sampling_key(slot_idx, sp)
        t0 = time.monotonic()
        with phase(PREFILL_DISPATCH) as span:
            if span:
                span.set_metadata(
                    request_id=rid, take=take, bucket=b,
                    seq=next(self._prefill_seq), last=True,
                )
            *cache, first_tok, new_kd = self._extend_fn(
                self.params, *self._cache, toks, pos, slot_arr,
                np.int32(off), np.int32(take - 1), kd,
                np.float32(sp.temperature), np.float32(sp.top_p),
                np.int32(sp.top_k),
                *self._grammar_args(request, sp),
            )
            self._cache = tuple(cache)
        if self._flight is not None and rid:
            self._flight.note_prefill_piece(rid, take, b, time.monotonic() - t0)
        self.metrics["extend_steps"] += len(pieces)
        self.metrics["extend_tokens"] += len(prompt) - reuse
        self.metrics["prefill_tokens_blocked"] += sum(
            take for _, take, b in pieces if self._blocked(b, False))
        return first_tok, new_kd
