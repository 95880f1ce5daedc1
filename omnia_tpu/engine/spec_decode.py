"""Prompt-lookup speculative decoding (EngineConfig.spec_decode).

Decode on TPU is HBM-bound: every step streams the full weight set for
one token per slot. The verify program streams the SAME weights over
T=W+1 tokens, so each accepted proposal is a nearly-free extra token —
the classic speculative-decoding win, with the draft model replaced by
prompt lookup (the strongest zero-cost proposer for chat/RAG/code
traffic, where continuations repeat spans of the prompt or history).

This module is a PER-SLOT capability, not an all-or-nothing step:

- **Per-slot adaptive depth.** Each slot proposes up to its own depth
  ``k_i``; with ``spec_decode_max > 0`` an accept-rate EMA drives
  ``k_i`` between 0 (lookup keeps missing / proposals keep losing —
  the slot stops proposing and rides the step as a plain-decode
  passenger, with a periodic 1-token re-probe) and ``spec_decode_max``
  (everything accepts). A lookup miss is the degenerate case: zero
  real proposals this step, zero cost. The compiled verify window stays
  the static ``[B, W+1]`` shape (W = ``EngineConfig.spec_window()``);
  per-slot depth only decides how many REAL proposals ride it.
- **Per-slot participation.** Greedy slots verify; sampled slots (and
  slots whose first token is not through) take the EXACT chunked
  sampling path — fused into the same dispatch via the ``verify_decode``
  program (programs.py): one verify window + one ``_mk_step_body`` scan
  step with the verify slots masked out of the scan, so sampled traffic
  keeps its per-slot PRNG reproducibility bit-for-bit. While a prefill
  piece is in flight (engine/interleave.py), the verify window rides
  the fused mixed dispatch the same way (``mixed_spec`` family).
- **Grammar-mask-aware verify.** The acceptance oracle applies each
  slot's device-resident ``[S, V]`` grammar rows as the same additive
  ``-inf`` bias the sampler uses (ops/sampling seam), advancing the
  per-slot FSM state across window positions along the PROPOSED stream
  — so every greedy token the oracle returns is admissible, structured-
  output slots speculate at full depth, and the old host-side
  truncation (``_spec_hold``) is gone.
- **Online self-gate.** :class:`_SpecGate` duty-cycles between
  spec-permitted and spec-suppressed probe windows, compares realized
  tokens/second, and disables speculation when it is not paying —
  reporting the decision in ``spec_gate_state`` and the bench's
  ``aux.greedy_spec.gate``. Verify steps are synchronous (acceptance
  decides the NEXT step's inputs), so they forgo the chunk pipeline —
  exactly the cost the gate weighs against the accepted-token win.

How a verify step works:

- Host proposes up to ``k_i`` tokens per verify slot from an
  INCREMENTAL, memory-bounded n-gram index over prompt+emitted
  (:class:`_NgramIndex`): the most recent earlier occurrence of the
  current tail n-gram (3→2→1), continued for ``k_i`` tokens.
- One compiled forward over ``[B, W+1]`` (last emitted token + padded
  proposals), writing KV rows at each slot's frontier. The (grammar-
  masked) greedy argmax over all W+1 positions is the acceptance
  oracle: the prefix of proposals matching the model's own choices is
  accepted, plus the model's next token after the accepted prefix
  ("bonus") — 1..W+1 tokens per weight stream, exactly what vanilla
  (masked) greedy decode would have produced.
- Rejected proposals' KV rows are garbage at rows ≥ the slot's new
  frontier — the invariant the whole cache design already tolerates.

Everything the step needs is HOST state (slot lengths, emitted tokens,
session frontiers), so in-flight decode chunks are flushed before a
verify dispatch — the engagement cost the old implementation dodged by
refusing to engage at all whenever the pipeline was busy.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from omnia_tpu.engine.types import EngineConfig

_NGRAM_MAX = 3
#: Entries kept per n-gram order per slot index (bounds host memory on
#: long sessions; see _NgramIndex eviction notes).
_NGRAM_CAP = 4096
#: Documented per-entry host-cost estimate for the ``spec_index_bytes``
#: gauge: key tuple (+ its ints) + dict slot + int value, rounded up.
_ENTRY_BYTES = 120
#: Accept-rate EMA smoothing for the per-slot depth controller.
_EMA_ALPHA = 0.25
#: Below this EMA a slot stops proposing entirely (depth 0) ...
_K_MIN_EMA = 0.125
#: ... and re-probes with a single proposal every this many verify
#: steps, so a slot whose traffic turns repetitive again can recover.
_RETRY_STEPS = 16


def validate_spec_config(ecfg: EngineConfig) -> None:
    """Construction-time validation (engine __init__ delegates here).
    ``spec_decode=0`` turns the whole subsystem off; the other knobs are
    then dead and deliberately unvalidated (the guarded-no-op rule)."""
    if not ecfg.spec_decode:
        return
    usable = ecfg.usable_buckets()
    w = ecfg.spec_window()
    if not usable or w + 1 > min(usable):
        # Rejected-proposal rows at an unpinned idle slot must be
        # covered by the next occupant's smallest prefill write.
        raise ValueError(
            f"spec window {w} (max of spec_decode={ecfg.spec_decode}, "
            f"spec_decode_max={ecfg.spec_decode_max}) needs "
            f"window + 1 <= min(prefill_buckets)"
        )
    if ecfg.spec_decode_max and ecfg.spec_decode_max < ecfg.spec_decode:
        raise ValueError(
            "spec_decode_max must be 0 (fixed depth) or >= spec_decode"
        )
    if ecfg.spec_gate_window < 0:
        raise ValueError("spec_gate_window must be >= 0")


def spec_depth_update(
    ema: float, real: int, accepted: int, kmax: int
) -> tuple[float, int]:
    """One accept-rate observation → (new EMA, new per-slot depth).

    The single depth policy, shared by the engine's per-slot controller
    and the MockEngine mirror so hermetic tests exercise the real
    curve: EMA of accepted/real; depth rounds the EMA up into
    [1, kmax], or 0 once the EMA falls under the floor (the slot then
    re-probes on the engine's _RETRY_STEPS cadence). kmax <= 0 means
    fixed-depth mode — the EMA still tracks (observability) but depth
    is pinned by the caller."""
    if real > 0:
        ema += _EMA_ALPHA * (accepted / real - ema)
    if kmax <= 0:
        return ema, 0
    if ema < _K_MIN_EMA:
        return ema, 0
    return ema, max(1, min(kmax, int(ema * kmax + 0.5)))


class _NgramIndex:
    """Incremental most-recent-occurrence index over an append-only
    token sequence: maps each n-gram (n = 1.._NGRAM_MAX) to the latest
    start position strictly BEFORE the current tail.

    Host memory is BOUNDED: each order keeps at most ``_NGRAM_CAP``
    entries, evicted least-recently-INGESTED first: a re-seen gram is
    re-inserted at the back of the dict's insertion order (delete +
    insert, O(1)), so the grams that keep recurring — prompt-lookup's
    highest-value hits — survive, and eviction drops grams the context
    never revisited. The RECENT context therefore stays fully indexed,
    which is where hits live."""

    __slots__ = ("maps", "built")

    def __init__(self):
        self.maps = {n: {} for n in range(1, _NGRAM_MAX + 1)}
        self.built = {n: 0 for n in range(1, _NGRAM_MAX + 1)}

    def entries(self) -> int:
        return sum(len(m) for m in self.maps.values())

    def propose(self, ctx: list[int], k: int) -> tuple[list[int], int]:
        """(k proposals zero-padded, number of REAL proposals)."""
        L = len(ctx)
        for n in range(min(_NGRAM_MAX, L - 1), 0, -1):
            m = self.maps[n]
            # Ingest every start whose gram lies fully before the tail
            # start (L - n); ctx only appends, so this is incremental.
            for i in range(self.built[n], L - n):
                gram = tuple(ctx[i:i + n])
                if gram in m:
                    del m[gram]  # re-insert at the back (recency order)
                elif len(m) >= _NGRAM_CAP:
                    del m[next(iter(m))]  # evict least-recently-ingested
                m[gram] = i
            self.built[n] = max(self.built[n], L - n)
            hit = m.get(tuple(ctx[L - n:]))
            if hit is not None:
                prop = ctx[hit + n:hit + n + k]
                if prop:
                    return prop + [0] * (k - len(prop)), len(prop)
        return [0] * k, 0


class _SpecGate:
    """Online self-gate: duty-cycle probe of realized decode throughput
    with speculation permitted vs suppressed.

    States cycle PROBE_SPEC(window ticks) → PROBE_PLAIN(window) →
    decide → HOLD_ON/HOLD_OFF(window × hold_factor) → re-probe. A tick
    is one scheduler step with live decode; the rate of a phase is
    (tokens generated) / (wall seconds) across it, so the comparison
    prices in EVERYTHING speculation changes — pipeline forfeiture,
    host propose time, verify sync — not just tokens per weight
    stream. Speculation must be at least ``margin`` of the plain rate
    to stay on; re-probing keeps a disable honest when traffic turns
    repetitive later. Host-side and jax-free; the engine skips ticking
    under an injected logical clock (multihost lockstep), where a
    wall-clock decision could diverge the replicated step streams."""

    PROBE_SPEC, PROBE_PLAIN, HOLD_ON, HOLD_OFF = range(4)
    _NAMES = {PROBE_SPEC: "probe_spec", PROBE_PLAIN: "probe_plain",
              HOLD_ON: "on", HOLD_OFF: "off"}

    def __init__(self, window: int, hold_factor: int = 8,
                 margin: float = 0.98):
        self.window = window
        self.hold_factor = hold_factor
        self.margin = margin
        self.state = self.PROBE_SPEC
        self.ticks = 0
        self.phase_t0: Optional[float] = None
        self.phase_tok0 = 0
        self.rate_spec: Optional[float] = None
        self.rate_plain: Optional[float] = None
        self.decisions = 0
        self.disables = 0

    def allows_spec(self) -> bool:
        return self.state in (self.PROBE_SPEC, self.HOLD_ON)

    def state_code(self) -> int:
        """Stable metric encoding: 0 = probing, 1 = on, 2 = off."""
        if self.state == self.HOLD_ON:
            return 1
        if self.state == self.HOLD_OFF:
            return 2
        return 0

    def tick(self, now: float, tokens: int) -> bool:
        """Advance one scheduler step; returns whether speculation is
        permitted for this step."""
        if self.window <= 0:
            return True
        if self.phase_t0 is None:
            self.phase_t0, self.phase_tok0 = now, tokens
        self.ticks += 1
        probing = self.state in (self.PROBE_SPEC, self.PROBE_PLAIN)
        limit = self.window if probing else self.window * self.hold_factor
        if self.ticks >= limit:
            rate = (tokens - self.phase_tok0) / max(now - self.phase_t0, 1e-9)
            if self.state == self.PROBE_SPEC:
                self.rate_spec = rate
                self.state = self.PROBE_PLAIN
            elif self.state == self.PROBE_PLAIN:
                self.rate_plain = rate
                self.decisions += 1
                if (self.rate_spec or 0.0) >= rate * self.margin:
                    self.state = self.HOLD_ON
                else:
                    self.state = self.HOLD_OFF
                    self.disables += 1
            else:
                # Hold expired: refresh that mode's rate and re-probe.
                if self.state == self.HOLD_ON:
                    self.rate_spec = rate
                else:
                    self.rate_plain = rate
                self.state = self.PROBE_SPEC
            self.ticks = 0
            self.phase_t0, self.phase_tok0 = now, tokens
        return self.allows_spec()

    def report(self) -> dict:
        """Bench/debug snapshot (aux.greedy_spec.gate)."""
        r = lambda v: None if v is None else round(v, 2)  # noqa: E731
        return {
            "state": self._NAMES[self.state],
            "rate_spec_tok_s": r(self.rate_spec),
            "rate_plain_tok_s": r(self.rate_plain),
            "decisions": self.decisions,
            "disables": self.disables,
        }


class _SpecPlan:
    """One step's speculative participation: the static [B, W+1] verify
    operands plus the host books acceptance needs."""

    __slots__ = ("toks", "pos", "wstart", "vmask", "proposals", "scan")

    def __init__(self, toks, pos, wstart, vmask, proposals, scan):
        self.toks = toks          # [B, W+1] int32: last token + proposals
        self.pos = pos            # [B, W+1] int32 window positions
        self.wstart = wstart      # [B] int32 per-slot write rows
        self.vmask = vmask        # [B] bool: slot rides the verify lane
        self.proposals = proposals  # {slot: (props padded to W, n real)}
        self.scan = scan          # [(slot, request_id)] scan-lane slots


class _SpecDecodeMixin:
    """Speculative-decode methods of :class:`InferenceEngine`."""

    # Engine-thread-owned controller state (built lazily on first use;
    # spec_decode=0 never touches any of it — the guarded no-op).
    _spec_gate: Optional[_SpecGate] = None
    _spec_ema_global = 0.0

    def _host_row(self, slot) -> int:
        """The row an INACTIVE slot's verify window may write from —
        mirrors the quiesce row _finish_slot chose, from host state
        only: the pinned session's valid frontier, else 0 (both are ≥
        any row the next occupant won't overwrite)."""
        sid = slot.session_id
        if sid:
            sess = self._sessions.get(sid)
            if sess is not None:
                return len(sess.token_ids)
        return 0

    def _spec_engaged(self) -> bool:
        """Config + gate check, shared by the standalone verify step and
        the mixed-dispatch fusion. Ticks the gate (one tick per
        scheduler step — each caller runs at most once per step)."""
        if not self.cfg.spec_decode or self._verify_fn is None:
            return False
        if self.cfg.spec_gate_window > 0 and self.clock is time.monotonic:
            # Replicated engines (multihost lockstep, injected logical
            # clock) skip the gate: a wall-clock disable on one rank
            # would diverge the compiled-step streams.
            if self._spec_gate is None:
                self._spec_gate = _SpecGate(self.cfg.spec_gate_window)
            allowed = self._spec_gate.tick(
                time.monotonic(), self.metrics["tokens_generated"]
            )
            self.metrics["spec_gate_state"] = self._spec_gate.state_code()
            if not allowed:
                return False
        return True

    def _slot_depth(self, slot) -> int:
        """Per-slot proposal budget for this step. Fixed-depth mode
        (spec_decode_max=0) always proposes cfg.spec_decode; adaptive
        mode follows the slot's EMA-driven depth, with a periodic
        1-token re-probe once the depth has collapsed to 0."""
        kmax = self.cfg.spec_decode_max
        if kmax <= 0:
            return self.cfg.spec_decode
        if slot.spec_k == 0:
            slot.spec_cool += 1
            if slot.spec_cool >= _RETRY_STEPS:
                slot.spec_cool = 0
                return 1
            return 0
        return slot.spec_k

    def _propose(self, slot, k: int, width: int) -> tuple[list[int], int]:
        """k proposals for a slot, zero-padded to the static window."""
        if k <= 0:
            return [0] * width, 0
        if slot.spec_index is None:
            slot.spec_index = _NgramIndex()
        ctx = slot.request.prompt_tokens + slot.emitted
        prop, real = slot.spec_index.propose(ctx, k)
        return prop + [0] * (width - len(prop)), real

    def _spec_plan(
        self, park: Optional[dict] = None, depths: Optional[dict] = None
    ) -> Optional[_SpecPlan]:
        """Plan this step's verify participation, or None when the step
        should ride the plain lane instead: no slot has a real proposal
        (a verify dispatch would be a synchronous plain step — strictly
        worse than the pipelined chunk path), or some slot's window
        would clamp at the cache end (a clamped dynamic_update_slice
        would corrupt earlier rows).

        ``park`` overrides the window row for specific INACTIVE slots —
        the interleave path parks the in-placement slot's garbage
        window at its piece frontier, where the next piece overwrites
        it (garbage only ever lives at rows ≥ the consumed frontier).

        ``depths`` memoizes per-slot proposal depths across the up-to-
        two plan calls one scheduler step makes (engage probe, then the
        post-flush plan): ``_slot_depth`` advances a collapsed slot's
        re-probe cooldown, so calling it twice per step would burn the
        periodic 1-token re-probe on the discarded first plan and run
        the cooldown at twice the documented cadence. Callers pass the
        SAME dict to every plan call of one step."""
        cfg = self.cfg
        W = cfg.spec_window()
        B, S = cfg.num_slots, cfg.max_seq
        toks = np.zeros((B, W + 1), np.int32)
        pos = np.zeros((B, W + 1), np.int32)
        wstart = np.zeros((B,), np.int32)
        vmask = np.zeros((B,), bool)
        proposals: dict[int, tuple[list[int], int]] = {}
        scan: list[tuple[int, str]] = []
        total_real = 0
        ar = np.arange(W + 1, dtype=np.int32)
        for i, s in enumerate(self._slots):
            if s.active:
                if s.length + W + 2 > S:
                    return None  # window (or its scan park row) would clamp
                wstart[i] = s.length
                pos[i] = s.length + ar
                if s.request.params.temperature == 0.0 and s.emitted:
                    # Verify lane — grammar slots included (the oracle
                    # masks on device). Zero-proposal slots still ride
                    # it: their "bonus" position IS a fused plain-decode
                    # token, so low-accept slots cost nothing extra.
                    if depths is not None and i in depths:
                        k_i = depths[i]
                    else:
                        k_i = self._slot_depth(s)
                        if depths is not None:
                            depths[i] = k_i
                    prop, real = self._propose(s, k_i, W)
                    vmask[i] = True
                    proposals[i] = (prop, real)
                    toks[i, 0] = s.emitted[-1]
                    toks[i, 1:] = prop
                    total_real += real
                else:
                    # Sampled (or first token not yet through): the
                    # exact chunked sampling path, fused as the scan
                    # half of the same dispatch. Its window write is
                    # garbage at rows ≥ its frontier; the scan half
                    # overwrites row `length` with the real token.
                    scan.append((i, s.request.request_id))
            else:
                row = park.get(i) if park else None
                row = self._host_row(s) if row is None else row
                if row + W + 1 > S:
                    # Frozen rows near the cache end: the garbage window
                    # would clamp back over valid rows — plain lane.
                    return None
                wstart[i] = row
                pos[i] = row + ar
        if total_real == 0:
            return None
        return _SpecPlan(toks, pos, wstart, vmask, proposals, scan)

    def _spec_step(self) -> bool:
        """Try one speculative step from the scheduler (no prefill piece
        in flight). Returns True when this method did the step's work;
        False sends the caller down the plain chunked lane.

        While speculation is live (configured, gate-permitted, and at
        least one verify-capable slot exists) the engine decodes at
        SINGLE-STEP granularity: a step with proposals dispatches the
        verify window; a step without them probes with one exact
        1-token decode step, so the moment the stream turns repetitive
        the very next step can speculate — chunk-granular probing would
        forfeit up to a whole chunk of accepted tokens at every
        transition. Single-step probing trades the chunk pipeline for
        that responsiveness; the self-gate measures the realized rate
        and flips the whole batch back to pipelined chunks when
        speculation (probing included) is not paying."""
        if not self._spec_engaged():
            return False
        if not any(
            s.active and s.request.params.temperature == 0.0 and s.emitted
            for s in self._slots
        ):
            return False  # nothing can verify — pure sampled traffic
        if self._inflight:
            # Acceptance decides the NEXT step's inputs, so the verify
            # window must start from settled host books: land in-flight
            # chunk tokens first (proposals from a stale tail would
            # corrupt the window), then plan against the moved
            # frontiers.
            self._flush_pipeline()
            if not any(s.active for s in self._slots):
                # The flush finished everything — processing those
                # chunks WAS this step's work; a probe dispatch over an
                # all-idle batch would be a pure garbage forward.
                return True
        plan = self._spec_plan(depths={})
        if plan is None:
            self._dispatch_decode(single=True)
            self._process_oldest_chunk()
            return True
        self._spec_dispatch(plan)
        return True

    def _spec_dispatch(self, plan: _SpecPlan) -> None:
        """One verify dispatch + host acceptance/emission (synchronous:
        there is nothing to pipeline behind an acceptance decision).
        All-greedy batches ride the pure ``verify`` program; batches
        with scan-lane slots ride ``verify_decode`` — the same verify
        window plus one exact decode step for the scan slots."""
        import jax.numpy as jnp

        W = self.cfg.spec_window()
        # Paged pool: every active slot's window rows need exclusive
        # pages before dispatch (scan-lane slots too — their garbage
        # window must land in owned pages, never a freed one). Idle
        # slots' frozen-row windows write garbage only — through owned
        # partial pages or the trash page — so they need none.
        for i, s in enumerate(self._slots):
            if s.active:
                self._prepare_slot_write(
                    i, s.length, min(s.length + W + 1, self.cfg.max_seq)
                )
        gargs = (
            (self._gstate, self._gtable, self._gactive) if self._gr_on else ()
        )
        t_dispatch = time.monotonic()
        dtoks = None
        if plan.scan:
            out = self._verify_decode_fn(
                self.params, self._ck, self._cv, self._tokens,
                self._positions, self._active, self._budget, self._stop_ids,
                self._key_data, self._temp, self._top_p, self._top_k,
                jnp.asarray(plan.toks), jnp.asarray(plan.pos),
                jnp.asarray(plan.wstart), jnp.asarray(plan.vmask), *gargs,
            )
            if self._gr_on:
                (self._ck, self._cv, self._tokens, self._positions,
                 self._active, self._budget, self._key_data, self._gstate,
                 dtoks, greedy) = out
            else:
                (self._ck, self._cv, self._tokens, self._positions,
                 self._active, self._budget, self._key_data,
                 dtoks, greedy) = out
        else:
            self._ck, self._cv, greedy = self._verify_fn(
                self.params, self._ck, self._cv,
                jnp.asarray(plan.toks), jnp.asarray(plan.pos),
                jnp.asarray(plan.wstart), *gargs,
            )
        dispatch_s = time.monotonic() - t_dispatch
        self.metrics["decode_dispatch_s"] += dispatch_s
        t_sync = time.monotonic()
        g = np.asarray(greedy)  # [B, W+1]
        host_toks = np.asarray(dtoks) if dtoks is not None else None
        sync_s = time.monotonic() - t_sync
        self.metrics["decode_sync_s"] += sync_s
        self.metrics["spec_steps"] += 1
        if dtoks is not None:
            self._count_decode_dispatch(1, plan.scan)
        self._spec_accept(plan, g, dispatch_s, sync_s)
        if host_toks is not None:
            # Scan-lane emission: the exact chunk-processing loop at
            # K=1 (the dispatch was synchronous, so the snapshot's
            # identity check only guards finishes earlier this loop).
            for i, rid in plan.scan:
                slot = self._slots[i]
                if not slot.active or slot.request.request_id != rid:
                    continue
                slot.length += 1
                self._emit_token(i, int(host_toks[0, i]))

    def _spec_accept(
        self, plan: _SpecPlan, g: np.ndarray, dispatch_s: float, sync_s: float
    ) -> None:
        """Host acceptance + emission for the verify lane: the matching
        proposal prefix plus the model's bonus token, then per-slot
        depth/EMA updates and the books."""
        W = self.cfg.spec_window()
        step_prop = step_acc = 0
        for i, (prop, real) in plan.proposals.items():
            s = self._slots[i]
            if not s.active:
                continue  # cancelled/finished between dispatch and here
            accepted = 0
            while accepted < W and prop[accepted] == g[i, accepted]:
                accepted += 1
            # Grammar slots: g is the MASKED argmax and its FSM walk
            # followed the proposals, so every token in the accepted
            # prefix (and the bonus) is admissible by construction —
            # emission needs no host-side truncation.
            emit = [*prop[:accepted], int(g[i, accepted])]
            # Metrics count only GENUINE proposals (padding that happens
            # to match would inflate the acceptance rate operators tune
            # against); emission still uses every matching token — a
            # matched pad IS the model's own choice.
            acc_real = min(accepted, real)
            step_prop += real
            step_acc += acc_real
            self.metrics["spec_proposed"] += real
            self.metrics["spec_accepted"] += acc_real
            if real > 0:
                s.spec_ema, new_k = spec_depth_update(
                    s.spec_ema, real, acc_real, self.cfg.spec_decode_max
                )
                if self.cfg.spec_decode_max > 0:
                    s.spec_k = new_k
                self._spec_ema_global += _EMA_ALPHA * (
                    acc_real / real - self._spec_ema_global
                )
                self.metrics["spec_accept_ema"] = round(
                    self._spec_ema_global, 4
                )
            # Emit accepted proposals then the bonus token, mirroring the
            # chunk path's bookkeeping (length BEFORE emit; stop/max
            # checks inside _emit_token can finish the slot mid-list).
            for tok in emit:
                s.length += 1
                self._emit_token(i, int(tok))
                if not s.active:
                    break
            if s.active:
                # Device state must match the host frontier exactly so a
                # later fallback to the chunked path stays coherent (the
                # device budget is not decremented here: it only ever
                # over-allows, and the host finish check fires first).
                self._tokens = self._tokens.at[i].set(int(s.emitted[-1]))
                self._positions = self._positions.at[i].set(s.length)
                if s.gr_view is not None and emit:
                    # _emit_token advanced the host FSM mirror; the device
                    # copy advances only inside compiled steps, so resync
                    # it or the next masked step gathers a stale row.
                    self._gstate = self._gstate.at[i].set(s.gr_state)
        self.metrics["spec_index_bytes"] = _ENTRY_BYTES * sum(
            s.spec_index.entries()
            for s in self._slots if s.spec_index is not None
        )
        if self._flight is not None:
            self._flight.note_spec_verify(
                step_prop, step_acc, dispatch_s, sync_s, len(plan.proposals)
            )
