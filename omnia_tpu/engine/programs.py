"""Compiled XLA programs for the serving engine.

Every device computation the engine dispatches is built here, once, at
engine construction — the request path never traces or compiles (the
TTFT discipline; readiness implies every program below is AOT-warm).

Program inventory (all static-shaped, KV caches donated where they flow
through):

- ``prefill_insert`` — fused fresh-prefill: forward + cache insert +
  first-token sample in ONE dispatch (every dispatch is a host↔device
  round trip that TTFT pays for; one program, one round trip).
- ``prefill_ring`` — long-context prefill (sp > 1): ring attention
  splits the O(T²) attention of buckets ≥ long_prefill_threshold across
  the sp mesh axis (SURVEY §5.7). Returns the last row's logits [1, V]
  and the KV chunks: ``insert``'s operands.
- ``insert`` — place a prefill KV chunk into a slot's rows + sample the
  first token (the gather step after a ring prefill).
- ``decode_fns`` — chunked decode: `k` decode steps in one compiled
  ``lax.scan`` program per chunk-size variant, with stop-token/length
  finishes masked ON DEVICE so mid-chunk finishes stop writing rows.
- ``mixed`` / ``mixed_sample`` — stall-free batching
  (``prefill_chunk_tokens > 0``): per prefill-piece bucket, ONE fused
  dispatch that runs a bounded prompt piece through the extend seam
  into the in-placement slot's rows AND advances every active decode
  slot by one token (the same scan body as ``decode_fns``, length 1).
  ``mixed_sample`` is the final-piece variant — it additionally samples
  the placed request's first token (with the grammar start-state bias,
  like ``extend``). An arriving prefill then costs decode at most one
  mixed step of latency instead of a full prefill stall.
- ``verify`` / ``verify_decode`` / ``mixed_spec`` /
  ``mixed_spec_sample`` — speculative decoding (``spec_decode > 0``):
  the grammar-mask-aware verify window, the window fused with one exact
  decode step for non-verify slots, and the window riding the mixed
  prefill-piece dispatches (engine/spec_decode.py drives all four).
- ``extend`` / ``extend_nosample`` — sessionful incremental prefill:
  run a prompt suffix through ``forward`` against the slot's EXISTING
  rows (cross-attention to history) from the reuse frontier; batch-1 on
  a sliced slot cache so one slot's cache moves, not B× suffix FLOPs.
- ``activate_slot`` / ``release_slot`` — a slot's per-slot device state
  (token, position, active flag, sampling parameters, budget, stop row,
  sampler key) written by ONE dispatch at placement and at a finish, so
  that the engine thread never updates it op by op.
- ``offload`` / ``restore`` — session paging: pull/push one slot's
  leading KV rows in fixed restore-bucket shapes (device↔host transfers
  stay compile-stable).
- ``prefix_store`` / ``prefix_seed`` / ``prefix_offload`` — shared-prefix
  pool transfers (engine/prefix_cache.py): copy a slot's leading rows
  into a pool entry, seed-copy a pool entry into a fresh slot, and pull
  a pool entry to host RAM for the paged tier. All device↔device (store
  and seed never cross the host link) in fixed prefix-bucket shapes.

First-token row: a program that samples a placement's first token hands
the prompt's last real row (``last_idx``) to the model as ``row=``. The
model takes that one row of the stream BEFORE its final norm and head and
returns [1, V] logits, so no [1, T, V] value exists in these programs.
Without a row the model returns every row: ``verify`` reads them all,
``extend_nosample`` and the other mixed pieces drop them (and XLA the head).

Model family and cache: the programs reach the model through
``models.model_module(cfg)`` and through nothing else, and a family's cache
is an opaque TUPLE of arrays (Llama's K and V; the latent family's one, or its rows
beside a recurrent state and a convolution's tail) that ``prefill_insert``, ``insert``,
``extend`` and the decode programs take and return whole, right behind ``params``: each
is donated and moved by the same row helpers. A module that names ``DECODE_COUNTERS``
has them summed on the device over a chunk's steps and layers and appended to the
chunk's token buffer, one row a counter, so they are read back with the tokens and by
nothing else. A slot's view of an array is its axis 2 whole, whatever its length (window
layers' K and V are rings: which rows mean what is models/stacks.py's; a recurrent state
has heads there and no rows: which steps may touch it is models/mla.py's). What is not
ported to a family keeps the pair's signature below and is refused for
the others at engine construction (family.py::refuse_unported).

KV representation: every program moves cache rows through the
cache-agnostic helpers in ``models/kv_quant.py``, so one program source
serves both KV precisions — with ``EngineConfig.kv_quant`` the caches
(and the pool / paged tiers downstream) are QuantKV pytrees (int8 rows
+ per-row-per-head f32 scales), quantized at the write sites here and
dequantized fused inside the attention ops. With ``kv_quant=None`` the
helpers reduce to the exact plain-array slicing they replaced, so the
traced programs carry the same operands as a pre-quant engine.

Replaces the reference's provider-relay hot path (it has no on-device
programs at all — internal/runtime/provider.go streams vendor SSE); the
program set is the TPU-native substitute for that relay loop.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from omnia_tpu.engine.types import EngineConfig
from omnia_tpu.models import ModelConfig, cache_arrays, decode_counters, model_module
from omnia_tpu.models.kv_quant import cache_put, cache_take, kv_map
from omnia_tpu.models import paged_kv as pkv
from omnia_tpu.ops.sampling import _NEG_INF, sample_tokens_per_slot


@dataclasses.dataclass(frozen=True)
class EnginePrograms:
    """The engine's compiled-program set (jitted callables)."""

    prefill_insert: Callable
    prefill_ring: Optional[Callable]
    insert: Callable
    decode_fns: dict[int, Callable]
    extend: Callable
    extend_nosample: Callable
    offload: Callable
    restore: Callable
    verify: Optional[Callable]  # speculative-decode verify (spec_decode > 0)
    # Shared-prefix pool transfers (prefix_cache_slots > 0, else None).
    prefix_store: Optional[Callable]
    prefix_seed: Optional[Callable]
    prefix_offload: Optional[Callable]
    # Fused mixed prefill+decode steps, one per prefill-piece bucket
    # (prefill_chunk_tokens > 0, else both dicts are empty).
    mixed: dict[int, Callable]
    mixed_sample: dict[int, Callable]
    # A slot's per-slot device state, written whole by one call each
    # (placement's tail, and a finish).
    activate_slot: Callable
    release_slot: Callable
    # Paged-pool programs (kv_pages > 0, else all None): copy-on-write
    # page duplication and the prefix host-tier page-run transfers.
    page_copy: Optional[Callable] = None
    gather_pages: Optional[Callable] = None
    scatter_pages: Optional[Callable] = None
    # Speculative-decode fusion (spec_decode > 0): verify window + one
    # exact decode step for the non-verify slots in ONE dispatch, and
    # the mixed-step twins that additionally stream a prefill piece
    # (both dicts empty unless prefill_chunk_tokens > 0 too).
    verify_decode: Optional[Callable] = None
    mixed_spec: dict[int, Callable] = dataclasses.field(default_factory=dict)
    mixed_spec_sample: dict[int, Callable] = dataclasses.field(
        default_factory=dict
    )


def build_programs(
    cfg: ModelConfig, ecfg: EngineConfig, mesh=None
) -> EnginePrograms:
    """Trace and jit every serving program for one (model, engine) config.

    Pure in the sense that matters: depends only on the configs and mesh,
    owns no state, and is safe to call before any device state exists.
    """

    model = model_module(cfg)
    # Arrays of the family's cache tuple: the operands right behind
    # ``params`` of every program that takes the cache whole.
    n_cache = cache_arrays(cfg, ecfg.kv_quant)
    cache_args = tuple(range(1, 1 + n_cache))
    counters = len(decode_counters(cfg))

    # Grammar-constrained decoding: when the engine is built with
    # ecfg.grammar, every first-token sampler (prefill_insert / insert /
    # extend) takes ONE extra ``*g`` operand — the start-state mask bias
    # [V] — and the decode scan threads per-slot FSM state through a
    # device-side gather (no host round-trip per step). When grammar is
    # off the engine never passes the operand, so the traced programs are
    # byte-identical to a pre-grammar engine (the guarded-no-op
    # contract).
    def _first_bias(g):
        return g[0][None] if g else None

    # Paged KV cache (kv_pages > 0): ck/cv operands are PagedKV pytrees
    # (pool + page table) instead of [L, B, S, H, D] arrays, and the
    # three access seams below reroute through the table. kv_pages=0
    # takes the exact pre-paging branches at trace time, so the lowered
    # programs carry the unchanged contiguous operands (the guarded
    # no-op contract).
    paged = ecfg.kv_pages > 0

    @jax.named_scope("insert")
    def _put(c, chunk, slot, start):
        """Write a slot-row chunk [L, 1, T, H, D] at rows [start, …)."""
        if paged:
            return pkv.put_chunk(c, chunk, slot, start)
        return cache_put(c, chunk, (0, slot, start))

    @jax.named_scope("insert")
    def _take_slot(c, slot):
        """One slot's contiguous [L, 1, S, H, D] view, either layout."""
        if paged:
            return pkv.gather_slot(c, slot)
        return cache_take(c, (0, slot, 0), (c.shape[0], 1, c.shape[2]))

    @jax.named_scope("insert")
    def _put_back(c, view, slot, write_start, t):
        """Write a slot view back after forward wrote rows
        [write_start, write_start + t): contiguous puts the whole view
        (one dynamic_update_slice, its storage); paged scatters ONLY
        the written rows through the page table — the rest of the view
        is a gather copy, not the storage."""
        if paged:
            new = cache_take(view, (0, 0, write_start), (view.shape[0], 1, t))
            return pkv.put_chunk(c, new, slot, write_start)
        return cache_put(c, view, (0, slot, 0))

    def _sample_first(last, key_data, temp, top_p, top_k, g):
        """A placed request's first token, sampled from the logits of its
        last prompt row, ``last`` [1, V] (the header's "first-token row")."""
        tok, new_kd = sample_tokens_per_slot(
            last, key_data[None], temp[None], top_p[None], top_k[None],
            mask_bias=_first_bias(g),
        )
        return tok[0], new_kd[0]

    def prefill_insert(params, *args):
        """(params, *cache, tokens, positions, slot, last_idx, key_data,
        temp, top_p, top_k, *g) -> (*cache, tok, new_key_data)."""
        cache, (tokens, positions, slot, last_idx, key_data, temp, top_p,
                top_k, *g) = args[:n_cache], args[n_cache:]
        last, *chunks = model.forward_prefill(params, cfg, tokens, positions,
                                              row=last_idx, mesh=mesh)

        # c: [L,B,S,...]; chunk: [L,1,T,...] — a quantized cache
        # quantizes the fresh rows inside cache_put (kv_quant mode).
        cache = tuple(_put(c, chunk, slot, 0) for c, chunk in zip(cache, chunks))
        tok, new_kd = _sample_first(last, key_data, temp, top_p, top_k, g)
        return (*cache, tok, new_kd)

    prefill_insert_fn = jax.jit(prefill_insert, donate_argnums=cache_args)

    prefill_ring_fn = None
    if ecfg.sp > 1:
        def prefill_ring(params, tokens, positions, last_idx):
            """-> (last_logits [1, V], *chunks): ``insert``'s operands."""
            return model.forward_prefill_ring(params, cfg, tokens, positions,
                                              mesh, row=last_idx)

        prefill_ring_fn = jax.jit(prefill_ring)

    def insert(*args):
        """(*cache, *chunks, slot, last_logits, key_data, temp, top_p,
        top_k, *g) -> (*cache, tok, new_key_data)."""
        cache, chunks = args[:n_cache], args[n_cache:2 * n_cache]
        slot, last_logits, key_data, temp, top_p, top_k, *g = args[2 * n_cache:]
        # Place the prefill chunk into the slot's rows [slot, 0:T]
        # (chunk [L,1,T,...] floats — quantized on write in kv mode).
        cache = tuple(_put(c, chunk, slot, 0) for c, chunk in zip(cache, chunks))
        return (*cache, *_sample_first(last_logits, key_data, temp, top_p,
                                       top_k, g))

    insert_fn = jax.jit(insert, donate_argnums=tuple(range(n_cache)))

    max_seq = ecfg.max_seq

    @jax.named_scope("sample")
    def _grammar_rows(gtable, state):
        """Each slot's current [V] transition row, gathered with one
        dynamic_slice per slot unrolled over the static batch dim: XLA
        CPU lowers gather (vmapped dynamic_index, take_along_axis) to
        an O(table) walk — cost grew with grammar_max_states — while a
        dynamic_slice per slot is an O(V) copy regardless of table
        size. The SINGLE gather idiom shared by the decode step body
        and the spec verify oracle, so the sampler's mask and the
        acceptance oracle's mask can never diverge."""
        nvocab = gtable.shape[-1]
        return jnp.stack([
            jax.lax.dynamic_slice(
                gtable, (b, state[b], 0), (1, 1, nvocab)
            )[0, 0]
            for b in range(gtable.shape[0])
        ])  # [B, V]

    def _mk_step_body(params, stop_ids, temp, top_p, top_k,
                      gtable=None, gactive=None, grammar_on=False):
        """One decode step as a ``lax.scan`` body — the SINGLE source of
        the decode-step math, shared by the chunked decode programs and
        the fused mixed prefill+decode programs (interleaved and
        monolithic serving must stay bit-identical, so there is exactly
        one place the step semantics live)."""

        def body(carry, _):
            cache, rest = carry[:n_cache], carry[n_cache:]
            if grammar_on:
                tokens, positions, active, budget, key_data, gstate = rest
            else:
                tokens, positions, active, budget, key_data = rest
            # The decode kernel reads no cache for a slot that is not
            # active: its sample is discarded below.
            logits, *cache = model.forward(
                params, cfg, tokens[:, None], positions[:, None], *cache,
                positions, mesh=mesh, live=active,
                **({"counters": True} if counters else {}),
            )
            if counters:
                counts = cache.pop()
            if grammar_on:
                row = _grammar_rows(gtable, gstate)
                bias = jnp.where(
                    gactive[:, None] & (row < 0), _NEG_INF, 0.0
                )
                tok, key_data = sample_tokens_per_slot(
                    logits[:, 0], key_data, temp, top_p, top_k,
                    mask_bias=bias,
                )
                # State advances on the sampled token, gated like
                # the position advance (active at step START); a
                # masked token cannot be sampled, so row[tok] >= 0
                # for any gactive slot — the max(·, 0) only covers
                # inactive slots' garbage samples.
                nxt = jnp.take_along_axis(row, tok[:, None], axis=1)[:, 0]
                gstate = jnp.where(
                    gactive & active, jnp.maximum(nxt, 0), gstate
                )
            else:
                tok, key_data = sample_tokens_per_slot(
                    logits[:, 0], key_data, temp, top_p, top_k
                )
            with jax.named_scope("finish_mask"):
                # Position advances for the row just written (gated on
                # active at step START); deactivation applies from the
                # NEXT step on, mirroring the host's finish bookkeeping.
                positions = jnp.where(
                    active, jnp.minimum(positions + 1, max_seq - 1), positions
                )
                budget = budget - active.astype(jnp.int32)
                hit_stop = (tok[:, None] == stop_ids).any(axis=1)
                active = active & ~hit_stop & (budget > 0)
                tokens = jnp.where(active | hit_stop, tok, tokens)
            out = (*cache, tokens, positions, active, budget, key_data)
            if grammar_on:
                out += (gstate,)
            return out, ((tok, counts) if counters else tok)

        return body

    def _verify_window(params, ck, cv, vtoks, vpos, vwstart,
                       gstate=None, gtable=None, gactive=None):
        """Speculative verify half: ONE forward over [B, W+1] tokens
        (last emitted + proposals per slot) with per-slot write offsets;
        the greedy argmax over every position is the acceptance oracle.
        The cache rows for rejected proposals are garbage at rows ≥ the
        slot's new frontier — the invariant the decode finish-mask
        already relies on.

        Grammar edition (gstate is not None): the oracle is the MASKED
        argmax — each slot's current [S, V] transition row applies as
        the same additive -inf bias the sampler uses (ops/sampling
        seam), and the per-slot FSM state advances across window
        positions along the PROPOSED stream (position t+1's input), so
        the oracle's choice at every position within the accepted
        prefix is admissible by construction. A masked proposal yields
        garbage states downstream of it, but it also mismatches the
        (admissible) masked argmax at its own position, so host
        acceptance never trusts anything past it. The row gather is the
        decode body's shared ``_grammar_rows`` helper — one idiom, one
        mask source for sampler and oracle alike."""
        logits, ck, cv = model.forward(
            params, cfg, vtoks, vpos, ck, cv, vwstart, mesh=mesh
        )
        if gstate is None:
            return ck, cv, jnp.argmax(logits, axis=-1).astype(jnp.int32)
        T = vtoks.shape[1]
        state = gstate
        cols = []
        for t in range(T):
            row = _grammar_rows(gtable, state)
            bias = jnp.where(gactive[:, None] & (row < 0), _NEG_INF, 0.0)
            cols.append(
                jnp.argmax(logits[:, t] + bias, axis=-1).astype(jnp.int32)
            )
            if t + 1 < T:
                nxt = jnp.take_along_axis(
                    row, vtoks[:, t + 1][:, None], axis=1
                )[:, 0]
                state = jnp.where(gactive, jnp.maximum(nxt, 0), state)
        return ck, cv, jnp.stack(cols, axis=1)

    def _vmasked_decode_step(params, ck, cv, tokens, positions, active,
                             budget, stop_ids, key_data, temp, top_p,
                             top_k, vmask, vshift, gstate, gtable,
                             gactive, grammar_on):
        """One _mk_step_body scan step with the verify-lane slots masked
        OUT: they run inactive for the scan (frozen sampling state — the
        host re-syncs their tokens/positions after acceptance) and their
        unavoidable garbage row write is parked ``vshift`` rows past
        their frontier, one row beyond the verify window they just
        received — ≥ any frontier acceptance can reach, so it never
        lands on real data. Scan-lane slots take the EXACT chunked step:
        same body, same per-slot PRNG consumption."""
        body = _mk_step_body(
            params, stop_ids, temp, top_p, top_k, gtable, gactive,
            grammar_on,
        )
        init = (ck, cv, tokens,
                jnp.where(vmask, positions + vshift, positions),
                active & ~vmask, budget, key_data)
        if grammar_on:
            init += (gstate,)
        carry, toks = jax.lax.scan(body, init, None, length=1)
        ck, cv, o_tok, o_pos, o_act, o_bud, o_kd = carry[:7]
        out = (ck, cv,
               jnp.where(vmask, tokens, o_tok),
               jnp.where(vmask, positions, o_pos),
               jnp.where(vmask, active, o_act),
               jnp.where(vmask, budget, o_bud),
               jnp.where(vmask[:, None], key_data, o_kd))
        if grammar_on:
            # The body already froze vmask slots' FSM state (they ran
            # inactive), so the carry value passes through unmerged.
            out += (carry[7],)
        return out, toks

    def make_decode(chunk: int):
        def decode_impl(params, cache, tokens, positions, active, budget,
                        stop_ids, key_data, temp, top_p, top_k,
                        gstate=None, gtable=None, gactive=None):
            """`chunk` decode steps in ONE compiled program (lax.scan):
            one host↔device round trip per K tokens instead of per
            token. Stop-token/length finishes are masked ON DEVICE:
            the step that samples a stop id (or exhausts the slot's
            budget) deactivates the slot inside the scan, freezing its
            position — a mid-chunk finish costs zero further row
            writes or position advances, so large chunks don't trade
            correctness-adjacent garbage for RTT amortization.
            Inactive slots' frozen row is re-written each step (row 0
            for unpinned slots — the next prefill's insert overwrites
            it — or the session's valid-row frontier for pinned ones:
            garbage only ever lives at rows ≥ the session's length).

            With grammar operands (one trace-time Python branch — the
            plain program stays byte-identical), per-slot FSM state
            rides the scan carry: each step gathers the current state's
            transition row from the per-slot table, applies it as an
            additive -inf mask inside the sampler, and advances the
            state on the sampled token. Slots with ``gactive=False``
            see a zero bias and a frozen state — an ungrammared request
            in the same batch samples exactly as the plain program
            would."""
            grammar_on = gstate is not None
            body = _mk_step_body(
                params, stop_ids, temp, top_p, top_k, gtable, gactive,
                grammar_on,
            )
            init = (*cache, tokens, positions, active, budget, key_data)
            if grammar_on:
                init += (gstate,)
            carry, toks = jax.lax.scan(body, init, None, length=chunk)
            # toks [K, B]
            if counters:
                # The chunk's counters ride its token buffer, one row a
                # counter (the sum over its steps, in every column): one
                # read-back, [K + counters, B].
                toks, counts = toks
                toks = jnp.concatenate([toks, jnp.broadcast_to(
                    counts.sum(axis=0)[:, None], (counters, toks.shape[1]))])
            return carry + (toks,)

        def decode_chunk(params, *args):
            """(params, *cache, tokens, positions, active, budget,
            stop_ids, key_data, temp, top_p, top_k[, gstate, gtable,
            gactive]) -> (*cache, tokens, positions, active, budget,
            key_data[, gstate], toks)."""
            return decode_impl(params, args[:n_cache], *args[n_cache:])

        if ecfg.grammar:
            decode_chunk.__name__ = "decode_chunk_grammar"
        return jax.jit(decode_chunk, donate_argnums=cache_args)

    # Compiled chunk-size variants: the big chunk for steady-state
    # throughput, smaller ones so the tail of a generation (or a step
    # taken while requests queue — TTFT discipline) doesn't pay for a
    # full chunk. The scheduler's _pick_chunk chooses per dispatch.
    decode_fns = {k: make_decode(k) for k in ecfg.chunk_variants()}

    def _extend_slot(params, cache, tokens, positions, slot, write_start,
                     row=None):
        """The extend seam: one slot's view of every cache array, forward
        over it with the slot's write offset, the view written back.
        -> (logits: of ``row`` [1, V], of every row when it is None; cache)."""
        views = [_take_slot(c, slot) for c in cache]
        logits, *views = model.forward(
            params, cfg, tokens, positions, *views, write_start[None],
            mesh=mesh, row=row,
        )
        # forward kept the slice in cache representation (suffix rows
        # quantized inside _write_kv when kv_quant is on) — write back
        # verbatim, no requantization of resident rows.
        t = tokens.shape[1]
        return logits, tuple(_put_back(c, view, slot, write_start, t)
                             for c, view in zip(cache, views))

    def extend(params, *args):
        """(params, *cache, tokens, positions, slot, write_start, last_idx,
        key_data, temp, top_p, top_k, *g) -> (*cache, tok, new_key_data)."""
        cache, (tokens, positions, slot, write_start, last_idx, key_data,
                temp, top_p, top_k, *g) = args[:n_cache], args[n_cache:]
        last, cache = _extend_slot(params, cache, tokens, positions, slot,
                                   write_start, row=last_idx)
        tok, new_kd = _sample_first(last, key_data, temp, top_p, top_k, g)
        return (*cache, tok, new_kd)

    extend_fn = jax.jit(extend, donate_argnums=cache_args)

    # Mid-extend chunk: writes rows, no sampling (sampling happens only
    # on the final chunk of a multi-chunk extend).
    def extend_nosample(params, *args):
        cache, (tokens, positions, slot, write_start) = args[:n_cache], args[n_cache:]
        return _extend_slot(params, cache, tokens, positions, slot, write_start)[1]

    extend_nosample_fn = jax.jit(extend_nosample, donate_argnums=cache_args)

    # Stall-free batching: fused mixed prefill+decode steps. One program
    # per prefill-piece bucket (and a *_sample twin for the final piece)
    # so the ENTIRE per-step work — a bounded prompt piece for the
    # in-placement slot AND one decode token for every active slot —
    # costs a single dispatch round trip. The piece runs the extend seam
    # FIRST (cache_take slot slice → forward with per-batch write offsets
    # → cache_put), then the decode step runs over the updated cache: the
    # in-placement slot is inactive during the decode part, so its frozen
    # position (parked by the scheduler at the piece's END) receives one
    # garbage row write at the NEW frontier — exactly the row the next
    # piece, or the first real decode write after activation, overwrites.
    # Both halves reuse their monolithic counterparts' exact op graphs
    # (forward + _mk_step_body), which is what makes interleaved prefill
    # bit-identical to monolithic prefill.
    mixed_fns: dict[int, Callable] = {}
    mixed_sample_fns: dict[int, Callable] = {}
    mixed_spec_fns: dict[int, Callable] = {}
    mixed_spec_sample_fns: dict[int, Callable] = {}
    if ecfg.prefill_chunk_tokens > 0:
        def make_mixed(bucket: int, sample: bool, spec: bool = False):
            grammar_on = bool(ecfg.grammar)

            def mixed_step(params, ck, cv, tokens, positions, active,
                           budget, stop_ids, key_data, temp, top_p, top_k,
                           ptoks, ppos, pslot, pwrite, *rest):
                rest = list(rest)
                if grammar_on:
                    gstate, gtable, gactive = rest[-3:]
                    del rest[-3:]
                else:
                    gstate = gtable = gactive = None
                if spec:
                    # Speculative edition: the verify window rides the
                    # SAME dispatch as the piece and the decode step —
                    # its operands sit between the piece's and the
                    # final-piece sampling family's.
                    vtoks, vpos, vwstart, vmask = rest[:4]
                    del rest[:4]
                # -- prefill piece via the extend seam ------------------
                piece = (params, (ck, cv), ptoks, ppos, pslot, pwrite)
                if sample:
                    # Final piece: sample the placed request's first
                    # token at the one row the head runs over (grammar
                    # start-state bias rides *pg, the extend signature
                    # exactly).
                    plast, pkd, ptemp, ptop_p, ptop_k, *pg = rest
                    last, (ck, cv) = _extend_slot(*piece, row=plast)
                    extra = _sample_first(last, pkd, ptemp, ptop_p, ptop_k, pg)
                else:
                    # Any other piece's logits are dropped.
                    _, (ck, cv) = _extend_slot(*piece)
                    extra = ()
                if spec:
                    # Verify window AFTER the piece (its garbage rows
                    # for the placing slot park at the piece frontier,
                    # where the next piece overwrites them), then the
                    # decode step with the verify slots masked out.
                    ck, cv, greedy = _verify_window(
                        params, ck, cv, vtoks, vpos, vwstart,
                        gstate, gtable, gactive,
                    )
                    carry, toks = _vmasked_decode_step(
                        params, ck, cv, tokens, positions, active, budget,
                        stop_ids, key_data, temp, top_p, top_k,
                        vmask, vtoks.shape[1], gstate, gtable, gactive,
                        grammar_on,
                    )
                    return carry + (toks,) + extra + (greedy,)
                # -- one decode step over the fixed batch ---------------
                body = _mk_step_body(
                    params, stop_ids, temp, top_p, top_k, gtable, gactive,
                    grammar_on,
                )
                init = (ck, cv, tokens, positions, active, budget, key_data)
                if grammar_on:
                    init += (gstate,)
                carry, toks = jax.lax.scan(body, init, None, length=1)
                # toks [1, B] (+ first_tok, new_key_data on final pieces)
                return carry + (toks,) + extra

            mixed_step.__name__ = (
                f"mixed_{'spec_' if spec else ''}"
                f"{'sample_' if sample else ''}{bucket}"
            )
            return jax.jit(mixed_step, donate_argnums=(1, 2))

        for b in ecfg.mixed_prefill_buckets():
            mixed_fns[b] = make_mixed(b, sample=False)
            mixed_sample_fns[b] = make_mixed(b, sample=True)
            if ecfg.spec_decode > 0:
                mixed_spec_fns[b] = make_mixed(b, sample=False, spec=True)
                mixed_spec_sample_fns[b] = make_mixed(
                    b, sample=True, spec=True
                )

    def offload(ck, cv, slot, rows: int):
        # Paged rows keep the cache representation (int8 + scales under
        # kv_quant — host pages shrink with the device bytes). Under
        # kv_pages only the pages covering the bucket are gathered, and
        # the HOST format is identical to the contiguous engine's, so
        # session pages survive a layout change.
        if paged:
            return pkv.gather_rows(ck, slot, rows), pkv.gather_rows(cv, slot, rows)
        L, B, S, H, D = ck.shape
        k = cache_take(ck, (0, slot, 0), (L, 1, rows))
        v = cache_take(cv, (0, slot, 0), (L, 1, rows))
        return kv_map(lambda a: a[:, 0], k), kv_map(lambda a: a[:, 0], v)

    offload_fn = jax.jit(offload, static_argnums=(3,))

    def restore(ck, cv, k_rows, v_rows, slot):
        ck = _put(ck, kv_map(lambda a: a[:, None], k_rows), slot, 0)
        cv = _put(cv, kv_map(lambda a: a[:, None], v_rows), slot, 0)
        return ck, cv

    restore_fn = jax.jit(restore, donate_argnums=(0, 1))

    # Shared-prefix pool transfers. store: slot rows → pool entry (pool
    # donated); seed: pool entry → slot rows (cache donated) — the
    # device-to-device copy that replaces a fresh session's shared-prefix
    # prefill; prefix_offload: pool entry → host (paged tier; promotion
    # back rides the slot restore program). All take a static row bucket.
    # Under kv_pages the prefix cache needs NO transfer programs at all:
    # publish and seed are pure page-table rewrites (engine/paged.py),
    # and the host tier rides the page-run gather/scatter below.
    prefix_store_fn = prefix_seed_fn = prefix_offload_fn = None
    if ecfg.prefix_cache_slots > 0 and not paged:
        def prefix_store(pool_k, pool_v, ck, cv, slot, pool_idx, rows: int):
            L, B, S, H, D = ck.shape
            # Pool entries inherit the cache representation: under
            # kv_quant the int8 rows + scales copy verbatim (2× entries
            # per pool byte, zero requantization drift on seed).
            k = cache_take(ck, (0, slot, 0), (L, 1, rows))
            v = cache_take(cv, (0, slot, 0), (L, 1, rows))
            pool_k = cache_put(pool_k, k, (0, pool_idx, 0))
            pool_v = cache_put(pool_v, v, (0, pool_idx, 0))
            return pool_k, pool_v

        prefix_store_fn = jax.jit(
            prefix_store, donate_argnums=(0, 1), static_argnums=(6,)
        )

        def prefix_seed(ck, cv, pool_k, pool_v, pool_idx, slot, rows: int):
            L, P, R, H, D = pool_k.shape
            k = cache_take(pool_k, (0, pool_idx, 0), (L, 1, rows))
            v = cache_take(pool_v, (0, pool_idx, 0), (L, 1, rows))
            ck = cache_put(ck, k, (0, slot, 0))
            cv = cache_put(cv, v, (0, slot, 0))
            return ck, cv

        prefix_seed_fn = jax.jit(
            prefix_seed, donate_argnums=(0, 1), static_argnums=(6,)
        )

        def prefix_offload(pool_k, pool_v, pool_idx, rows: int):
            L, P, R, H, D = pool_k.shape
            k = cache_take(pool_k, (0, pool_idx, 0), (L, 1, rows))
            v = cache_take(pool_v, (0, pool_idx, 0), (L, 1, rows))
            return kv_map(lambda a: a[:, 0], k), kv_map(lambda a: a[:, 0], v)

        prefix_offload_fn = jax.jit(prefix_offload, static_argnums=(3,))

    # Paged-pool programs: the copy-on-write page duplicator and the
    # prefix host-tier page-run transfers (TRASH-padded fixed-length
    # runs keep them compile-stable; pad gathers are garbage the host
    # slices off, pad scatters land in the trash page).
    page_copy_fn = gather_pages_fn = scatter_pages_fn = None
    if paged:
        def page_copy(ck, cv, src, dst):
            return (
                pkv.PagedKV(pkv.copy_page(ck.pool, src, dst), ck.table),
                pkv.PagedKV(pkv.copy_page(cv.pool, src, dst), cv.table),
            )

        page_copy_fn = jax.jit(page_copy, donate_argnums=(0, 1))

        def gather_pages(ck, cv, idx):
            return pkv.gather_pages(ck.pool, idx), pkv.gather_pages(cv.pool, idx)

        gather_pages_fn = jax.jit(gather_pages)

        def scatter_pages(ck, cv, idx, k_pages, v_pages):
            return (
                pkv.PagedKV(pkv.scatter_pages(ck.pool, idx, k_pages), ck.table),
                pkv.PagedKV(pkv.scatter_pages(cv.pool, idx, v_pages), cv.table),
            )

        scatter_pages_fn = jax.jit(scatter_pages, donate_argnums=(0, 1))

    # Speculative-decode programs (engine/spec_decode.py). `verify` is
    # the pure window for all-verify-lane batches; `verify_decode`
    # additionally runs ONE exact _mk_step_body step for the scan-lane
    # slots (sampled traffic) with the verify slots masked out — per-
    # slot participation in a single dispatch. Grammar engines pass the
    # (gstate, gtable, gactive) triple so the acceptance oracle is the
    # MASKED argmax (one trace-time branch; grammar-off programs carry
    # zero extra operands — the guarded no-op contract).
    verify_fn = verify_decode_fn = None
    if ecfg.spec_decode > 0:
        def verify(params, ck, cv, tokens, positions, write_start, *g):
            gs, gt, ga = g if g else (None, None, None)
            return _verify_window(
                params, ck, cv, tokens, positions, write_start, gs, gt, ga
            )

        verify_fn = jax.jit(verify, donate_argnums=(1, 2))

        def verify_decode(params, ck, cv, tokens, positions, active,
                          budget, stop_ids, key_data, temp, top_p, top_k,
                          vtoks, vpos, vwstart, vmask, *g):
            gs, gt, ga = g if g else (None, None, None)
            ck, cv, greedy = _verify_window(
                params, ck, cv, vtoks, vpos, vwstart, gs, gt, ga
            )
            carry, toks = _vmasked_decode_step(
                params, ck, cv, tokens, positions, active, budget,
                stop_ids, key_data, temp, top_p, top_k,
                vmask, vtoks.shape[1], gs, gt, ga, bool(g),
            )
            return carry + (toks, greedy)

        verify_decode_fn = jax.jit(verify_decode, donate_argnums=(1, 2))

    def _slot_put(ints):
        """``(vector, value) -> vector`` written at the slot ``ints[0]``."""
        return lambda v, x: v.at[ints[0]].set(x)

    def activate_slot(tokens, positions, active, temp, top_p, top_k, budget,
                      stop_ids, key_data, first_tok, new_kd, ints, floats,
                      *g):
        """(the nine per-slot vectors, first_tok, new_key_data, ints,
        floats[, gactive]) -> the vectors[, gactive]: all a placement
        writes for its slot. ``first_tok`` and ``new_key_data`` are the
        prefill's outputs, still on the device; ``ints`` is the host's
        ``[slot, n_prompt, top_k, budget, *stop_row]``, ``floats`` its
        ``[temperature, top_p]``. A grammar engine's gate is closed here;
        a grammar request's attach opens it again."""
        put = _slot_put(ints)
        return (
            put(tokens, first_tok), put(positions, ints[1]),
            put(active, True), put(temp, floats[0]), put(top_p, floats[1]),
            put(top_k, ints[2]), put(budget, ints[3]),
            put(stop_ids, ints[4:]), put(key_data, new_kd),
            *(put(ga, False) for ga in g),
        )

    def release_slot(positions, tokens, temp, active, ints, *g):
        """(positions, tokens, temp, active, ints[, gactive]) -> the same:
        a finished slot quiesced. ``ints`` is the host's ``[slot,
        quiesce_row]``: decode keeps running over the slot (static shape)
        and rewrites that one row."""
        put = _slot_put(ints)
        return (
            put(positions, ints[1]), put(tokens, 0), put(temp, 0.0),
            put(active, False), *(put(ga, False) for ga in g),
        )

    # Every vector is donated; the gate only where grammar support is on.
    gate = bool(ecfg.grammar)
    activate_slot_fn = jax.jit(
        activate_slot, donate_argnums=(*range(9), *((13,) * gate))
    )
    release_slot_fn = jax.jit(
        release_slot, donate_argnums=(*range(4), *((5,) * gate))
    )

    return EnginePrograms(
        activate_slot=activate_slot_fn,
        release_slot=release_slot_fn,
        prefill_insert=prefill_insert_fn,
        prefill_ring=prefill_ring_fn,
        insert=insert_fn,
        decode_fns=decode_fns,
        extend=extend_fn,
        extend_nosample=extend_nosample_fn,
        offload=offload_fn,
        restore=restore_fn,
        verify=verify_fn,
        prefix_store=prefix_store_fn,
        prefix_seed=prefix_seed_fn,
        prefix_offload=prefix_offload_fn,
        mixed=mixed_fns,
        mixed_sample=mixed_sample_fns,
        page_copy=page_copy_fn,
        gather_pages=gather_pages_fn,
        scatter_pages=scatter_pages_fn,
        verify_decode=verify_decode_fn,
        mixed_spec=mixed_spec_fns,
        mixed_spec_sample=mixed_spec_sample_fns,
    )
