"""Engine request/response types.

The serving counterpart of the reference's provider-call surface: where the
reference submits an HTTPS SSE request per turn and relays chunks (reference
internal/runtime/message.go:148-238 via PromptKit), omnia_tpu submits a
token-level Request to the in-process engine and streams StreamEvents off
the device.
"""

from __future__ import annotations

import dataclasses
import enum
import queue
import threading
import time
from typing import Any, Iterator, Optional


# Per-slot stop-token ids tracked ON DEVICE (padded with -1). Requests with
# more stop ids than this still finish correctly — the host checks the full
# set — the device mask just can't early-freeze on the overflow ids.
MAX_DEVICE_STOP_IDS = 8

# Prompt tokens per queue-slot request-equivalent of prefill backlog —
# the ONE normalization shared by the coordinator's routing load signal,
# the fleet scaler's autoscaling depth signal, and the operator's pod
# scrape, so "one request of prefill work" means the same thing at every
# decision point (retuning it in one place retunes them all).
PENDING_TOKENS_NORM = 512.0


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.7
    top_p: float = 1.0
    top_k: int = 0
    max_tokens: int = 256
    stop_token_ids: tuple[int, ...] = ()
    seed: Optional[int] = None


class FinishReason(enum.Enum):
    STOP = "stop"          # hit a stop/EOS token
    LENGTH = "length"      # hit max_tokens or context limit
    CANCELLED = "cancelled"
    ERROR = "error"
    # Request-lifecycle robustness terminals: a request past its TTL is
    # shed from the queue (or finished early mid-decode), and a request
    # hitting a full queue / saturated fleet / draining engine is shed
    # at admission. Both are FAST, OBSERVABLE degradation — the caller
    # gets a terminal event immediately instead of unbounded latency.
    DEADLINE = "deadline"
    OVERLOADED = "overloaded"


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_tokens: list[int]
    params: SamplingParams
    # Sessionful serving: requests carrying the same session_id reuse the
    # session's resident KV rows across turns (prefix-matched), so turn
    # N+1 prefills only its new tokens.
    session_id: Optional[str] = None
    # Grammar-constrained decoding (engine/grammar.TokenGrammar): when
    # set, the sampler masks every step to the grammar's admissible
    # tokens and EOS is unmasked only in accepting states. Requires
    # EngineConfig.grammar=True on the real engine (the mock honors it
    # host-side unconditionally).
    grammar: Optional[object] = None
    submitted_at: float = dataclasses.field(default_factory=time.monotonic)
    # Absolute deadline in the ENGINE's clock domain (engine.clock() at
    # submit + deadline_s) — self.clock, not time.monotonic, so
    # replicated engines (multihost lockstep) reap deadlines from the
    # leader-broadcast logical clock and every rank decides identically.
    # None = no deadline (the guarded default).
    deadline_at: Optional[float] = None
    # W3C traceparent of the caller's span (the runtime's llm span):
    # with flight recording on, the engine opens a child
    # `omnia.engine.request` span under it, so one trace id covers
    # facade → runtime → engine — and the coordinator re-sends the SAME
    # context on failover/resubmit, so a worker death extends the trace
    # instead of starting a new one. None = no trace continuity.
    trace_ctx: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class StreamEvent:
    """One engine output event: a generated token, or end-of-stream."""

    request_id: str
    token_id: Optional[int] = None
    finish_reason: Optional[FinishReason] = None
    # Filled on the final event.
    num_prompt_tokens: int = 0
    num_generated_tokens: int = 0
    error: Optional[str] = None

    @property
    def is_final(self) -> bool:
        return self.finish_reason is not None


@dataclasses.dataclass
class SessionExport:
    """One session's portable residency record — the live-migration
    payload ``EngineCoordinator.remove_worker(migrate=True)`` carries
    from a retiring worker to its survivor.

    ``host_k``/``host_v`` ride the EXISTING host-row offload format
    (``_offload_session``'s ``[L, R, H, D]`` restore-bucket rows; a
    ``QuantKV`` of numpy leaves under ``kv_quant``; under ``kv_pages``
    the retiring pool's pages gather to the SAME host layout) — so an
    import is exactly a deferred ``_restore_session``, and the int8 and
    paged pools migrate with zero extra formats. ``kv_quant`` and
    ``restore_rows`` are the import-side compatibility stamp: a
    survivor with a different KV representation or bucket set rejects
    the payload loudly and the coordinator books a fresh-prefill
    fallback instead of restoring garbage rows.

    Lives HERE (not ``engine/sessions.py``, which re-exports it) so the
    jax-free mock fleet can build payloads without pulling the engine's
    device stack."""

    session_id: str
    token_ids: list
    host_k: object
    host_v: object
    kv_quant: Optional[str] = None
    restore_rows: int = 0


class RequestHandle:
    """Consumer side of a submitted request: iterate StreamEvents."""

    def __init__(self, request_id: str) -> None:
        self.request_id = request_id
        self._queue: "queue.Queue[StreamEvent]" = queue.Queue()
        self._cancelled = threading.Event()
        self.first_token_at: Optional[float] = None

    # engine side -----------------------------------------------------------
    def _push(self, event: StreamEvent) -> None:
        if event.token_id is not None and self.first_token_at is None:
            self.first_token_at = time.monotonic()
        self._queue.put(event)

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    # consumer side ---------------------------------------------------------
    def cancel(self) -> None:
        self._cancelled.set()

    def events(self, timeout: Optional[float] = None) -> Iterator[StreamEvent]:
        """Blocking iterator over events until the final one."""
        while True:
            event = self._queue.get(timeout=timeout)
            yield event
            if event.is_final:
                return

    def get_event(self, timeout: Optional[float] = None) -> StreamEvent:
        return self._queue.get(timeout=timeout)

    def collect_tokens(self, timeout: Optional[float] = None) -> tuple[list[int], StreamEvent]:
        """Drain the stream; returns (token_ids, final_event)."""
        toks: list[int] = []
        for ev in self.events(timeout=timeout):
            if ev.token_id is not None:
                toks.append(ev.token_id)
            if ev.is_final:
                return toks, ev
        raise AssertionError("stream ended without final event")


def resolve_dtype(name: str) -> Any:
    """EngineConfig.dtype string → jnp dtype. The single mapping shared by
    the engine, the provider layer, and bench — adding a dtype means
    touching exactly this table."""
    import jax.numpy as jnp

    table: dict[str, Any] = {
        "bfloat16": jnp.bfloat16,
        "float32": jnp.float32,
        "float16": jnp.float16,
    }
    if name not in table:
        raise ValueError(f"unknown engine dtype {name!r}; have {sorted(table)}")
    return table[name]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-engine shape/placement configuration.

    Static shapes are the XLA contract: num_slots fixes the decode batch,
    prefill_buckets fixes the set of compiled prefill lengths, max_seq fixes
    the KV cache. warmup() compiles all of them ahead of readiness (the
    TTFT discipline SURVEY.md §7 calls out).
    """

    num_slots: int = 8
    max_seq: int = 1024
    prefill_buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024)
    dtype: str = "bfloat16"
    # Mesh shape; dp divides num_slots, tp divides num_kv_heads.
    dp: int = 1
    tp: int = 1
    # Sequence/context parallelism for LONG-PROMPT prefill: buckets ≥
    # long_prefill_threshold prefill via causal ring attention with the
    # prompt sequence-sharded over the "sp" mesh axis, splitting the
    # O(T²) attention FLOPs across the ring (SURVEY §5.7 / parallel/
    # ring_attention.py). Decode and the cache layout are unchanged —
    # the KV chunk gathers into the resident slot rows on insert.
    sp: int = 1
    long_prefill_threshold: int = 2048
    # Decode steps per device dispatch (lax.scan inside one compiled
    # program). Each dispatch costs a host↔device round trip, so K
    # tokens per sync amortizes it.
    # Trade-offs: streaming granularity becomes K tokens, a queued prefill
    # waits up to one chunk, and a slot finishing mid-chunk wastes ≤K-1
    # slot-steps (bounded by on-device stop/length masking: a finished
    # slot stops advancing/writing inside the chunk). 1 = per-token sync.
    decode_chunk: int = 8
    # Additional compiled chunk sizes for adaptive dispatch. While more
    # work remains than the full chunk, the engine dispatches decode_chunk;
    # for the tail it picks the SMALLEST variant covering the remaining
    # work (overshoot preferred: overshot steps are cheap on-device-masked
    # garbage, an extra dispatch is a full host round trip — see
    # _pick_chunk). () = {decode_chunk, 1}. Every variant costs one warmup
    # compile.
    decode_chunk_variants: tuple[int, ...] = ()
    # Decode chunks kept in flight (dispatched on the previous chunk's
    # output futures before its tokens are read). 2 hides the host's
    # read-RTT + bookkeeping gap behind device compute — the device runs
    # chunks back-to-back; 1 = synchronous dispatch-then-read. Streaming
    # latency worst case becomes pipeline × chunk tokens.
    decode_pipeline: int = 2
    # Cross-turn KV reuse: sessions beyond num_slots page their KV rows to
    # host RAM (LRU) and swap back on demand, so this many *logical*
    # sessions share the fixed device cache. 0 disables sessionful serving.
    max_sessions: int = 64
    # Prompt-lookup speculative decoding (engine/spec_decode.py): each
    # verify step feeds the last token plus host-proposed tokens
    # (n-gram lookup over prompt+history) through ONE forward of
    # T=W+1 and accepts the matching prefix — up to W+1 tokens per
    # weight stream instead of 1, a direct multiplier on the HBM-bound
    # decode roofline. Participation is PER SLOT: greedy slots verify
    # (grammar-constrained ones included — the acceptance oracle is
    # masked on device), while sampled slots ride the exact chunked
    # sampling path fused into the same dispatch. 0 = off (the guarded
    # no-op: no verify programs, no spec state). Must satisfy
    # spec_window() + 1 <= min(prefill_buckets) (rejected-proposal rows
    # land below the next occupant's smallest prefill write).
    spec_decode: int = 0
    # Per-slot adaptive speculation depth cap: > 0 lets each slot's
    # proposal depth follow its accept-rate EMA between 0 (lookup keeps
    # missing — the slot rides verify steps as a plain passenger, with
    # a periodic 1-token re-probe) and this cap, starting from
    # spec_decode. Must be 0 (fixed depth = spec_decode) or >=
    # spec_decode. Dead while spec_decode = 0.
    spec_decode_max: int = 0
    # Online self-gate (spec_decode.py _SpecGate): > 0 duty-cycles
    # speculation in probe windows of this many scheduler steps,
    # compares realized tokens/second with speculation permitted vs
    # suppressed, and disables it (state reported in the
    # `spec_gate_state` metric and bench aux.greedy_spec.gate) when it
    # is not paying; holds each decision for 8 windows, then re-probes.
    # 0 = no gate (speculation always permitted). Ignored under an
    # injected logical clock (multihost lockstep) — a wall-clock
    # decision could diverge the replicated step streams. Dead while
    # spec_decode = 0.
    spec_gate_window: int = 0
    # Weight quantization: None (full dtype), "int8" (W8A16 weight-only,
    # near-lossless, halves weight HBM), or "int8-dynamic" (W8A8 dynamic
    # activation quant, int8×int8 MXU path — fastest). Dense models only;
    # see models/quant.py.
    quant: Optional[str] = None
    # KV-cache quantization: None (cache stored at `dtype`) or "int8"
    # (rows stored int8 with a f32 scale per (layer, slot, row, kv_head)
    # — models/kv_quant.py). Halves KV HBM read traffic per decode step
    # and doubles the effective capacity of the slot cache, the shared-
    # prefix pool, and both host-paged tiers, at ~0.5-1% per-row
    # round-trip error (near-lossless greedy decoding; see
    # docs/serving.md "KV cache precision"). None is a guarded true
    # no-op: no scale tensors exist and the compiled programs take the
    # exact pre-quant operands.
    kv_quant: Optional[str] = None
    # Paged KV cache (engine/kv_pages.py + models/paged_kv.py): > 0
    # replaces the slot-contiguous cache AND the dedicated prefix-pool
    # arrays with ONE device page pool of this many fixed-size pages
    # ([L, kv_pages, kv_page_tokens, Hkv, D]; page 0 is a reserved
    # trash page for quiesced-slot garbage writes) served by a single
    # free list: active slots map rows through per-slot page tables
    # [num_slots, max_seq / kv_page_tokens], the prefix cache shares
    # refcounted page runs copy-on-write (publish and seed become pure
    # table rewrites — zero device copies), and session offload pages
    # out only the rows a session actually holds. Decode gathers pages
    # inside the Pallas kernel (ops/decode_attention.py); prefill/
    # extend/verify and off-TPU decode take an XLA `take` fallback that
    # is bit-identical to the contiguous layout. 0 (default) is a
    # guarded true no-op: no pool, no tables, no allocator — the
    # compiled programs carry the exact contiguous operands
    # (tests/test_guards.py::test_kv_pages_zero_is_true_noop).
    kv_pages: int = 0
    # Tokens per KV page. Must divide max_seq; it is also the paged
    # decode kernel's block size, so on real TPUs keep it a multiple of
    # the sublane tile (≥ 16 recommended). Dead while kv_pages == 0.
    kv_page_tokens: int = 64
    # Cross-SESSION shared-prefix KV pool (engine/prefix_cache.py): a
    # device-resident, radix-matched cache of refcounted prompt prefixes
    # (pack system blocks, tool schemas) so a FRESH session seed-copies
    # the shared rows and prefills only its suffix. This many pool
    # entries are allocated beside the slot cache; 0 disables the pool
    # entirely (no allocation, no programs — a true no-op path).
    prefix_cache_slots: int = 0
    # Max KV rows cached per pool entry; 0 = max_seq. Longer prefixes
    # cache their leading rows only (the tail re-prefills).
    prefix_cache_rows: int = 0
    # A prefix publishes into the pool once seen this many times across
    # placements (radix LCP of fresh prompts). Prefixes registered via
    # register_prefix() (pack system blocks) publish on first sight.
    prefix_cache_publish_threshold: int = 2
    # Prefixes shorter than this never publish or seed — a row copy that
    # saves fewer tokens than this is not worth the dispatch.
    prefix_cache_min_tokens: int = 8
    # Host-paged tier: entries LRU-demoted off the device pool keep their
    # rows in host RAM up to this count (restore machinery pages them
    # back through a slot on the next hit). 0 = evicted entries drop.
    prefix_cache_host_entries: int = 32
    # Grammar-constrained decoding (engine/grammar/): False is a guarded
    # true no-op — no per-slot FSM state or mask tables are allocated and
    # the compiled programs carry zero mask operands (byte-identical
    # traces to a pre-grammar engine). True threads a per-slot grammar
    # state + [num_slots, grammar_max_states, vocab] transition table
    # through the decode step: the mask row is gathered ON DEVICE and
    # applied inside sample_tokens_per_slot (no host round-trip), and
    # the FSM state advances on the sampled token.
    grammar: bool = False
    # Bounded admission: submit() fast-fails with FinishReason.OVERLOADED
    # once this many requests are already waiting — overload degrades to
    # an immediate, observable shed instead of unbounded queue latency
    # (the KEDA-style backpressure signal turned into a hard bound).
    # 0 = unbounded (the guarded pre-existing behavior).
    max_queue: int = 0
    # Hung-dispatch watchdog: a decode chunk whose device→host sync
    # exceeds this many seconds trips WatchdogTimeout — the engine marks
    # itself unhealthy, fails in-flight handles, and takes the existing
    # crash-recovery path (device state reallocation; health restores on
    # success). Costs one short-lived sync thread per chunk while
    # enabled. None = no watchdog threads, direct sync (the guarded
    # default). Leave None under multihost lockstep: a wall-clock trip
    # on one rank would diverge the replicated step streams (the tick
    # watchdog in multihost.py owns that failure class).
    watchdog_s: Optional[float] = None
    # State capacity of one slot's device transition table. Grammars
    # needing more states are rejected at submit. Device memory cost is
    # num_slots × grammar_max_states × vocab_size × 4 bytes — size it
    # down for large vocabularies (the engine warns at >1 GiB). The
    # default keeps generic JSON mode servable (its automaton needs
    # 2237 states over the byte tokenizer); schema grammars typically
    # need well under 200.
    grammar_max_states: int = 2560
    # Stall-free batching (engine/interleave.py): per-step prompt-token
    # budget for MIXED prefill+decode dispatches. With a positive
    # budget, an arriving prompt no longer stalls the decode batch for
    # its full prefill: placement splits the prompt into pieces of at
    # most this many tokens and every piece rides a fused program that
    # also advances all active decode slots by one token — decode
    # inter-token latency is bounded by ONE mixed step instead of a
    # whole prefill, at the cost of one extra batch-decode forward per
    # piece. Interleaved prefill is bit-identical to monolithic prefill
    # (tests/test_interleave.py pins greedy tokens AND resident KV).
    # 0 (default) is a guarded true no-op: no mixed programs are built
    # and the scheduler keeps the exact prefill-first paths.
    prefill_chunk_tokens: int = 0
    # Parallel AOT warmup (engine/warmup.py): > 0 dispatches warmup's
    # independent compile tasks (decode variants, prefill/extend
    # buckets, mixed pieces, session/prefix/page transfers, the spec
    # family) across a bounded pool of this many threads — XLA
    # compilation releases the GIL, so a cold start compiles N program
    # families concurrently instead of one at a time. Each concurrent
    # worker chains donated KV operands through its OWN scratch cache
    # copy, so peak warmup device memory grows by up to
    # (warmup_threads - 1) x the KV allocation; size it to spare HBM.
    # The compiled program set, the traced signatures, and the
    # post-warmup state restore are IDENTICAL to serial warmup
    # (tests/test_coldstart.py pins both). 0 (default) is a guarded
    # true no-op: no executor, no scratch caches, the exact serial
    # warmup order (the knob is never read at trace time, so lowered
    # programs are byte-identical across values).
    warmup_threads: int = 0
    # Engine flight recorder (engine/flight.py): capacity of the
    # fixed-size ring buffer of lifecycle events (submit/claim/placement/
    # prefill piece/mixed step/decode chunk/offload/restore/terminal)
    # with per-request latency breakdowns, step-timing histograms, and
    # the `omnia.engine.request` child span when submit() carries a
    # trace_ctx. Everything it records is strictly host-side wall time
    # between dispatches — compiled programs and sampled tokens are
    # untouched. 0 (default) is a guarded true no-op: no recorder object
    # exists, no span is ever opened, every seam is one `is not None`
    # check (tests/test_flight.py).
    flight_events: int = 0

    def spec_window(self) -> int:
        """Speculative verify window W — the most proposals any slot
        may submit per verify step; the compiled verify shape is
        [num_slots, W + 1]. 0 while speculation is off."""
        if not self.spec_decode:
            return 0
        return max(self.spec_decode, self.spec_decode_max)

    def chunk_variants(self) -> tuple[int, ...]:
        """Compiled decode-chunk sizes, descending, always containing
        decode_chunk and 1 (the queued-prefill TTFT escape hatch)."""
        sizes = set(self.decode_chunk_variants) | {max(1, self.decode_chunk), 1}
        bad = [k for k in sizes if k < 1 or k > max(1, self.decode_chunk)]
        if bad:
            raise ValueError(
                f"decode_chunk_variants {bad} outside [1, decode_chunk]"
            )
        return tuple(sorted(sizes, reverse=True))

    def restore_buckets(self) -> tuple[int, ...]:
        """Row counts used when moving a session's KV rows device↔host:
        fixed power-of-two sizes (plus max_seq) keep the transfer/restore
        programs compile-stable regardless of actual session length."""
        usable = self.usable_buckets()
        b = min(usable) if usable else 64
        out = []
        while b < self.max_seq:
            out.append(b)
            b *= 2
        out.append(self.max_seq)
        return tuple(out)

    def restore_bucket_for(self, n: int) -> int:
        for b in self.restore_buckets():
            if n <= b:
                return b
        raise ValueError(f"{n} rows exceed max_seq {self.max_seq}")

    def prefix_rows(self) -> int:
        """Row capacity of one shared-prefix pool entry."""
        rows = self.prefix_cache_rows or self.max_seq
        return min(rows, self.max_seq)

    def prefix_buckets(self) -> tuple[int, ...]:
        """Row counts for shared-prefix pool transfers (store / seed-copy /
        demote): the restore buckets that fit a pool entry — the same
        fixed-shape discipline that keeps session paging compile-stable."""
        buckets = tuple(b for b in self.restore_buckets() if b <= self.prefix_rows())
        return buckets or self.restore_buckets()[:1]

    def prefix_bucket_for(self, n: int) -> int:
        for b in self.prefix_buckets():
            if n <= b:
                return b
        return self.prefix_buckets()[-1]

    def num_page_positions(self) -> int:
        """Page-table width: table positions per slot (max_seq / page)."""
        return self.max_seq // max(self.kv_page_tokens, 1)

    def page_run_buckets(self) -> tuple[int, ...]:
        """Page-count buckets for prefix host-tier page transfers
        (gather/scatter a TRASH-padded fixed-length page run — the same
        fixed-shape discipline as the restore buckets)."""
        cap = max(-(-self.prefix_rows() // max(self.kv_page_tokens, 1)), 1)
        out, b = [], 1
        while b < cap:
            out.append(b)
            b *= 2
        out.append(cap)
        return tuple(out)

    def page_bucket_for(self, n: int) -> int:
        for b in self.page_run_buckets():
            if n <= b:
                return b
        return self.page_run_buckets()[-1]

    def mixed_prefill_buckets(self) -> tuple[int, ...]:
        """Prefill-piece buckets the fused mixed prefill+decode programs
        compile for: every usable bucket a budget-sized piece can land
        in, plus the 1-token degrade bucket used at the cache end (the
        same no-write-past-max_seq discipline as ``_extend_pieces``).
        () when interleaving is off — no mixed programs exist at all."""
        usable = self.usable_buckets()
        if self.prefill_chunk_tokens <= 0 or not usable:
            return ()
        cap = self.bucket_for(min(self.prefill_chunk_tokens, max(usable)))
        return tuple(sorted({b for b in usable if b <= cap} | {1}))

    def usable_buckets(self) -> tuple[int, ...]:
        """Prefill buckets that fit the KV cache (a bucket's chunk is
        written whole, so it must not exceed max_seq)."""
        return tuple(b for b in self.prefill_buckets if b <= self.max_seq)

    def bucket_for(self, n: int) -> int:
        buckets = self.usable_buckets()
        for b in buckets:
            if n <= b:
                return b
        limit = buckets[-1] if buckets else 0
        raise ValueError(
            f"prompt of {n} tokens exceeds largest usable prefill bucket {limit}"
        )
