"""Continuous-batching inference engine.

This is the component the reference platform does not have: it serves LLM
turns from the attached accelerator instead of relaying HTTPS SSE streams
(the reference's provider clients, SURVEY.md §0.2 / reference
internal/runtime/provider.go). The runtime gRPC layer streams tokens from
here (replacing reference internal/runtime/message.go:169 `conv.Stream`).

Architecture (TPU-first):

- **Slot batching.** The decode step is one compiled XLA program over a
  fixed batch of `num_slots` sequences; requests claim/free slots as they
  arrive/finish (continuous batching). Inactive slots still compute — a
  static shape beats a recompile, and idle-slot FLOPs are reclaimed by
  admission, not by shape changes.
- **Prefill/decode disaggregation.** Prefill runs as its own self-contained
  program per bucketed prompt length (no cache reads), producing a KV chunk
  that a tiny donated-insert program places into the slot's rows. Decode
  never sees prompt-length shapes, so its compiled step is stable.
- **Everything stays on device.** Sampled tokens feed the next decode step
  as device arrays; only the int32[num_slots] token vector crosses to host
  per step for streaming/stop logic.
- **Donation.** KV caches are donated through insert and decode steps, so
  XLA updates them in place — no per-step HBM copy of the cache.
- **Per-slot PRNG streams** make a request's sampling reproducible (seed)
  regardless of which other requests share the batch.
- **warmup()** AOT-compiles every (bucket) shape before the engine reports
  ready — the serving analog of the reference's capability gate (its
  operator scales a pod to zero until the runtime advertises capabilities;
  here readiness additionally implies "no compile on the request path").

Module layout (one seam per concern): compiled programs live in
``programs.py``, the dispatch/pipeline policy in ``scheduler.py``, slot
and session-KV residency in ``sessions.py``, request placement (prefill/
extend/grammar attach) in ``placement.py``; this module owns
construction, submission, warmup, and the thread lifecycle.
"""

from __future__ import annotations

import collections
import itertools
import logging
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp

from omnia_tpu.engine import phases
from omnia_tpu.engine.coldstart import PHASE_CODES, ColdStartTracker
from omnia_tpu.engine.devloop import DevLoopState
from omnia_tpu.engine.family import _PairCacheMixin, decode_blocks, refuse_unported
from omnia_tpu.engine.faults import FaultPlan
from omnia_tpu.engine.flight import FlightRecorder
from omnia_tpu.engine.interleave import _InflightPrefill, _InterleaveMixin
from omnia_tpu.engine.lifecycle import _LifecycleMixin
from omnia_tpu.engine.phases import phase
from omnia_tpu.engine.paged import (
    _PagedKVMixin,
    dp_divisibility_error,
    validate_paged_config,
)
from omnia_tpu.engine.placement import _PlacementMixin
from omnia_tpu.engine.prefix_cache import PrefixPool, _PrefixCacheMixin
from omnia_tpu.engine.programs import build_programs
from omnia_tpu.engine.scheduler import _SchedulerMixin
from omnia_tpu.engine.sessions import _SessionKV, _SessionMixin, _Slot
from omnia_tpu.engine.spec_decode import _SpecDecodeMixin, validate_spec_config
from omnia_tpu.engine.warmup import _WarmupMixin, listen_to_jax
from omnia_tpu.engine.types import (
    MAX_DEVICE_STOP_IDS,
    EngineConfig,
    FinishReason,
    Request,
    RequestHandle,
    SamplingParams,
    StreamEvent,
    resolve_dtype,
)
from omnia_tpu.models import ModelConfig, model_module, quant
from omnia_tpu.models.kv_quant import cache_bytes, validate_kv_quant
from omnia_tpu.ops.sampling import make_slot_key_data
from omnia_tpu.ops.attention import check_decode_kernel, pallas_decode_mode
from omnia_tpu.ops.moe import GROUPED_MATMUL_MIN_ROWS
from omnia_tpu.parallel import init_sharded, make_mesh, shard_pytree
from omnia_tpu.utils.compile_cache import enable_compilation_cache, enabled_dir

logger = logging.getLogger(__name__)


class InferenceEngine(
    _SchedulerMixin, _SessionMixin, _SpecDecodeMixin, _PrefixCacheMixin,
    _PlacementMixin, _InterleaveMixin, _LifecycleMixin, _PagedKVMixin,
    _WarmupMixin, _PairCacheMixin,
):
    """Slot-based continuous-batching engine over one model."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        engine_cfg: EngineConfig = EngineConfig(),
        params=None,
        seed: int = 0,
        devices=None,
        coldstart: Optional[ColdStartTracker] = None,
    ):
        self.model_cfg = model_cfg
        self.cfg = engine_cfg
        # The module every program, the weights and the cache go through
        # (models/__init__.py::model_module); the benchmark reads it
        # (benchmark/harness/manifest.py::served_by).
        self.model_module = model_module(model_cfg)
        refuse_unported(model_cfg, engine_cfg)
        # Cold-start tracker (engine/coldstart.py): phase spans + weight
        # streaming + warmup progress, mirrored into the stable metrics.
        # Callers that measure backend bring-up (bench, the runtime
        # server) pass their own tracker with the backend_init phase
        # already begun; construction here closes it.
        self._coldstart = coldstart or ColdStartTracker()
        # Every serving path compiles through the persistent cache (a
        # restart deserializes: cold warmup ~100 s → seconds), under the
        # process's one pair of jax.monitoring listeners (warmup.py).
        enable_compilation_cache()
        listen_to_jax()
        if engine_cfg.max_seq > model_cfg.max_seq_len:
            raise ValueError("engine max_seq exceeds model max_seq_len")
        if engine_cfg.num_slots % max(engine_cfg.dp, 1) != 0:
            raise ValueError("num_slots must be divisible by dp")
        if engine_cfg.warmup_threads < 0:
            raise ValueError("warmup_threads must be >= 0")
        validate_spec_config(engine_cfg)

        # Grammar-constrained decoding (engine/grammar/): gated ONCE here;
        # every grammar code path below checks this flag, so grammar=False
        # builds no tables, allocates no device state, and traces the
        # exact pre-grammar programs (the guarded-no-op contract).
        self._gr_on = bool(engine_cfg.grammar)
        if self._gr_on and engine_cfg.grammar_max_states < 2:
            raise ValueError("grammar_max_states must be >= 2 with grammar on")

        self._dtype = resolve_dtype(engine_cfg.dtype)
        # int8 KV cache (models/kv_quant.py): validated ONCE here; the
        # cache allocations below decide representation, and every
        # program/op dispatches on the array type — None means plain
        # arrays flow exactly as before (the guarded-no-op contract).
        self._kv_quant = validate_kv_quant(engine_cfg.kv_quant)
        self._mesh = None
        use_mesh = engine_cfg.dp * engine_cfg.tp * engine_cfg.sp > 1
        validate_paged_config(engine_cfg, use_mesh)
        # A cache the decode kernel refuses is an error here, not a
        # quiet switch of implementation at the first decode trace.
        check_decode_kernel(
            engine_cfg.max_seq, model_cfg.num_kv_heads,
            engine_cfg.kv_pages > 0, engine_cfg.dp, engine_cfg.tp,
        )
        if use_mesh:
            self._mesh = make_mesh(
                engine_cfg.dp, engine_cfg.tp, sp=engine_cfg.sp, devices=devices
            )

        self._seed = seed
        # Session-LRU clock. Injectable so replicated engines (multi-host
        # lockstep, engine/multihost.py) share a LOGICAL clock: eviction
        # order must be identical on every process or their compiled-step
        # streams diverge and the cross-host collectives deadlock.
        self.clock = time.monotonic
        # Cross-session shared-prefix pool (engine/prefix_cache.py).
        # Host-side books live here; the device arrays (_pk/_pv) are
        # (re)allocated with the caches in _init_device_state. The pool
        # LRU shares the engine's logical clock (lambda defers the
        # lookup — self.clock is injectable for multi-host lockstep).
        self._prefix_pool: Optional[PrefixPool] = None
        self._pending_prefix_regs: list[list[int]] = []  # guarded-by: _lock
        if engine_cfg.prefix_cache_slots > 0:
            if self._mesh is not None and (
                engine_cfg.prefix_cache_slots % max(engine_cfg.dp, 1) != 0
            ):
                raise ValueError(dp_divisibility_error(
                    "prefix_cache_slots", engine_cfg.prefix_cache_slots,
                    engine_cfg.dp,
                ))
            self._prefix_pool = PrefixPool(
                engine_cfg.prefix_cache_slots,
                engine_cfg.prefix_cache_host_entries,
                clock=lambda: self.clock(),
            )

        # Flight recorder (engine/flight.py): the step-level event ring
        # + per-request latency breakdowns. flight_events=0 allocates NO
        # recorder state — every seam below is a single None check (the
        # guarded no-op contract, tests/test_flight.py). The recorder
        # keeps its OWN monotonic clock, never self.clock: breakdowns
        # are host wall time, and an injected logical clock (lockstep)
        # must not distort them. Created before weight loading so the
        # cold-start init-phase events have somewhere to land.
        self._flight: Optional[FlightRecorder] = (
            FlightRecorder(engine_cfg.flight_events)
            if engine_cfg.flight_events > 0 else None
        )
        # Tracer for the `omnia.engine.request` child span (trace
        # continuity from the runtime's llm span): set by the embedding
        # server (utils.tracing.Tracer), None = no engine spans. Spans
        # only open for submits that carry a trace_ctx AND with the
        # flight recorder on — the recorder owns the span lifecycle.
        self.tracer = None

        # Programs are pure config functions — built BEFORE params so a
        # callable `params` (the streaming checkpoint loader) can overlap
        # weight streaming with the param-free program compiles
        # (engine/warmup.py _load_params_overlapped).
        progs = build_programs(self.model_cfg, self.cfg, self._mesh)
        # Program callables live as flat attributes (not the dataclass) so
        # tests/recovery can swap one (e.g. fault injection on
        # _prefill_insert_fn) without rebuilding the set.
        self._prefill_insert_fn = progs.prefill_insert
        self._prefill_ring_fn = progs.prefill_ring
        self._insert_fn = progs.insert
        self._decode_fns = progs.decode_fns
        self._decode_fn = self._decode_fns[max(self._decode_fns)]
        self._decode_fn_single = self._decode_fns[1]
        self._extend_fn = progs.extend
        self._extend_nosample_fn = progs.extend_nosample
        self._offload_fn = progs.offload
        self._restore_fn = progs.restore
        self._verify_fn = progs.verify
        self._verify_decode_fn = progs.verify_decode
        self._mixed_spec_fns = progs.mixed_spec
        self._mixed_spec_sample_fns = progs.mixed_spec_sample
        self._prefix_store_fn = progs.prefix_store
        self._prefix_seed_fn = progs.prefix_seed
        self._prefix_offload_fn = progs.prefix_offload
        self._mixed_fns = progs.mixed
        self._mixed_sample_fns = progs.mixed_sample
        self._activate_slot_fn = progs.activate_slot
        self._release_slot_fn = progs.release_slot
        self._page_copy_fn = progs.page_copy
        self._gather_pages_fn = progs.gather_pages
        self._scatter_pages_fn = progs.scatter_pages

        backend_init_s = self._coldstart.end_phase("backend_init")
        if self._flight is not None:
            self._flight.note_init_phase("backend_init", {
                "backend": jax.default_backend(),
                "seconds": backend_init_s,
            })

        qmode = quant.validate_mode(engine_cfg.quant)
        if callable(params):
            # Streaming checkpoint loader: runs under the weights_load
            # phase while the param-free program families compile on a
            # side thread (engine/warmup.py) — cold start pays
            # max(weights, KV-transfer compiles), not their sum.
            params = self._load_params_overlapped(params)
        if params is not None and quant.params_quantized(params):
            # Pre-quantized tree (the loader's flagship path): its mode is
            # authoritative — shard specs must match the actual leaf
            # structure, and a silent w8/w8d mismatch would serve the
            # wrong arithmetic. Adopt it; reject a contradictory config.
            detected = quant.detect_mode(params)
            if qmode is None:
                qmode = detected
            elif qmode != detected:
                raise ValueError(
                    f"EngineConfig.quant={qmode!r} but supplied params are "
                    f"{detected!r}-quantized"
                )
        specs = self.model_module.param_specs(model_cfg)
        if qmode:
            specs = quant.quantize_param_specs(specs, model_cfg, qmode)
        if params is None:
            key = jax.random.key(seed)
            if qmode:
                # Born quantized: for flagship sizes the full-precision
                # tree would not fit in HBM beside the int8 one.
                def init():
                    return quant.init_params_quantized(
                        model_cfg, key, qmode, dtype=self._dtype
                    )
            else:
                def init():
                    return self.model_module.init_params(
                        model_cfg, key, dtype=self._dtype
                    )
            params = init_sharded(init, specs, self._mesh)
        else:
            if qmode and not quant.params_quantized(params):
                # Caller-supplied full-precision params (small models /
                # tests). Checkpoint-loaded flagships should quantize in
                # the loader (load_params(quant=...)) so this on-device
                # pass is skipped.
                params = quant.quantize_params(params, model_cfg, qmode)
            if self._mesh is not None:
                params = shard_pytree(params, specs, self._mesh)
        self.params = params
        self._init_device_state()

        B = engine_cfg.num_slots
        self._slots = [_Slot() for _ in range(B)]
        self._waiting: list[tuple[Request, RequestHandle]] = []  # guarded-by: _lock
        # Requests between queue removal and slot activation (mid-
        # placement): invisible to queue_depth AND active_slots, so the
        # graceful-drain wait must count them explicitly.
        self._placing = 0  # guarded-by: _lock
        self._pass_at, self._prefill_seq = 0.0, itertools.count()  # flight's t_pass; spans' seq
        self._lock = threading.Lock()
        self._req_counter = itertools.count()
        # Sessionful KV registry — engine-thread-owned: only step() and the
        # helpers it calls touch it. Cross-thread requests (release_session)
        # arrive via _pending_releases under _lock. LRU uses last_used.
        self._sessions: dict[str, _SessionKV] = {}
        self._pending_releases: list[str] = []  # guarded-by: _lock
        # Cross-worker session migration (sessions.py import_session):
        # validated payloads queued for the engine thread to adopt —
        # the same queued cross-thread contract as releases.
        self._pending_imports: list = []  # guarded-by: _lock
        # Dispatched-but-unread decode chunks (_InflightChunk entries,
        # engine/devloop.py). Engine-thread-owned.
        self._inflight: collections.deque = collections.deque()
        # The watchdog's read-back lane (engine/devloop.py): ONE
        # long-lived drainer thread, started on the first chunk read.
        # None without a watchdog (no thread, no state).
        self._devloop: Optional[DevLoopState] = (
            DevLoopState() if engine_cfg.watchdog_s is not None else None
        )
        # Token-budget interleaving (engine/interleave.py): the at-most-
        # one placement currently mid-interleave. Always None with
        # prefill_chunk_tokens=0 — every interleave path is then dead.
        self._prefilling: Optional[_InflightPrefill] = None

        self._thread: Optional[threading.Thread] = None
        self._stop_event = threading.Event()
        self._healthy = True
        # Graceful drain (stop(drain=True)): True stops admission —
        # submit() sheds OVERLOADED — while queued/active work finishes.
        self._draining = False  # guarded-by: _lock
        # Chaos-harness injection seam (engine/faults.py): tests set this
        # to inject hung/slow chunk syncs and flaky submits. None in
        # production — every consult is a cheap attribute check.
        self._fault_plan: Optional[FaultPlan] = None

        # Metrics (engine-level; exported via utils.metrics by the runtime).
        # The *_s accumulators split host wall time between program
        # DISPATCH (async submit to the device stream) and SYNC (waiting
        # on chunk outputs) — the roofline evidence for whether serving is
        # device-bound or host/link-bound.
        self.metrics = {
            "requests_submitted": 0,
            "requests_finished": 0,
            "tokens_generated": 0,
            "prefill_steps": 0,
            "decode_steps": 0,
            # Where the scheduler decides (scheduler.py
            # _count_decode_dispatch): one dispatch per program call that
            # decodes, so decode_steps / decode_dispatches is the
            # realised chunk; _single counts calls of the one-step decode
            # program; _blocked the dispatches made while requests waited
            # and none had a slot (pipelined, so its share of
            # decode_dispatches is how much of a full engine's queueing
            # ran a step ahead); decode_slot_steps sums live slots x
            # steps at dispatch (occupancy where the batch is formed) and
            # decode_kv_blocks the decode kernel's blocks their contexts
            # span x steps (decode_window_rows: a window layer's ring rows);
            # decode_steps_sampling / _filtering the steps in which a live
            # request sampled / also filtered (ops/sampling.py's gates);
            # pipeline_flushes counts the flushes forced by a waiting
            # request that had a slot to go to; programs_compiled_serving
            # the programs asked of the compiler after warmup() returned;
            # placements_deferred the placements whose first token went on
            # the pipeline unread (placement.py _defers_first_token): over
            # prefill_steps, the share that left the thread without a read.
            "decode_dispatches": 0,
            "decode_dispatches_single": 0,
            "decode_dispatches_blocked": 0,
            "decode_slot_steps": 0,
            "decode_kv_blocks": 0, "decode_window_rows": 0,
            "decode_steps_sampling": 0,
            "decode_steps_filtering": 0,
            "pipeline_flushes": 0,
            "placements_deferred": 0,
            "programs_compiled_serving": 0,
            "extend_steps": 0, "extend_tokens": 0,  # (tokens placed in pieces)
            "prefill_tokens": 0, "prefill_tokens_blocked": 0,
            "prefix_reuse_tokens": 0,
            "session_offloads": 0,
            "session_restores": 0,
            # Live cross-worker session migration (sessions.py): exports
            # hand a retiring worker's idle sessions to the coordinator
            # in the host offload row format; imports adopt them here so
            # the next turn restores instead of re-prefilling.
            "session_exports": 0,
            "session_imports": 0,
            # Cross-session shared-prefix pool (engine/prefix_cache.py).
            "prefix_cache_hit_tokens": 0,
            "prefix_cache_insertions": 0,
            "prefix_cache_evictions": 0,
            "prefix_cache_host_hits": 0,
            "prefix_cache_offload_elisions": 0,
            "decode_dispatch_s": 0.0,
            "decode_sync_s": 0.0,
            "prefill_dispatch_s": 0.0,
            # Speculative decoding (spec_decode.py): acceptance rate =
            # spec_accepted / spec_proposed; tokens-per-weight-stream =
            # (tokens_generated during spec) / spec_steps. gate_state is
            # the self-gate's decision (0 probing / 1 on / 2 off),
            # accept_ema the engine-wide accept-rate EMA driving the
            # per-slot depths, index_bytes the bounded n-gram index's
            # estimated host footprint.
            "spec_steps": 0,
            "spec_proposed": 0,
            "spec_accepted": 0,
            "spec_gate_state": 0,
            "spec_accept_ema": 0.0,
            "spec_index_bytes": 0,
            # Request-lifecycle robustness (always present, zero until a
            # knob/fault engages): shed = OVERLOADED fast-fails at
            # submit (full queue or draining; NOT counted as submitted),
            # deadline_exceeded = DEADLINE terminals (queued sheds +
            # early mid-decode finishes), watchdog_trips = hung-dispatch
            # watchdog firings (each one also counts a recovery).
            "requests_shed": 0,
            "deadline_exceeded": 0,
            "watchdog_trips": 0,
            # Crash recoveries (lifecycle._recover): device-state
            # reallocations after a failed/watchdog-tripped step.
            # Initialized here (not lazily on first recovery) so the
            # stable key set is the same on a healthy engine — a
            # dashboard querying it pre-incident reads 0, not KeyError.
            "recoveries": 0,
            # Stall-free batching (engine/interleave.py): mixed_steps =
            # fused prefill+decode dispatches, interleaved_prefill_tokens
            # = prompt tokens consumed by them (metered per piece — exact
            # under mid-prefill aborts), decode_stall_steps = prefill
            # dispatches that idled a live decode batch (the prefill-
            # first cost the token-budget policy drives to zero).
            "mixed_steps": 0,
            "interleaved_prefill_tokens": 0,
            "decode_stall_steps": 0,
            # Grammar-constrained decoding (engine/grammar/).
            # compile_hits/misses mirror the process-global grammar
            # compile cache (content-addressed, key-stable across
            # processes); masked_logit_fraction is the running mean
            # fraction of the vocabulary masked per constrained step;
            # rejections_avoided counts constrained generations brought
            # to a valid finish — each one a would-have-been
            # bad_response_format retry loop.
            "grammar_compile_hits": 0,
            "grammar_compile_misses": 0,
            "masked_logit_fraction": 0.0,
            "grammar_rejections_avoided": 0,
            # int8 KV cache (models/kv_quant.py) — capacity gauges, set
            # at every (re)allocation: bytes_per_token is the per-token
            # KV read/write footprint (k+v across layers, scales
            # included) at the configured precision; device_bytes is the
            # real allocation of slot cache + prefix pool. The bench
            # roofline and the 2× capacity claim read THESE, not an
            # assumed dtype.
            "kv_quant_enabled": 1 if self._kv_quant else 0,
            "kv_quant_bytes_per_token": self.kv_bytes_per_token(),
            "kv_quant_device_bytes": cache_bytes(
                *self._cache, self._pk, self._pv
            ),
            # The expert layer of a chip that holds a share of the experts
            # (ops/moe.py::moe_dropless), summed on the device over a decode chunk's
            # steps and layers and read back with its tokens: the (token, expert)
            # assignments that landed on an expert held here, and the held experts
            # that got a token, a layer a step, over every row of the batch, live or
            # not; decode_kda_slots (the latent family's), decode_delta_slots, decode_mamba_slots
            # (the pair family's): the states linear-attention / state-space layers updated.
            "moe_assignments_held": 0, "moe_experts_hit": 0,
            "decode_kda_slots": 0, "decode_delta_slots": 0, "decode_mamba_slots": 0,
            # Paged KV cache (engine/kv_pages.py) — pool gauges, live
            # while kv_pages > 0 and zero otherwise: usable pages total/
            # free, internal fragmentation of slot-referenced pages
            # (allocated-but-unused token slack), and copy-on-write page
            # copies (a shared prefix page duplicated because a slot
            # diverged into it).
            "kv_pages_total": self._pages.total if self._pages else 0,
            "kv_pages_free": self._pages.free_count if self._pages else 0,
            "kv_page_fragmentation": 0.0,
            "kv_page_cow_copies": 0,
            # Engine flight recorder (engine/flight.py): set once at
            # construction, like kv_quant_enabled — dashboards can tell
            # whether per-request latency breakdowns exist before asking
            # for a dump.
            "flight_enabled": 1 if self._flight is not None else 0,
            # Cold-start observability (engine/coldstart.py): the
            # persistent-compile-cache switch and the submit-to-ready
            # progress surface. warmup_phase is the PHASE_CODES index
            # (0 idle → 5 ready); programs/bytes counters fill in DURING
            # bring-up, so a probe mid-warmup reads real progress.
            # manifest hits/misses compare this start's program list with
            # the last's; warmup_cache_hits/_misses join at the sync below.
            "compile_cache_enabled": 1 if enabled_dir() else 0,
            "warmup_phase": PHASE_CODES[self._coldstart.current_phase()],
            "warmup_programs_total": 0,
            "warmup_programs_done": 0,
            "warmup_manifest_hits": 0,
            "warmup_manifest_misses": 0,
            "weights_bytes_total": 0,
            "weights_bytes_loaded": 0,
        }
        self._gr_mask_sum = 0.0
        self._gr_mask_steps = 0
        # A callable-params construction streamed weights before the
        # metrics dict existed — fold the tracker's view in now.
        self._sync_coldstart_metrics()
        logger.info(
            "engine built: backend=%s pallas_decode=%s grouped_matmul_from_rows=%d decode_blocks=%s"
            " blocked_buckets=%s slots=%d max_seq=%d chunks=%s quant=%s kv_quant=%s",
            jax.default_backend(), pallas_decode_mode(), GROUPED_MATMUL_MIN_ROWS,
            decode_blocks(model_cfg, self.cfg, self._dtype), self._blocked_buckets(), B,
            engine_cfg.max_seq, self.cfg.chunk_variants(), qmode, self._kv_quant)

    def _alloc_kv_state(self):
        """Fresh KV arrays at the engine's exact layout, representation,
        and sharding: (ck, cv, pk, pv) — the allocation half of
        ``_init_device_state``, also what each ADDITIONAL parallel
        warmup worker chains its donated operands through
        (engine/warmup.py). Pure allocation: no allocator or pool books
        are touched.

        Non-paged: the slot cache plus (pool on) the shared-prefix
        arrays [L, P, R, H, D] beside it, same layout/sharding (P over
        dp, heads over tp) AND the same KV representation — under
        kv_quant both hold int8 rows + scales, so the same pool bytes
        cache 2× the prefixes. Paged: ONE page pool + per-slot tables
        (engine/paged.py), pk/pv None."""
        B, S = self.cfg.num_slots, self.cfg.max_seq
        if self.cfg.kv_pages > 0:
            return tuple(self._alloc_paged_kv()), None, None
        pk = pv = None
        if self._prefix_pool is not None:
            pk, pv = self._zero_kv(
                self.cfg.prefix_cache_slots, self.cfg.prefix_buckets()[-1]
            )
        return self._zero_kv(B, S), pk, pv

    def _born_sharded(self, init, specs):
        """``init()`` at the engine's placement: plain on one device,
        every leaf born on its shards under a mesh (never whole on one
        device and moved)."""
        if self._mesh is None:
            return init()
        return init_sharded(init, specs, self._mesh)

    def _zero_kv(self, batch: int, rows: int):
        """The family's zeroed cache tuple for ``batch`` slots of ``rows``
        rows ((k, v) [L, batch, rows, Hkv, D] for Llama's), at the engine's
        KV representation and placement."""
        return tuple(self._born_sharded(
            lambda: self.model_module.init_kv_cache(
                self.model_cfg, batch, rows, dtype=self._dtype,
                kv_quant=self._kv_quant,
            ),
            self.model_module.kv_cache_specs(self._kv_quant),
        ))

    def _init_device_state(self):
        """(Re)allocate KV caches and per-slot device state. Called at
        construction and from crash recovery — after an exception inside a
        donated-buffer step, self._ck/_cv may point at deleted arrays, so
        the only way back to a healthy engine is a fresh allocation."""
        B = self.cfg.num_slots
        if self.cfg.kv_pages > 0:
            # Paged layout (engine/paged.py): ONE page pool + per-slot
            # page tables serve the slots, the prefix cache (page runs
            # in the same pool), and session paging from a single free
            # list — the dedicated _pk/_pv prefix arrays do not exist.
            self._init_paged_state()
        else:
            self._cache, self._pk, self._pv = self._alloc_kv_state()
            if self._prefix_pool is not None:
                # A reallocation means any device-resident pool entries
                # died with the caches; host-paged entries survive in
                # the pool's books.
                self._prefix_pool.on_device_reset()
                if hasattr(self, "metrics"):  # absent at construction
                    self.metrics["prefix_cache_evictions"] = (
                        self._prefix_pool.evictions
                    )
        if hasattr(self, "metrics"):
            self.metrics["kv_quant_device_bytes"] = cache_bytes(
                *self._cache, self._pk, self._pv
            )

        # Grammar-constrained decoding state: per-slot FSM state beside
        # the sampler key data, per-slot transition tables, and the
        # active-mask gate. grammar=off allocates NONE of it.
        self._gstate = self._gtable = self._gactive = None
        self._gbias_zero = None
        self._gslot_key = None
        if self._gr_on:
            V = self.model_cfg.vocab_size
            Sg = self.cfg.grammar_max_states
            table_bytes = B * Sg * V * 4
            if table_bytes > 1 << 30:
                logger.warning(
                    "grammar transition tables need %.1f GiB of device "
                    "memory (num_slots=%d x grammar_max_states=%d x "
                    "vocab=%d x 4B) — size grammar_max_states down to "
                    "the largest schema you actually serve",
                    table_bytes / (1 << 30), B, Sg, V,
                )
            self._gstate = jnp.zeros((B,), jnp.int32)
            self._gactive = jnp.zeros((B,), jnp.bool_)
            self._gtable = jnp.zeros((B, Sg, V), jnp.int32)
            self._gbias_zero = jnp.zeros((V,), jnp.float32)
            # Host mirror of what each slot's device table rows hold, so
            # re-placing the same grammar (the common case: one schema,
            # many requests) skips the [Sg, V] re-upload.
            self._gslot_key = [None] * B

        self._tokens = jnp.zeros((B,), jnp.int32)       # last sampled token
        self._positions = jnp.zeros((B,), jnp.int32)    # next write row
        self._temp = jnp.zeros((B,), jnp.float32)
        self._top_p = jnp.ones((B,), jnp.float32)
        self._top_k = jnp.zeros((B,), jnp.int32)
        self._active = jnp.zeros((B,), jnp.bool_)
        # Device-side finish tracking: remaining emission budget after the
        # first token, and the request's stop ids (-1 padded). The decode
        # chunk deactivates a slot the step it hits a stop id or exhausts
        # its budget, so positions freeze and no garbage rows are written
        # for the rest of the chunk — the host stays authoritative for
        # handles, the device mask just stops wasted work.
        self._budget = jnp.zeros((B,), jnp.int32)
        self._stop_ids = jnp.full((B, MAX_DEVICE_STOP_IDS), -1, jnp.int32)
        self._key_data = jnp.stack(
            [make_slot_key_data(self._seed + 1 + i) for i in range(B)]
        )

    def kv_bytes_per_token(self) -> int:
        """HBM bytes one cached token costs (k+v over all layers, f32
        row scales included under kv_quant) — the KV term of the decode
        roofline at THIS engine's configured precision."""
        mc = self.model_cfg
        itemsize = 1 if self._kv_quant else jnp.dtype(self._dtype).itemsize
        if mc.is_latent:  # a row a token a latent layer, as allocated (a state does not grow)
            return mc.attention_kinds.count("full") * self.model_module.row_width(mc) * itemsize
        scale_bytes = 4 if self._kv_quant else 0
        return (
            mc.attention_kinds.count("full") * mc.num_kv_heads  # rings do not grow
            * (mc.head_dim * itemsize + scale_bytes) * 2
        )


    # ------------------------------------------------------------------
    # Submission API
    # ------------------------------------------------------------------

    def submit(
        self,
        prompt_tokens: list[int],
        params: SamplingParams = SamplingParams(),
        session_id: Optional[str] = None,
        grammar=None,
        deadline_s: Optional[float] = None,
        trace_ctx: Optional[str] = None,
    ) -> RequestHandle:
        """Queue a generation request. With a session_id, the session's KV
        rows persist across requests: the next request prefills only the
        tokens past its longest common prefix with what is already cached
        (multi-turn serving cost becomes O(new tokens), SURVEY §7).
        With a `grammar` (engine/grammar.TokenGrammar), every sampled
        token is FSM-masked on device and EOS is admissible only in
        accepting states — requires EngineConfig.grammar=True.
        With a `deadline_s` TTL, a request still queued at the deadline
        is shed with FinishReason.DEADLINE and an active request
        finishes early at the deadline boundary (chunk granularity).
        With a `trace_ctx` W3C traceparent (the runtime llm span) and
        flight recording on, the request's lifecycle is recorded and an
        `omnia.engine.request` child span is emitted into self.tracer —
        trace continuity from the facade down to TPU dispatch."""
        with phase(phases.SUBMIT) as sp:
            handle = self._submit(
                prompt_tokens, params, session_id, grammar, deadline_s,
                trace_ctx,
            )
            if sp:
                sp.set_metadata(
                    request_id=handle.request_id, n_prompt=len(prompt_tokens)
                )
            return handle

    def _submit(self, prompt_tokens, params, session_id, grammar, deadline_s,
                trace_ctx) -> RequestHandle:
        if self._fault_plan is not None and self._fault_plan.take_submit_fault():
            raise RuntimeError("injected flaky submit (FaultPlan)")
        rid = f"req-{next(self._req_counter)}"
        handle = RequestHandle(rid)
        request = Request(
            rid, list(prompt_tokens), params, session_id=session_id,
            grammar=grammar, trace_ctx=trace_ctx,
        )
        if deadline_s is not None:
            # Engine clock domain (not time.monotonic): lockstep ranks
            # share the leader's logical clock, so the deadline reaps
            # identically everywhere.
            request.deadline_at = self.clock() + deadline_s
        if grammar is not None:
            err = self._validate_grammar(grammar, params)
            if err:
                handle._push(
                    StreamEvent(rid, finish_reason=FinishReason.ERROR, error=err)
                )
                return handle
            self._sync_grammar_cache_metrics()
        if not prompt_tokens:
            handle._push(
                StreamEvent(rid, finish_reason=FinishReason.ERROR, error="empty prompt")
            )
            return handle
        if params.max_tokens < 1:
            handle._push(
                StreamEvent(
                    rid,
                    finish_reason=FinishReason.ERROR,
                    error=f"max_tokens must be >= 1, got {params.max_tokens}",
                )
            )
            return handle
        if not self.cfg.usable_buckets():
            handle._push(
                StreamEvent(
                    rid,
                    finish_reason=FinishReason.ERROR,
                    error="no usable prefill buckets (all exceed max_seq)",
                )
            )
            return handle
        # Prompts longer than the largest bucket prefill in chunks, so the
        # only hard limit is the KV cache itself (≤ max_seq - 2 leaves the
        # decode-step write rows legal).
        if len(prompt_tokens) > self.cfg.max_seq - 2:
            handle._push(
                StreamEvent(
                    rid,
                    finish_reason=FinishReason.ERROR,
                    error=f"prompt of {len(prompt_tokens)} tokens exceeds "
                    f"KV capacity (max_seq {self.cfg.max_seq} - 2)",
                )
            )
            return handle
        with self._lock:
            # Bounded admission: overload (or a draining engine) is an
            # immediate OVERLOADED terminal, never unbounded queue wait.
            # Shed requests are NOT counted as submitted (the rejected-
            # request convention) — requests_shed is their own ledger.
            if self._draining:
                shed_why = "engine draining (stop(drain=True))"
            elif 0 < self.cfg.max_queue <= len(self._waiting):
                shed_why = f"queue full (max_queue={self.cfg.max_queue})"
            else:
                self._waiting.append((request, handle))
                self.metrics["requests_submitted"] += 1
                if self._flight is not None:
                    # Inside the admission critical section: the engine
                    # thread cannot claim this request (it needs _lock to
                    # see the queue) before its submit event is recorded,
                    # so submit-seq < claim-seq always holds in the ring.
                    self._flight.note_submit(
                        rid, len(prompt_tokens), trace_ctx, self.tracer
                    )
                return handle
            self.metrics["requests_shed"] += 1
        handle._push(
            StreamEvent(rid, finish_reason=FinishReason.OVERLOADED, error=shed_why)
        )
        return handle

    def supports_grammar(self) -> bool:
        """True when this engine enforces request grammars (the runtime
        only attaches one when this answers True)."""
        return self._gr_on

    def queue_depth(self) -> int:
        """Waiting requests — the autoscaling signal (north star replaces the
        reference's active-connections KEDA trigger with queue depth)."""
        with self._lock:
            return len(self._waiting)

    def active_slots(self) -> int:
        return sum(1 for s in self._slots if s.active)

    def decode_slots_active(self) -> int:
        """Occupied decode slots — the disaggregated decode tier's
        autoscaling signal (engine/disagg.py). An active slot IS a
        decode-resident stream (placement completes the prefill), so
        today this equals active_slots(); the alias keeps the wire
        name stable for when the device-resident decode loop splits
        the two."""
        return self.active_slots()

    # ------------------------------------------------------------------
    # Thread loop / lifecycle: start/stop/drain/recovery live in
    # engine/lifecycle.py (_LifecycleMixin); the synchronous generate()
    # helper and live_request_ids() in engine/scheduler.py
    # (_SchedulerMixin) — the step-driving seam.
    # ------------------------------------------------------------------
