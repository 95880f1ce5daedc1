"""Mock engine: scripted scenario playback with the real Engine interface.

The platform analog of the reference's mock LLM provider (reference
internal/runtime/provider.go:50-91 wires a scenario-playback provider so
every platform test runs with zero real LLM calls). Here the mock
implements the same submit/step/handle surface as InferenceEngine so the
runtime, facade, and e2e tests exercise the identical streaming path with
no device.

Scenarios map a matcher against the decoded prompt to a scripted reply;
special directives simulate failures and tool calls.
"""

from __future__ import annotations

import itertools
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

from omnia_tpu.engine.disagg import validate_role
from omnia_tpu.engine.faults import FaultPlan
from omnia_tpu.engine.flight import FlightRecorder
from omnia_tpu.engine.mock_mirrors import _MockMirrorsMixin
from omnia_tpu.engine.mock_sessions import _MockSessionsMixin
from omnia_tpu.engine.tokenizer import ByteTokenizer
from omnia_tpu.engine.types import (
    FinishReason,
    Request,
    RequestHandle,
    SamplingParams,
    StreamEvent,
)


@dataclass
class Scenario:
    """One scripted behavior: if `pattern` matches the prompt, stream `reply`.

    By default the pattern is matched against the system block plus the
    CURRENT turn only (`match="turn"`) — a real model answers the latest
    user message, and matching the whole prompt would let a scenario keyed
    on an old user turn re-fire forever once that turn is in persisted
    history. Scenarios that deliberately assert history retention set
    `match="prompt"`.
    """

    pattern: str
    reply: str = ""
    error: Optional[str] = None          # stream an ERROR final instead
    delay_per_token_s: float = 0.0       # simulated decode latency
    ttft_s: float = 0.0                  # simulated prefill latency
    match: str = "turn"                  # "turn" | "prompt"

    def __post_init__(self):
        if self.match not in ("turn", "prompt"):
            raise ValueError(f"Scenario.match must be 'turn' or 'prompt', got {self.match!r}")

    def matches(self, prompt: str) -> bool:
        return re.search(self.pattern, prompt, re.DOTALL) is not None


def _current_turn_view(prompt: str) -> str:
    """System block + last user turn (incl. this turn's tool rounds):
    previous conversation turns are cut out. The marker is anchored at a
    line start, which keeps ordinary content containing the literal
    '[USER]' from hijacking the split; content that embeds a full
    newline-prefixed marker (a pasted transcript) can still confuse it —
    acceptable for a test mock, don't put raw transcripts in scenario
    content."""
    sys_end = prompt.find("[/SYS]")
    last_user = prompt.rfind("\n[USER]")
    if sys_end < 0 or last_user < 0 or last_user < sys_end:
        return prompt
    return prompt[: sys_end + len("[/SYS]")] + prompt[last_user + 1:]


DEFAULT_REPLY = "mock-reply"


class MockEngine(_MockMirrorsMixin, _MockSessionsMixin):
    """Drop-in scripted engine (no device, no model)."""

    def __init__(self, scenarios: Sequence[Scenario] = (), tokenizer=None,
                 kv_quant=None, fault_plan: Optional[FaultPlan] = None,
                 max_queue: int = 0, watchdog_s: Optional[float] = None,
                 prefill_chunk_tokens: int = 0, flight_events: int = 0,
                 kv_pages: int = 0, kv_page_tokens: int = 64,
                 spec_decode: int = 0, spec_decode_max: int = 0,
                 spec_gate_window: int = 0, warmup_threads: int = 0,
                 coldstart=None, name: str = "mock", role: str = "pooled"):
        from omnia_tpu.engine.coldstart import ColdStartTracker

        self.scenarios = list(scenarios)
        # Disaggregated role (engine/disagg.py): duck-typed off any
        # worker; "pooled" (the default) is the guarded true no-op —
        # an all-pooled fleet keeps the coordinator's role list None.
        self.role = validate_role(role)
        # Decode-slot occupancy gauge: playbacks past placement.
        self._decode_rids: set = set()  # guarded-by: _lock
        self.tokenizer = tokenizer or ByteTokenizer()
        # Request-id prefix. Default preserves the historical "mock-N"
        # ids; a FLEET of mocks behind one coordinator names each worker
        # so request ids stay unique across workers — the traffic
        # simulator joins flight terminals back to submits by id.
        self.name = name
        # Cold-start parity (engine/coldstart.py): no programs to
        # compile, but warmup() books the same phase spans, progress
        # counters, and manifest hits/misses through the REAL tracker —
        # scripted output untouched. warmup_threads is accepted
        # (providers forward it to both engines) and only affects the
        # thread count the ledger reports (nothing to parallelize).
        if warmup_threads < 0:
            raise ValueError("warmup_threads must be >= 0")
        self.warmup_threads = warmup_threads
        self._coldstart = coldstart or ColdStartTracker()
        self._coldstart.end_phase("backend_init")
        self._req_counter = itertools.count()
        self._lock = threading.Lock()
        # Flight-recorder parity (engine/flight.py): the mock records
        # the IDENTICAL event vocabulary so hermetic tests exercise the
        # full breakdown + trace-continuity path with no device;
        # flight_events=0 is the same guarded no-op as the engine's.
        self._flight: Optional[FlightRecorder] = (
            FlightRecorder(flight_events) if flight_events > 0 else None
        )
        self.tracer = None  # utils.tracing.Tracer for engine-request spans
        # Stall-free batching parity (engine/interleave.py): with a
        # token budget, each playback's "prefill" books the same
        # mixed-step/interleaved-token counts the real engine meters per
        # consumed piece; budget 0 mirrors prefill-first — a playback
        # whose prefill lands while other playbacks are live counts a
        # decode stall, exactly the signal the budget exists to zero.
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # Prompt-token backlog mirror for the coordinator's token-aware
        # load signal (live playbacks' prompt tokens).
        self._live_prompt_tokens = 0  # guarded-by: _lock
        # Request-lifecycle parity with InferenceEngine (chaos harness):
        # a counted FaultPlan (engine/faults.py) injects deaths/hangs/
        # flaky submits; max_queue bounds concurrent playbacks the same
        # way the engine bounds its waiting queue; watchdog_s converts a
        # hung dispatch (an injected hang past the bound) into the same
        # ERROR terminal + watchdog_trips count the engine produces.
        self.fault_plan = fault_plan
        self.max_queue = max_queue
        self.watchdog_s = watchdog_s
        self._healthy = True
        self._draining = False  # guarded-by: _lock
        self._live_plays = 0  # guarded-by: _lock
        # int8-KV parity (models/kv_quant.py): the mock has no cache,
        # but with kv_quant set it round-trips a deterministic pseudo-KV
        # block per request through the SAME rowwise quantize/dequant
        # the compiled programs trace (numpy twins are bit-identical to
        # the jnp path), so hermetic tests exercise identical numerics —
        # and scripted token output is EXACTLY unchanged, mirroring the
        # near-lossless contract the real engine documents.
        if kv_quant is not None:
            from omnia_tpu.models.kv_quant import validate_kv_quant

            kv_quant = validate_kv_quant(kv_quant)
        self.kv_quant = kv_quant
        # Paged-KV parity (engine/kv_pages.py): the mock has no device
        # pool, but with kv_pages set each live playback reserves real
        # pages from the SAME host-side allocator the engine books with,
        # so the occupancy/fragmentation gauges (and their exhaustion
        # behavior) are exercisable hermetically. kv_pages=0 allocates
        # nothing — the guarded no-op, zero-valued gauges.
        self.kv_pages = kv_pages
        self.kv_page_tokens = kv_page_tokens
        # Speculative-decoding parity (engine/spec_decode.py): the mock
        # has no verify program, but with spec_decode set each GREEDY
        # playback walks its scripted reply through the REAL bounded
        # _NgramIndex, the real per-slot depth policy
        # (spec_depth_update), and a real _SpecGate — the scripted
        # reply stands in for the model's own greedy choices, so
        # acceptance is what prompt lookup would genuinely achieve on
        # that stream. Scripted token output is EXACTLY unchanged; the
        # mirror only drives the spec metrics. All three knobs at 0 =
        # the guarded no-op (no index, no gate, zero-valued keys).
        self.spec_decode = spec_decode
        self.spec_decode_max = spec_decode_max
        self.spec_gate_window = spec_gate_window
        self._spec_gate = None
        self._spec_ema = 0.0  # guarded-by: _lock
        # Cumulative tokens walked by the mirror across ALL playbacks —
        # _SpecGate.tick assumes a monotone engine-wide counter (the
        # real engine passes tokens_generated); a per-playback position
        # would run the gate's rate math backwards between playbacks.
        self._spec_walked = 0  # guarded-by: _lock
        if spec_decode > 0 and spec_gate_window > 0:
            from omnia_tpu.engine.spec_decode import _SpecGate

            self._spec_gate = _SpecGate(spec_gate_window)
        # Session-migration parity (engine/sessions.py export/import):
        # the mock keeps no KV, but it DOES remember which sessions are
        # resident — token streams keyed by session_id — so the
        # coordinator's scale-down migration (export at the retiring
        # worker, import at the survivor, re-pin) is exercisable
        # hermetically, including the PoolExhausted rejection when the
        # survivor's page mirror cannot hold the imported rows.
        self._sessions: dict = {}  # guarded-by: _lock
        # The allocator REFERENCE is immutable after construction; its
        # internal books (and _page_slots) mutate only under _lock.
        self._page_alloc = None
        self._page_slots: list[int] = []  # guarded-by: _lock
        if kv_pages > 0:
            from omnia_tpu.engine.kv_pages import PageAllocator

            self._page_alloc = PageAllocator(kv_pages, kv_page_tokens, kv_pages)
            self._page_slots = list(range(kv_pages))
        self.metrics = {  # guarded-by: _lock
            "requests_submitted": 0,
            "requests_finished": 0,
            "tokens_generated": 0,
            # Grammar parity with InferenceEngine (host-side masks).
            "grammar_compile_hits": 0,
            "grammar_compile_misses": 0,
            "masked_logit_fraction": 0.0,
            "grammar_rejections_avoided": 0,
            # int8-KV parity: rows round-tripped host-side and the worst
            # per-request relative error observed (tests bound it by the
            # documented drift bound; 0.0 until a request runs).
            "kv_quant_enabled": 1 if kv_quant else 0,
            "kv_quant_rows_written": 0,
            "kv_quant_roundtrip_rel_err": 0.0,
            # Request-lifecycle parity (same semantics as the engine's
            # counters — the chaos suite reconciles against these).
            "requests_shed": 0,
            "deadline_exceeded": 0,
            "watchdog_trips": 0,
            # Stall-free batching parity (engine/interleave.py).
            "mixed_steps": 0,
            "interleaved_prefill_tokens": 0,
            "decode_stall_steps": 0,
            # Flight-recorder parity (engine/flight.py).
            "flight_enabled": 1 if flight_events > 0 else 0,
            # Session-migration parity (engine/sessions.py): scale-down
            # exports at the retiring worker, imports at the survivor.
            "session_exports": 0,
            "session_imports": 0,
            # Speculative-decoding parity (engine/spec_decode.py): the
            # greedy-playback prompt-lookup mirror books these.
            "spec_steps": 0,
            "spec_proposed": 0,
            "spec_accepted": 0,
            "spec_gate_state": 0,
            "spec_accept_ema": 0.0,
            "spec_index_bytes": 0,
            # Paged-KV parity (engine/kv_pages.py): live playbacks hold
            # pages in a real allocator, so these mirror the engine's
            # pool gauges; all zero with kv_pages=0.
            "kv_pages_total": self._page_alloc.total if self._page_alloc else 0,
            "kv_pages_free": (
                self._page_alloc.free_count if self._page_alloc else 0
            ),
            "kv_page_fragmentation": 0.0,
            "kv_page_cow_copies": 0,
            # Cold-start parity (engine/coldstart.py): warmup() books
            # these through the real tracker/manifest machinery.
            # compile_cache_enabled reads the same module state the
            # engine reads (normally 0 in a jax-free mock process).
            "compile_cache_enabled": 0,
            "warmup_phase": 0,
            "warmup_programs_total": 0,
            "warmup_programs_done": 0,
            "warmup_manifest_hits": 0,
            "warmup_manifest_misses": 0,
            # The mock compiles nothing, so its warm-up asks no cache.
            "warmup_cache_hits": 0,
            "warmup_cache_misses": 0,
            "weights_bytes_total": 0,
            "weights_bytes_loaded": 0,
        }
        self._gr_mask_sum = 0.0
        self._gr_mask_steps = 0

    def warmup(self, sessions: bool = True):
        """Cold-start ledger parity with InferenceEngine.warmup(): the
        same phase spans, progress counters, and manifest transaction
        through the REAL coldstart machinery — with a one-entry pseudo
        program inventory standing in for the compiled set (the mock
        compiles nothing; a second mock with the same knobs reads the
        manifest back as a hit). Playback behavior is untouched."""
        from omnia_tpu.engine.coldstart import (
            PHASE_CODES,
            WarmupManifest,
            manifest_bookkeeping,
            manifest_dir,
        )
        from omnia_tpu.utils.compile_cache import enabled_dir

        cs = self._coldstart
        inventory = [f"playback:vocab{self.tokenizer.vocab_size}"]
        cs.set_programs_total(len(inventory))
        cs.begin_phase("warmup_compile")
        key = WarmupManifest.manifest_key({
            "backend": "mock",
            "vocab": self.tokenizer.vocab_size,
            "kv_quant": self.kv_quant,
            "kv_pages": self.kv_pages,
            "kv_page_tokens": self.kv_page_tokens,
            "spec_decode": self.spec_decode,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
        })
        hits, misses = manifest_bookkeeping(
            manifest_dir(), key, inventory, cs, meta={"backend": "mock"},
        )
        done = cs.note_program(len(inventory))
        seconds = cs.end_phase("warmup_compile")
        cs.mark_ready()
        if self._flight is not None:
            # Same init-phase timeline shape as the real engine (the
            # closed-vocabulary parity tests read both).
            self._flight.note_init_phase("warmup_compile", {
                "seconds": seconds, "programs": len(inventory),
                "threads": self.warmup_threads, "manifest_hits": hits,
                "manifest_misses": misses,
            })
        snap = cs.snapshot()
        with self._lock:
            self.metrics["compile_cache_enabled"] = 1 if enabled_dir() else 0
            self.metrics["warmup_phase"] = PHASE_CODES["ready"]
            self.metrics["warmup_programs_total"] = len(inventory)
            self.metrics["warmup_programs_done"] = done
            self.metrics["warmup_manifest_hits"] = hits
            self.metrics["warmup_manifest_misses"] = misses
            self.metrics["weights_bytes_total"] = snap["weights_bytes_total"]
            self.metrics["weights_bytes_loaded"] = snap["weights_bytes_loaded"]

    def register_prefix(self, tokens) -> None:
        """Interface parity with InferenceEngine; the mock has no KV."""

    def supports_grammar(self) -> bool:
        """The mock enforces grammars host-side (same masks, no device),
        so tier-1 tests exercise the full constrained path hermetically."""
        return True

    def healthy(self) -> bool:
        """Interface parity with InferenceEngine; chaos tests flip the
        backing flag to simulate worker death/flap."""
        return self._healthy

    def queue_depth(self) -> int:
        # Only meaningful under bounded admission: live playbacks stand
        # in for the engine's waiting queue (with max_queue=0 the mock
        # keeps its historical always-idle signal).
        if self.max_queue <= 0:
            return 0
        with self._lock:
            return self._live_plays

    def active_slots(self) -> int:
        return 0

    def pending_prefill_tokens(self) -> int:
        """Prompt-token backlog of live playbacks — the mock's mirror of
        the engine's queued+in-flight prefill work, so the coordinator's
        token-aware load signal is exercisable hermetically."""
        with self._lock:
            return self._live_prompt_tokens

    def decode_slots_active(self) -> int:
        """Playbacks past placement — the decode tier's autoscaling
        signal (engine/disagg.py, prefill done and tokens streaming)."""
        with self._lock:
            return len(self._decode_rids)

    def submit(
        self,
        prompt_tokens: list[int],
        params: SamplingParams = SamplingParams(),
        session_id: Optional[str] = None,
        grammar=None,
        deadline_s: Optional[float] = None,
        trace_ctx: Optional[str] = None,
    ) -> RequestHandle:
        # Playback stays stateless (scenarios key on the prompt), but a
        # session_id registers the completed token stream in the
        # migration registry so scale-down can export/import it.
        if self.fault_plan is not None and self.fault_plan.take_submit_fault():
            raise RuntimeError("injected flaky submit (FaultPlan)")
        rid = f"{self.name}-{next(self._req_counter)}"
        handle = RequestHandle(rid)
        # Mirror InferenceEngine.submit's validation (and its metric
        # ordering: rejected requests are NOT counted as submitted).
        # Grammar liveness is checked first like the real engine does —
        # a starved grammar (stop id that is also a required token) must
        # refuse here too, not play back truncated "completed" output.
        error = None
        if grammar is not None:
            from omnia_tpu.engine.grammar.fsm import GrammarError

            try:
                grammar.validate(
                    1 << 30,  # host-side playback has no state budget
                    self.tokenizer.vocab_size,
                    params.stop_token_ids,
                )
            except GrammarError as e:
                error = f"grammar rejected: {e}"
        if error is None and not prompt_tokens:
            error = "empty prompt"
        if error is None and params.max_tokens < 1:
            error = f"max_tokens must be >= 1, got {params.max_tokens}"
        if error is not None:
            handle._push(
                StreamEvent(rid, finish_reason=FinishReason.ERROR, error=error)
            )
            return handle
        # Bounded admission / drain parity AFTER validation (the
        # engine's ordering: a bad request is ERROR even at a full
        # queue). Check-and-reserve in ONE critical section so
        # concurrent submits can never overshoot max_queue.
        with self._lock:
            if self._draining or (0 < self.max_queue <= self._live_plays):
                self.metrics["requests_shed"] += 1
                why = (
                    "engine draining (stop(drain=True))" if self._draining
                    else f"queue full (max_queue={self.max_queue})"
                )
            else:
                why = None
                self.metrics["requests_submitted"] += 1
                self._live_plays += 1
                self._live_prompt_tokens += len(prompt_tokens)
        if why is not None:
            handle._push(
                StreamEvent(rid, finish_reason=FinishReason.OVERLOADED, error=why)
            )
            return handle
        if self._flight is not None:
            # Before the playback thread starts, so submit-seq < claim-seq
            # in the ring (same ordering contract as the real engine).
            self._flight.note_submit(
                rid, len(prompt_tokens), trace_ctx, self.tracer
            )
        if grammar is not None:
            from omnia_tpu.engine.grammar.cache import stats

            with self._lock:
                self.metrics["grammar_compile_hits"] = stats["hits"]
                self.metrics["grammar_compile_misses"] = stats["misses"]
        deadline_at = (
            time.monotonic() + deadline_s if deadline_s is not None else None
        )
        thread = threading.Thread(
            target=self._play_guarded,
            args=(rid, list(prompt_tokens), params, handle, grammar,
                  deadline_at, session_id),
            daemon=True,
        )
        thread.start()
        return handle

    def generate(self, prompt_tokens, params=SamplingParams()):
        return self.submit(prompt_tokens, params).collect_tokens(timeout=30)

    def start(self):
        with self._lock:
            self._draining = False

    def stop(self, drain: bool = False, drain_timeout_s: float = 30.0):
        """Interface parity: drain stops admission (submit sheds
        OVERLOADED) and waits out live playbacks, bounded. The
        ``_draining`` flip happens under the lock: submit's
        check-and-reserve reads it in its critical section, so an
        unlocked write could admit a playback AFTER the drain decided
        the engine was idle (the books then disagree with the wait)."""
        if not drain:
            return
        with self._lock:
            self._draining = True
        deadline = time.monotonic() + drain_timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if self._live_plays == 0:
                    return
            time.sleep(0.002)

    def _scenario_for(self, prompt: str) -> Scenario:
        turn_view = _current_turn_view(prompt)
        for s in self.scenarios:
            if s.matches(prompt if s.match == "prompt" else turn_view):
                return s
        return Scenario(pattern=".*", reply=DEFAULT_REPLY)

    def _constrained_reply(self, reply_ids, params, grammar) -> list[int]:
        """Apply the SAME token masks the compiled engine path enforces:
        the scripted reply is the proposal stream (the mock's stand-in
        for argmax logits); a proposed token that the current FSM state
        masks is replaced by the grammar's completion move, and once the
        script is exhausted the walk is force-completed to an accepting
        state — so scripted garbage becomes schema-valid output, exactly
        what masked sampling does to a misbehaving model."""
        from omnia_tpu.engine.grammar.fsm import force_complete

        # Same view the compiled engine would mask with: the request's
        # stop ids are unmasked in accepting states (parity — a custom
        # stop id in a scripted reply must survive, not be rewritten).
        view = grammar.view(self.tokenizer.vocab_size, params.stop_token_ids)
        it = iter(reply_ids)

        def propose(_state, _allowed):
            return next(it, None)

        toks, _done = force_complete(view, propose, params.max_tokens)
        # Host-side masked-fraction mirror (parity with the engine's
        # metrics; one walk re-derives the per-step states).
        s = view.start
        with self._lock:
            for t in toks:
                self._gr_mask_sum += view.masked_fraction(s)
                self._gr_mask_steps += 1
                s = view.advance(s, t)
            if self._gr_mask_steps:
                self.metrics["masked_logit_fraction"] = round(
                    self._gr_mask_sum / self._gr_mask_steps, 6
                )
            if view.is_accepting(s):
                self.metrics["grammar_rejections_avoided"] += 1
        return toks

    def _play_guarded(self, rid, prompt_tokens, params, handle, grammar,
                      deadline_at, session_id=None):
        page_slot = self._page_mirror_begin(len(prompt_tokens))
        try:
            self._play(rid, prompt_tokens, params, handle, grammar,
                       deadline_at, session_id)
        finally:
            self._page_mirror_end(page_slot)
            with self._lock:
                self._live_plays -= 1
                self._live_prompt_tokens -= len(prompt_tokens)
                self._decode_rids.discard(rid)

    def _finish(self, handle, rid, reason, n_prompt, generated, error=None):
        """Push the terminal event and keep the books balanced: every
        accepted submit reaches exactly one finish count, whatever the
        reason (the documented requests_finished semantics)."""
        handle._push(
            StreamEvent(
                rid, finish_reason=reason, error=error,
                num_prompt_tokens=n_prompt, num_generated_tokens=generated,
            )
        )
        with self._lock:
            self.metrics["requests_finished"] += 1
        if self._flight is not None:
            self._flight.note_terminal(
                rid, reason.value, tokens=generated, error=error,
                first_token_at=handle.first_token_at,
            )

    def _play(self, rid, prompt_tokens, params, handle: RequestHandle,
              grammar=None, deadline_at=None, session_id=None):
        prompt = self.tokenizer.decode(prompt_tokens)
        scenario = self._scenario_for(prompt)
        fault = self.fault_plan
        n_prompt = len(prompt_tokens)
        if self._flight is not None:
            # Playback-thread start is the mock's "claim" seam: a mock
            # has a slot for everyone, so the whole wait was for the
            # thread to come round (loop_wait_s).
            self._flight.note_claim(rid, t_pass=time.monotonic())
        # Hung-dispatch parity: an injected hang past watchdog_s fails
        # the request at the watchdog bound (the engine's trip path),
        # never after the full hang — bounded client latency.
        hang = fault.take_hang_s() if fault is not None else 0.0
        if hang > 0.0 and self.watchdog_s is not None and hang > self.watchdog_s:
            time.sleep(self.watchdog_s)
            with self._lock:
                self.metrics["watchdog_trips"] += 1
            self._finish(
                handle, rid, FinishReason.ERROR, n_prompt, 0,
                error=f"dispatch hung > watchdog_s={self.watchdog_s}",
            )
            return
        t_enq = time.monotonic() if self._flight is not None else 0.0
        time.sleep(hang + scenario.ttft_s)
        if self._flight is not None:
            # The post-ttft-sleep moment is the mock's "placement": the
            # simulated prefill (prefill_s, all of it spent blocked) is
            # done, tokens stream next.
            self._flight.note_placement(
                rid, 0, n_prompt, t_enq=t_enq, t_read0=t_enq,
                t_read=time.monotonic(),
            )
        # Stall-free batching mirror: this is the playback's "prefill"
        # moment. With a token budget the prompt books ceil(n/budget)
        # mixed steps and its full token count (identical to the real
        # engine's per-piece metering); prefill-first instead counts a
        # decode stall whenever other playbacks are live to be stalled.
        # Placement also claims the decode-slot gauge (disagg).
        with self._lock:
            self._decode_rids.add(rid)
            if self.prefill_chunk_tokens > 0:
                self.metrics["mixed_steps"] += -(
                    -n_prompt // self.prefill_chunk_tokens
                )
                self.metrics["interleaved_prefill_tokens"] += n_prompt
            elif self._live_plays > 1:
                self.metrics["decode_stall_steps"] += 1
        if scenario.error is not None:
            # Scripted errors model DETERMINISTIC provider failures
            # (they would recur identically on any worker), so they keep
            # num_prompt_tokens=0 — the coordinator's resubmit
            # discriminator must not reclassify them as worker deaths
            # and replay the scenario on another worker. Only FaultPlan
            # deaths and watchdog trips carry the accepted-prompt marker.
            self._finish(
                handle, rid, FinishReason.ERROR, 0, 0, error=scenario.error,
            )
            return
        reply_ids = self.tokenizer.encode(scenario.reply, add_bos=False)
        if grammar is not None:
            reply_ids = self._constrained_reply(reply_ids, params, grammar)
        reply_ids = reply_ids[: params.max_tokens]
        # Worker-death injection: decided ONCE per playback so the
        # chaos suite's counts are exact; the request emits its first
        # die_after_tokens tokens and then the "worker" dies mid-stream
        # (0 = death before any token — the resubmittable case).
        die_after = (
            fault.die_after_tokens
            if fault is not None and fault.take_death()
            else None
        )
        # Every row the real engine would write (prompt prefill + each
        # decoded token) round-trips through the int8 scheme host-side.
        self._kv_roundtrip(prompt_tokens + reply_ids)
        self._spec_mirror(prompt_tokens, reply_ids, params)
        generated = 0
        if die_after == 0:
            self._finish(
                handle, rid, FinishReason.ERROR, n_prompt, 0,
                error="injected worker death (FaultPlan)",
            )
            return
        for tok in reply_ids:
            if handle.cancelled:
                self._finish(
                    handle, rid, FinishReason.CANCELLED, n_prompt, generated
                )
                return
            if deadline_at is not None and time.monotonic() >= deadline_at:
                with self._lock:
                    self.metrics["deadline_exceeded"] += 1
                self._finish(
                    handle, rid, FinishReason.DEADLINE, n_prompt, generated
                )
                return
            delay = scenario.delay_per_token_s
            if fault is not None:
                delay += fault.slow_sync_s
            if delay:
                time.sleep(delay)
            handle._push(StreamEvent(rid, token_id=tok))
            generated += 1
            with self._lock:
                self.metrics["tokens_generated"] += 1
            if die_after is not None and generated >= die_after:
                self._finish(
                    handle, rid, FinishReason.ERROR, n_prompt, generated,
                    error="injected worker death (FaultPlan)",
                )
                return
        reason = (
            FinishReason.LENGTH
            if len(reply_ids) >= params.max_tokens
            else FinishReason.STOP
        )
        if session_id is not None:
            # Completed sessionful turn: the prompt+reply stream is the
            # session's resident record (the migration payload).
            self._session_note(session_id, prompt_tokens + reply_ids)
        self._finish(handle, rid, reason, n_prompt, generated)
