"""Continuous-batching engine tests (tiny model, CPU)."""

import numpy as np
import pytest

from omnia_tpu.engine import (
    EngineConfig,
    FinishReason,
    InferenceEngine,
    MockEngine,
    SamplingParams,
)
from omnia_tpu.engine.mock import Scenario
from omnia_tpu.engine.tokenizer import ByteTokenizer, IncrementalDetokenizer
from omnia_tpu.models import get_config


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("test-tiny")
    ecfg = EngineConfig(
        num_slots=4, max_seq=64, prefill_buckets=(8, 16, 32), dtype="float32"
    )
    return InferenceEngine(cfg, ecfg, seed=0)


def test_generate_greedy_deterministic(engine):
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    toks1, fin1 = engine.generate([1, 2, 3, 4], sp)
    toks2, fin2 = engine.generate([1, 2, 3, 4], sp)
    assert toks1 == toks2
    assert len(toks1) == 8
    assert fin1.finish_reason == FinishReason.LENGTH
    assert fin1.num_prompt_tokens == 4
    assert fin1.num_generated_tokens == 8
    assert all(0 <= t < engine.model_cfg.vocab_size for t in toks1)


def test_seeded_sampling_reproducible(engine):
    sp = SamplingParams(temperature=1.0, top_p=0.9, top_k=40, max_tokens=6, seed=1234)
    toks1, _ = engine.generate([5, 6, 7], sp)
    toks2, _ = engine.generate([5, 6, 7], sp)
    assert toks1 == toks2
    assert len(toks1) == 6


def test_generation_independent_of_batch_mates(engine):
    """A seeded request must produce identical tokens whether it runs alone
    or concurrently with other requests — the continuous-batching isolation
    invariant."""
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    alone, _ = engine.generate([9, 8, 7], sp)

    handles = [
        engine.submit([9, 8, 7], sp),
        engine.submit([1, 1, 2, 2, 3, 3], SamplingParams(temperature=0.7, max_tokens=10, seed=7)),
        engine.submit([4, 4, 4], SamplingParams(temperature=0.0, max_tokens=4)),
    ]
    while engine.step():
        pass
    together, fin = handles[0].collect_tokens(timeout=5)
    assert fin.finish_reason == FinishReason.LENGTH
    assert together == alone


def test_stop_token(engine):
    sp0 = SamplingParams(temperature=0.0, max_tokens=5)
    free_run, _ = engine.generate([3, 1, 4, 1, 5], sp0)
    stop_tok = free_run[2]
    sp = SamplingParams(temperature=0.0, max_tokens=5, stop_token_ids=(stop_tok,))
    toks, fin = engine.generate([3, 1, 4, 1, 5], sp)
    assert fin.finish_reason == FinishReason.STOP
    assert toks == free_run[:2]
    assert stop_tok not in toks


def test_more_requests_than_slots(engine):
    sp = SamplingParams(temperature=0.0, max_tokens=3)
    handles = [engine.submit([i + 1, i + 2], sp) for i in range(9)]
    while engine.step():
        pass
    for h in handles:
        toks, fin = h.collect_tokens(timeout=5)
        assert len(toks) == 3
        assert fin.finish_reason == FinishReason.LENGTH


def test_prompt_too_long_rejected(engine):
    sp = SamplingParams(max_tokens=2)
    handle = engine.submit(list(range(200)), sp)
    ev = handle.get_event(timeout=5)
    assert ev.finish_reason == FinishReason.ERROR
    assert "exceeds" in ev.error


def test_empty_prompt_rejected(engine):
    ev = engine.submit([], SamplingParams()).get_event(timeout=5)
    assert ev.finish_reason == FinishReason.ERROR


def test_cancellation(engine):
    sp = SamplingParams(temperature=0.0, max_tokens=50)
    handle = engine.submit([2, 4, 6], sp)
    engine.step()  # prefill + first token
    handle.cancel()
    while engine.step():
        pass
    events = []
    while True:
        ev = handle.get_event(timeout=5)
        events.append(ev)
        if ev.is_final:
            break
    assert events[-1].finish_reason == FinishReason.CANCELLED


def test_queue_depth_signal(engine):
    sp = SamplingParams(temperature=0.0, max_tokens=2)
    handles = [engine.submit([1, 2], sp) for _ in range(6)]
    assert engine.queue_depth() == 6
    while engine.step():
        pass
    assert engine.queue_depth() == 0
    for h in handles:
        h.collect_tokens(timeout=5)


def test_engine_thread_mode(engine):
    engine.start()
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=4)
        toks, fin = engine.submit([1, 2, 3], sp).collect_tokens(timeout=60)
        assert len(toks) == 4
    finally:
        engine.stop()


def test_warmup_compiles_without_error(engine):
    engine.warmup()
    sp = SamplingParams(temperature=0.0, max_tokens=2)
    toks, _ = engine.generate([1, 2], sp)
    assert len(toks) == 2


class TestMockEngine:
    def test_scenario_playback(self):
        tok = ByteTokenizer()
        eng = MockEngine([Scenario(pattern="weather", reply="it is sunny")])
        toks, fin = eng.generate(tok.encode("what is the weather?"), SamplingParams(max_tokens=64))
        assert tok.decode(toks) == "it is sunny"
        assert fin.finish_reason == FinishReason.STOP

    def test_default_reply(self):
        tok = ByteTokenizer()
        eng = MockEngine()
        toks, _ = eng.generate(tok.encode("anything"), SamplingParams(max_tokens=64))
        assert tok.decode(toks) == "mock-reply"

    def test_error_scenario(self):
        tok = ByteTokenizer()
        eng = MockEngine([Scenario(pattern="boom", error="simulated failure")])
        handle = eng.submit(tok.encode("boom now"), SamplingParams())
        ev = handle.get_event(timeout=5)
        assert ev.finish_reason == FinishReason.ERROR
        assert ev.error == "simulated failure"

    def test_max_tokens_truncates(self):
        tok = ByteTokenizer()
        eng = MockEngine([Scenario(pattern=".", reply="0123456789")])
        toks, fin = eng.generate(tok.encode("x"), SamplingParams(max_tokens=4))
        assert tok.decode(toks) == "0123"
        assert fin.finish_reason == FinishReason.LENGTH


class TestTokenizer:
    def test_byte_roundtrip(self):
        tok = ByteTokenizer()
        ids = tok.encode("héllo ⚡", add_bos=True)
        assert ids[0] == tok.bos_id
        assert tok.decode(ids) == "héllo ⚡"

    def test_incremental_detokenizer_utf8_boundary(self):
        tok = ByteTokenizer()
        det = IncrementalDetokenizer(tok)
        ids = tok.encode("a⚡b", add_bos=False)  # ⚡ is 3 bytes
        out = "".join(det.push(i) for i in ids) + det.flush()
        assert out == "a⚡b"
        # no replacement chars were ever emitted mid-rune
        assert "�" not in out


def test_max_tokens_zero_rejected(engine):
    ev = engine.submit([1, 2], SamplingParams(max_tokens=0)).get_event(timeout=5)
    assert ev.finish_reason == FinishReason.ERROR
    assert "max_tokens" in ev.error


def test_prompt_past_largest_bucket_served_chunked():
    """Prompts longer than the largest prefill bucket are served via
    chunked prefill (bucket pieces + single-token tail near the cache end)
    instead of rejected; only KV capacity itself bounds prompt length."""
    cfg = get_config("test-tiny")
    eng = InferenceEngine(
        cfg,
        EngineConfig(num_slots=2, max_seq=20, prefill_buckets=(8, 16, 128), dtype="float32"),
        seed=0,
    )
    toks, fin = eng.generate(list(range(1, 18)), SamplingParams(temperature=0.0, max_tokens=1))
    assert len(toks) == 1 and fin.num_prompt_tokens == 17
    # KV capacity is the hard limit.
    ev = eng.submit(list(range(1, 20)), SamplingParams(max_tokens=1)).get_event(timeout=5)
    assert ev.finish_reason == FinishReason.ERROR
    assert "KV capacity" in ev.error
    toks, fin = eng.generate([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=2))
    assert len(toks) == 2 and fin.finish_reason == FinishReason.LENGTH


def test_recovery_reallocates_device_state(engine):
    """After a step failure (donated caches deleted), _recover must rebuild
    device state so the engine keeps serving."""
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    before, _ = engine.generate([6, 5, 4], sp)
    # Needs more tokens than one decode chunk so the slot is still active
    # after a step (chunked decode can finish a short request in one step).
    h = engine.submit([6, 5, 4], SamplingParams(temperature=0.0, max_tokens=40))
    engine.step()  # slot active mid-request
    engine._recover("injected failure")
    ev = h.get_event(timeout=5)
    # drain to the final event (first token may already be queued)
    while not ev.is_final:
        ev = h.get_event(timeout=5)
    assert ev.finish_reason == FinishReason.ERROR
    assert engine.healthy()
    assert engine.metrics["recoveries"] >= 1
    after, fin = engine.generate([6, 5, 4], sp)
    assert fin.finish_reason == FinishReason.LENGTH
    assert after == before  # greedy generation identical post-recovery


def test_warmup_is_behavior_neutral():
    """Unseeded sampled generation must not depend on whether warmup ran."""
    cfg = get_config("test-tiny")
    ecfg = EngineConfig(num_slots=2, max_seq=64, prefill_buckets=(8, 16), dtype="float32")
    sp = SamplingParams(temperature=1.0, max_tokens=5)  # no seed: slot stream
    e1 = InferenceEngine(cfg, ecfg, seed=3)
    t1, _ = e1.generate([1, 2, 3], sp)
    e2 = InferenceEngine(cfg, ecfg, seed=3)
    e2.warmup()
    t2, _ = e2.generate([1, 2, 3], sp)
    assert t1 == t2


def test_mock_rejects_like_real_engine():
    eng = MockEngine()
    ev = eng.submit([], SamplingParams()).get_event(timeout=5)
    assert ev.finish_reason == FinishReason.ERROR
    ev = eng.submit([1], SamplingParams(max_tokens=0)).get_event(timeout=5)
    assert ev.finish_reason == FinishReason.ERROR


def test_prefill_failure_reaches_handle(engine):
    """A prefill exception must deliver an ERROR final to that request's
    handle (it has no slot yet, so recovery's fail_all can't see it)."""
    sp = SamplingParams(temperature=0.0, max_tokens=2)
    orig = engine._prefill_insert_fn
    engine._prefill_insert_fn = (
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    try:
        h = engine.submit([1, 2], sp)
        with pytest.raises(RuntimeError):
            engine.step()
        ev = h.get_event(timeout=5)
        assert ev.finish_reason == FinishReason.ERROR
        assert "prefill" in ev.error
    finally:
        engine._prefill_insert_fn = orig
        engine._recover("test cleanup")
    toks, fin = engine.generate([1, 2], sp)
    assert len(toks) == 2


def test_chunked_decode_matches_per_token(engine):
    """decode_chunk must be behavior-invisible: greedy output identical
    between K=1 and K=8 engines."""
    cfg = get_config("test-tiny")
    sp = SamplingParams(temperature=0.0, max_tokens=11)
    e1 = InferenceEngine(
        cfg,
        EngineConfig(num_slots=2, max_seq=64, prefill_buckets=(8,),
                     dtype="float32", decode_chunk=1),
        seed=7,
    )
    e8 = InferenceEngine(
        cfg,
        EngineConfig(num_slots=2, max_seq=64, prefill_buckets=(8,),
                     dtype="float32", decode_chunk=8),
        seed=7,
    )
    t1, f1 = e1.generate([3, 1, 4], sp)
    t8, f8 = e8.generate([3, 1, 4], sp)
    assert t1 == t8
    assert f1.finish_reason == f8.finish_reason
    # seeded sampling too (per-slot PRNG must advance identically)
    sp2 = SamplingParams(temperature=1.0, max_tokens=9, seed=42)
    assert e1.generate([2, 7], sp2)[0] == e8.generate([2, 7], sp2)[0]


class TestDecodePipeline:
    """decode_pipeline must be behavior-invisible: only dispatch timing
    changes, never tokens."""

    def _mk(self, pipeline, **kw):
        cfg = get_config("test-tiny")
        return InferenceEngine(
            cfg,
            EngineConfig(
                num_slots=2, max_seq=64, prefill_buckets=(8,), dtype="float32",
                decode_chunk=4, decode_pipeline=pipeline, **kw,
            ),
            seed=11,
        )

    def test_pipelined_matches_sync(self):
        sp = SamplingParams(temperature=0.0, max_tokens=10)
        sync = self._mk(1)
        pipe = self._mk(2)
        assert sync.generate([3, 1, 4], sp)[0] == pipe.generate([3, 1, 4], sp)[0]
        sp2 = SamplingParams(temperature=1.0, max_tokens=9, seed=5)
        assert sync.generate([2, 7], sp2)[0] == pipe.generate([2, 7], sp2)[0]

    def test_pipelined_sessions_match_fresh(self):
        """Cross-turn prefix reuse under a pipelined engine still equals a
        fresh full-prompt generation."""
        sp = SamplingParams(temperature=0.0, max_tokens=5)
        pipe = self._mk(2)
        t1, _ = pipe.generate([1, 2, 3, 4, 5], sp)

        sess = self._mk(2)
        a, _ = sess.generate([1, 2, 3], sp)  # unrelated warm traffic
        h = sess.submit([1, 2, 3, 4, 5], sp, session_id="s1")
        while sess.step():
            pass
        got, fin = h.collect_tokens(timeout=5)
        assert got == t1
        # turn 2 extends the resident rows
        prompt2 = [1, 2, 3, 4, 5] + t1 + [9]
        fresh = self._mk(1)
        want, _ = fresh.generate(prompt2, sp)
        h2 = sess.submit(prompt2, sp, session_id="s1")
        while sess.step():
            pass
        got2, _ = h2.collect_tokens(timeout=5)
        assert got2 == want
        assert sess.metrics["prefix_reuse_tokens"] > 0

    def test_cancel_and_reuse_slot_mid_flight(self):
        """A slot freed by cancellation while a chunk is in flight must not
        leak the old request's tokens into its new occupant."""
        pipe = self._mk(2)
        sp_long = SamplingParams(temperature=0.0, max_tokens=40)
        h1 = pipe.submit([1, 2, 3], sp_long)
        h2 = pipe.submit([4, 5, 6], sp_long)
        for _ in range(3):
            pipe.step()
        h1.cancel()
        h2.cancel()
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        want, _ = self._mk(1).generate([7, 8, 9], sp)
        h3 = pipe.submit([7, 8, 9], sp)
        while pipe.step():
            pass
        got, fin = h3.collect_tokens(timeout=5)
        assert fin.finish_reason == FinishReason.LENGTH
        assert got == want

    def test_more_requests_than_slots_pipelined(self):
        pipe = self._mk(2)
        sp = SamplingParams(temperature=0.0, max_tokens=3)
        want = [self._mk(1).generate([i + 1, i + 2], sp)[0] for i in range(5)]
        handles = [pipe.submit([i + 1, i + 2], sp) for i in range(5)]
        while pipe.step():
            pass
        for h, w in zip(handles, want):
            got, fin = h.collect_tokens(timeout=5)
            assert fin.finish_reason == FinishReason.LENGTH
            assert got == w

    # More requests than slots, so most decode steps run with requests
    # waiting and no slot for them (the scheduler's blocked regime: the
    # next step is dispatched before the one in flight is read).
    _SATURATED = ((3, 9), (5, 5), (7, 12), (9, 7), (11, 3), (13, 10), (15, 6))

    def _run_saturated(self, eng, stop_id):
        """Seven requests on two slots: mixed ``max_tokens``, the third
        ends on a stop id mid-run, the last is cancelled while it waits.
        Returns ``[(tokens, reason, num_generated)]`` in submit order."""
        handles = []
        for i, (first, n) in enumerate(self._SATURATED):
            stops = (stop_id,) if i == 2 else ()
            handles.append(eng.submit(
                [first, first + 1, first + 2],
                SamplingParams(temperature=0.0, max_tokens=n,
                               stop_token_ids=stops),
            ))
        for _ in range(3):
            eng.step()
        handles[-1].cancel()  # still queued: five requests are ahead of it
        while eng.step():
            pass
        out = []
        for h in handles:
            toks, fin = h.collect_tokens(timeout=5)
            out.append((toks, fin.finish_reason, fin.num_generated_tokens))
        return out

    def test_saturated_pipelined_matches_sync(self):
        # Each request alone on a fresh engine: what its stream must be,
        # whoever held its slot before it.
        alone = [
            self._mk(1).generate(
                [first, first + 1, first + 2],
                SamplingParams(temperature=0.0, max_tokens=n))[0]
            for first, n in self._SATURATED
        ]
        stop_id = alone[2][4]
        assert stop_id not in alone[2][:4]
        want = [(t, FinishReason.LENGTH, len(t)) for t in alone]
        want[2] = (alone[2][:4], FinishReason.STOP, 4)
        want[-1] = ([], FinishReason.CANCELLED, 0)
        for pipeline in (1, 2):
            eng = self._mk(pipeline)
            try:
                assert self._run_saturated(eng, stop_id) == want, pipeline
                m = eng.metrics
                # The mechanism engaged, and only ever with one-step programs.
                assert 0 < m["decode_dispatches_blocked"] <= m["decode_dispatches_single"]
                assert m["requests_finished"] == len(want)
            finally:
                eng.stop()

    def test_blocked_turn_waits_for_its_own_slot_and_evicts_nobody(self):
        """A session's next turn arrives while its previous turn still
        decodes: its own slot is the only one it may take, so it is blocked
        even though an idle session's slot could be had by eviction. The
        predicate runs every step with a chunk in flight and offloads
        nothing; the turn lands on its own slot and reuses its rows."""
        sp1 = SamplingParams(temperature=0.0, max_tokens=12)
        sp2 = SamplingParams(temperature=0.0, max_tokens=5)
        p1 = [1, 2, 3, 4, 5]
        t1, _ = self._mk(1).generate(p1, sp1)
        p2 = p1 + t1 + [9]
        want2, _ = self._mk(1).generate(p2, sp2)

        eng = self._mk(2, max_sessions=4)
        m = eng.metrics
        idle = eng.submit([6, 7, 8], sp2, session_id="idle")
        while eng.step():
            pass
        assert idle.collect_tokens(timeout=5)[1].finish_reason == FinishReason.LENGTH
        idle_slot = eng._sessions["idle"].slot
        assert idle_slot is not None and not eng._slots[idle_slot].active

        h1 = eng.submit(p1, sp1, session_id="s")
        eng.step()
        own = eng._sessions["s"].slot
        assert own is not None and own != idle_slot
        h2 = eng.submit(p2, sp2, session_id="s")
        blocked_steps = 0
        while eng._slots[own].active and eng._slots[own].request.request_id == h1.request_id:
            assert eng._choose_slot(eng._waiting[0][0]) == (None, False)
            eng.step()
            blocked_steps += bool(eng._inflight)
            assert m["session_offloads"] == 0
            assert eng._sessions["idle"].slot == idle_slot
        assert blocked_steps > 2 and m["decode_dispatches_blocked"] > 2
        reuse0 = m["prefix_reuse_tokens"]
        while eng.step():
            pass
        got1, _ = h1.collect_tokens(timeout=5)
        got2, fin2 = h2.collect_tokens(timeout=5)
        assert got1 == t1 and got2 == want2
        assert fin2.finish_reason == FinishReason.LENGTH
        assert eng._sessions["s"].slot == own
        # Every valid row of turn 1 (its last token's row is not trusted).
        assert m["prefix_reuse_tokens"] - reuse0 == len(p1) + len(t1) - 1
        assert m["session_offloads"] == 0

    def test_choosing_a_slot_is_pure_and_the_claim_evicts(self):
        """``_choose_slot`` names the idle session's slot and says that it
        costs an eviction; only ``_slot_for`` pays it."""
        sp = SamplingParams(temperature=0.0, max_tokens=20)
        eng = self._mk(2, max_sessions=4)
        m = eng.metrics
        eng.submit([6, 7, 8], SamplingParams(temperature=0.0, max_tokens=3),
                   session_id="idle")
        while eng.step():
            pass
        idle_slot = eng._sessions["idle"].slot
        busy = eng.submit([1, 2, 3], sp)
        eng.step()
        waiter = eng.submit([4, 5, 6], SamplingParams(temperature=0.0, max_tokens=3))
        request = eng._waiting[0][0]
        assert eng._choose_slot(request) == (idle_slot, True)
        assert eng._queued_placeable() == (True, True)
        assert m["session_offloads"] == 0 and eng._sessions["idle"].slot == idle_slot
        eng.step()  # placeable: flush, claim (evicts), place
        assert m["session_offloads"] == 1 and eng._sessions["idle"].slot is None
        assert eng._slots[idle_slot].request.request_id == waiter.request_id
        assert m["pipeline_flushes"] == 1 and m["decode_dispatches_blocked"] == 0
        busy.cancel()
        while eng.step():
            pass
        assert len(waiter.collect_tokens(timeout=5)[0]) == 3


def test_decode_kernel_skips_freed_slots_and_counts_its_blocks(monkeypatch):
    """Through the (interpreted) decode kernel: a request's greedy tokens
    are the same when every row of the slots freed (or never used) around
    it is NaN while it decodes — the kernel reads a slot only while it is
    live — and ``decode_kv_blocks`` advances by the 256-row blocks the
    live contexts span, a step."""
    import jax.numpy as jnp

    from omnia_tpu.ops import attention as attn

    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "interpret")
    attn._pallas_decode_mode.cache_clear()
    try:
        eng = InferenceEngine(
            get_config("test-tiny", max_seq_len=512),
            EngineConfig(
                num_slots=3, max_seq=512, prefill_buckets=(8, 264),
                dtype="float32", decode_chunk=4,
            ),
            seed=3,
        )
        m = eng.metrics
        long_prompt = [1 + i % 50 for i in range(260)]  # two blocks of 256
        sp_long = SamplingParams(temperature=0.0, max_tokens=13)
        sp_short = SamplingParams(temperature=0.0, max_tokens=3)

        def delta(run):
            before = {k: m[k] for k in ("decode_steps", "decode_slot_steps",
                                        "decode_kv_blocks")}
            out = run()
            return out, {k: m[k] - v for k, v in before.items()}

        (alone, _), d = delta(lambda: eng.generate(long_prompt, sp_long))
        assert d["decode_steps"] >= 12
        assert d["decode_kv_blocks"] == 2 * d["decode_steps"]
        _, d = delta(lambda: eng.generate([7, 8, 9], sp_short))
        assert 0 < d["decode_kv_blocks"] == d["decode_steps"]

        def poisoned():
            h_long = eng.submit(long_prompt, sp_long)
            h_short = eng.submit([7, 8, 9], sp_short)
            poisoned_slots = 0
            while eng.step():
                free = [i for i, s in enumerate(eng._slots) if not s.active]
                if len(free) == 2 and not poisoned_slots:
                    # The short request has ended: its slot and the one
                    # never used are dead to the kernel from here on.
                    eng._flush_pipeline()
                    for name in ("_ck", "_cv"):
                        cache = getattr(eng, name)
                        setattr(eng, name,
                                cache.at[:, jnp.asarray(free)].set(jnp.nan))
                    poisoned_slots = len(free)
            assert poisoned_slots == 2
            assert h_short.collect_tokens(timeout=5)[1].finish_reason == \
                FinishReason.LENGTH
            return h_long.collect_tokens(timeout=5)[0]

        together, d = delta(poisoned)
        assert together == alone
        # Two blocks a step for the long request, live at every dispatch,
        # and one for the short one while it lived.
        assert d["decode_slot_steps"] > d["decode_steps"]
        assert d["decode_kv_blocks"] == d["decode_slot_steps"] + d["decode_steps"]
    finally:
        attn._pallas_decode_mode.cache_clear()


# ---------------------------------------------------------------------------
# A placement is enqueues only: the slot's device state is one program call,
# and the first token is an entry of the pipeline, read after the decode step
# that follows the prefill has been dispatched.
# ---------------------------------------------------------------------------


def _placement_engine(eager: bool = False, **over) -> InferenceEngine:
    base = dict(num_slots=3, max_seq=64, prefill_buckets=(8, 16),
                dtype="float32", max_sessions=0, decode_chunk=4,
                decode_pipeline=2)
    base.update(over)
    eng = InferenceEngine(get_config("test-tiny"), EngineConfig(**base), seed=5)
    if eager:
        # The same engine made to read at once: the branch a grammar or
        # speculation takes, for every request.
        eng._defers_first_token = lambda request: False
    return eng


def _events(handle):
    """A finished request's events, in order: (token, reason, generated)."""
    out = []
    while True:
        ev = handle.get_event(timeout=5)
        out.append((ev.token_id, ev.finish_reason, ev.num_generated_tokens))
        if ev.is_final:
            return out


def _drive(eng):
    while eng.step():
        pass


def _script_mix(eng):
    """Eleven requests over three slots, lengths and parameters drawn from
    a fixed seed: greedy and seeded-sampling, stop ids, two arrivals."""
    rng = np.random.RandomState(20260929)
    free_run, _ = eng.generate([3, 1, 4], SamplingParams(temperature=0.0, max_tokens=6))
    handles = []

    def submit():
        n = int(rng.randint(2, 15))
        prompt = [int(t) for t in rng.randint(1, 200, size=n)]
        if rng.rand() < 0.4:
            sp = SamplingParams(temperature=0.8, top_p=0.9, top_k=20,
                                max_tokens=int(rng.randint(1, 9)),
                                seed=int(rng.randint(1, 1000)))
        else:
            stops = (free_run[int(rng.randint(0, 6))],) if rng.rand() < 0.5 else ()
            sp = SamplingParams(temperature=0.0, stop_token_ids=stops,
                                max_tokens=int(rng.randint(1, 9)))
        handles.append(eng.submit(prompt, sp))

    for _ in range(7):
        submit()
    for _ in range(3):
        eng.step()
    for _ in range(4):
        submit()
    _drive(eng)
    return handles


def _script_first_token_is_a_stop_id(eng):
    prompt = [3, 1, 4, 1, 5]
    first = eng.generate(prompt, SamplingParams(temperature=0.0, max_tokens=1))[0][0]
    live = eng.submit([9, 9, 8], SamplingParams(temperature=0.0, max_tokens=6))
    eng.step()
    ends_at_once = eng.submit(prompt, SamplingParams(
        temperature=0.0, max_tokens=5, stop_token_ids=(first,)))
    _drive(eng)
    return [live, ends_at_once]


def _script_one_token(eng):
    live = eng.submit([9, 9, 8], SamplingParams(temperature=0.0, max_tokens=6))
    eng.step()
    one = eng.submit([2, 7, 1, 8], SamplingParams(temperature=0.0, max_tokens=1))
    alone = eng.submit([6, 6], SamplingParams(temperature=0.0, max_tokens=1))
    _drive(eng)
    return [live, one, alone]


def _script_cancel_before_the_read(eng):
    h = eng.submit([2, 4, 6], SamplingParams(temperature=0.0, max_tokens=20))
    eng.step()  # placed; a pipeline of three leaves the first token unread
    h.cancel()
    _drive(eng)
    return [h]


def _script_back_to_back(eng):
    sp = SamplingParams(temperature=0.0, max_tokens=7)
    handles = [eng.submit([i + 1, i + 2, i + 3], sp) for i in range(3)]
    _drive(eng)
    return handles


@pytest.mark.parametrize("script, over", [
    (_script_mix, {}),
    (_script_mix, {"decode_pipeline": 1}),
    (_script_mix, {"prefill_chunk_tokens": 8}),
    (_script_first_token_is_a_stop_id, {}),
    (_script_one_token, {}),
    (_script_cancel_before_the_read, {"decode_pipeline": 3}),
    (_script_back_to_back, {}),
], ids=["mix", "mix-unpipelined", "mix-interleaved", "first-is-stop",
        "one-token", "cancel-before-read", "back-to-back"])
def test_deferred_first_token_streams_equal_the_eager_ones(script, over):
    """Token for token and terminal for terminal, a request's events are
    those of the same engine made to read every first token at once."""
    deferred = _placement_engine(**over)
    eager = _placement_engine(eager=True, **over)
    got = [_events(h) for h in script(deferred)]
    want = [_events(h) for h in script(eager)]
    assert got == want
    assert all(evs[-1][1] is not None for evs in got)
    m = deferred.metrics
    assert m["placements_deferred"] == m["prefill_steps"] > 0
    assert eager.metrics["placements_deferred"] == 0
    assert m["requests_finished"] == m["requests_submitted"]
    assert not deferred._inflight and not any(s.active for s in deferred._slots)


def test_a_cancel_between_placement_and_read_still_gets_its_first_token():
    eng = _placement_engine(decode_pipeline=3)
    h = eng.submit([2, 4, 6], SamplingParams(temperature=0.0, max_tokens=20))
    eng.step()
    # Placed, one chunk dispatched behind the first token, nothing read.
    assert [ch.placement is not None for ch in eng._inflight] == [True, False]
    assert h._queue.empty() and eng._slots[0].active
    h.cancel()
    eng.step()
    evs = _events(h)
    assert [e[0] is not None for e in evs] == [True, False]
    assert evs[-1][1:] == (FinishReason.CANCELLED, 1)
    _drive(eng)
    assert not eng._inflight


def _grammar_request(eng):
    from omnia_tpu.engine.grammar import compile_json_schema

    g = compile_json_schema(
        {"type": "object", "properties": {"a": {"type": "integer"}},
         "required": ["a"]}, ByteTokenizer())
    sp = SamplingParams(temperature=0.0, max_tokens=24, stop_token_ids=(0,))
    return eng.submit(ByteTokenizer().encode("json"), sp, grammar=g)


@pytest.mark.parametrize("over, submit, deferred", [
    ({}, None, 2),
    ({"grammar": True, "grammar_max_states": 256}, None, 2),
    ({"grammar": True, "grammar_max_states": 256}, _grammar_request, 0),
    ({"spec_decode": 3}, None, 0),
], ids=["plain", "grammar-engine-plain-request", "grammar-request", "spec-decode"])
def test_placements_deferred_counts_the_placements_that_read_nothing(
        over, submit, deferred):
    """One a placement whose first token went on the pipeline: all of
    them for plain requests, none under a grammar or speculation."""
    eng = _placement_engine(prefill_buckets=(8, 16, 32), **over)
    sp = SamplingParams(temperature=0.0, max_tokens=5)
    handles = [
        submit(eng) if submit else eng.submit([i + 1, i + 2, i + 3], sp)
        for i in range(2)
    ]
    _drive(eng)
    for h in handles:
        toks, fin = h.collect_tokens(timeout=5)
        assert toks and fin.finish_reason in (FinishReason.LENGTH, FinishReason.STOP)
    m = eng.metrics
    assert m["prefill_steps"] == 2
    assert m["placements_deferred"] == deferred


@pytest.mark.parametrize("path", ["fresh", "extend", "interleaved"])
def test_a_placement_and_a_finish_are_one_program_call_each(path, monkeypatch):
    """The slot's device state is written by ``activate_slot`` at
    placement and ``release_slot`` at the finish, once each, and by no
    ``.at[].set`` from the engine thread (the interleaved path parks the
    placing slot's position once a piece, before its dispatch: not part
    of the activation)."""
    from jax._src.numpy import array_methods

    over = {"prefill_chunk_tokens": 8} if path == "interleaved" else {}
    eng = _placement_engine(num_slots=2, **over)
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    prompt = list(range(1, 21)) if path != "fresh" else [5, 6, 7]
    eng.generate([1, 2, 3], sp)  # every program compiled
    calls = {"activate": 0, "release": 0, "at": 0}

    def counted(name, fn):
        def call(*a, **k):
            calls[name] += 1
            return fn(*a, **k)
        return call

    eng._activate_slot_fn = counted("activate", eng._activate_slot_fn)
    eng._release_slot_fn = counted("release", eng._release_slot_fn)
    for method in ("set", "add", "get"):
        monkeypatch.setattr(
            array_methods._IndexUpdateRef, method,
            counted("at", getattr(array_methods._IndexUpdateRef, method)))
    if path == "interleaved":
        # A live slot, so that the placement is split into mixed steps.
        eng.submit([9, 9], SamplingParams(temperature=0.0, max_tokens=40))
        eng.step()
        calls.update(activate=0, release=0, at=0)
    h = eng.submit(prompt, sp)
    while not h._queue.qsize():
        eng.step()
    pieces = eng.metrics["mixed_steps"] if path == "interleaved" else 0
    assert (calls["activate"], calls["release"]) == (1, 0)
    assert calls["at"] == pieces
    while any(s.active and s.request.request_id == h.request_id
              for s in eng._slots):
        eng.step()
    assert h.collect_tokens(timeout=5)[1].finish_reason == FinishReason.LENGTH
    assert (calls["activate"], calls["release"]) == (1, 1)
    assert calls["at"] == pieces


def test_the_first_token_read_is_under_the_watchdog():
    """The read goes through ``_sync_chunk_host``: a hang injected there
    (engine/faults.py) trips the watchdog at the first token, which a
    read inside the placement never could."""
    from omnia_tpu.engine.faults import FaultPlan, WatchdogTimeout

    eng = _placement_engine(watchdog_s=0.1)
    eng.generate([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=2))
    eng._fault_plan = FaultPlan(hang_dispatch_s=0.6, hang_count=1)
    try:
        h = eng.submit([4, 5, 6], SamplingParams(temperature=0.0, max_tokens=4))
        tokens0 = eng.metrics["tokens_generated"]
        with pytest.raises(WatchdogTimeout):
            eng.step()
        # Tripped at the first entry of the pipeline: placed, the step
        # behind it dispatched and still in flight, no token out.
        assert eng._fault_plan.fired["hangs"] == 1
        assert eng.metrics["watchdog_trips"] == 1
        assert eng.metrics["tokens_generated"] == tokens0
        assert [ch.placement for ch in eng._inflight] == [None]
        assert eng._slots[0].active and h._queue.empty()
        eng._recover("hung first-token read")
        evs = _events(h)
        assert evs == [(None, FinishReason.ERROR, 0)]
        toks, fin = eng.generate([4, 5, 6], SamplingParams(temperature=0.0, max_tokens=4))
        assert len(toks) == 4 and fin.finish_reason == FinishReason.LENGTH
    finally:
        eng.stop()


def test_a_prefill_error_surfaces_at_the_read_and_reaches_the_handle():
    """Outside the placement's own error surface the request sits in its
    slot, so recovery's ``_fail_all`` ends it."""
    eng = _placement_engine()
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    eng.generate([1, 2, 3], sp)
    orig = eng._sync_chunk_host

    def failing(toks):
        eng._sync_chunk_host = orig
        raise RuntimeError("device error in the prefill")

    eng._sync_chunk_host = failing
    h = eng.submit([4, 5, 6], sp)
    with pytest.raises(RuntimeError, match="device error"):
        eng.step()
    assert eng._slots[0].active and h._queue.empty()
    eng._recover("engine step failed")
    (ev,) = _events(h)
    assert ev[1] == FinishReason.ERROR
    assert eng.metrics["requests_finished"] == eng.metrics["requests_submitted"]
    assert len(eng.generate([4, 5, 6], sp)[0]) == 4
