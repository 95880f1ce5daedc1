"""Speculative-decoding suite (EngineConfig.spec_decode).

Two halves, one marker (``spec``, tier-1):

- **Controllers** (jax-free): the bounded ``_NgramIndex``, the shared
  per-slot depth policy (``spec_depth_update``), the ``_SpecGate``
  duty-cycle self-gate, and the MockEngine mirror — this subset runs in
  the CI analysis job with no jax installed (module-level imports stay
  jax-free; engine-backed cases importorskip jax).
- **Equivalence battery**: the verify path must be TOKEN-IDENTICAL to
  vanilla (masked) greedy decode while spending measurably fewer weight
  streams on repetitive context — across sampled co-tenants, grammar
  constraints, int8 KV, token-budget interleaving, and mid-stream
  deadline/cancel with exact partial ledgers.
"""

from __future__ import annotations

import pytest

import omnia_tpu.engine.spec_decode as sd
from omnia_tpu.engine.spec_decode import (
    _NgramIndex,
    _SpecGate,
    spec_depth_update,
    validate_spec_config,
)

pytestmark = pytest.mark.spec


# ---------------------------------------------------------------------------
# Bounded n-gram index (jax-free)
# ---------------------------------------------------------------------------


class TestNgramIndex:
    def test_proposes_most_recent_continuation(self):
        idx = _NgramIndex()
        prop, real = idx.propose([5, 6, 7, 8, 5, 6], 3)
        assert (prop, real) == ([7, 8, 5], 3)

    def test_miss_returns_zero_real(self):
        idx = _NgramIndex()
        prop, real = idx.propose([1, 2, 3, 4, 5], 4)
        assert real == 0 and prop == [0, 0, 0, 0]

    def test_incremental_appends_only(self):
        idx = _NgramIndex()
        ctx = [1, 2, 3]
        idx.propose(ctx, 2)
        built = dict(idx.built)
        ctx += [1, 2]
        prop, real = idx.propose(ctx, 2)
        assert real == 2 and prop == [3, 1]
        assert all(idx.built[n] >= built[n] for n in idx.built)

    def test_cap_bounds_entries_with_fifo_eviction(self, monkeypatch):
        monkeypatch.setattr(sd, "_NGRAM_CAP", 8)
        idx = _NgramIndex()
        ctx = list(range(100))  # all-distinct grams: every insert is new
        idx.propose(ctx, 4)
        assert all(len(m) <= 8 for m in idx.maps.values())
        assert idx.entries() <= 8 * sd._NGRAM_MAX
        # The RECENT context stays indexed (eviction drops the oldest;
        # the tail gram itself is the query and is never inserted).
        assert (98,) in idx.maps[1]
        assert (0,) not in idx.maps[1]

    def test_entries_counts_all_orders(self):
        idx = _NgramIndex()
        idx.propose([1, 2, 1, 2, 1], 2)
        assert idx.entries() == sum(len(m) for m in idx.maps.values())

    def test_recurring_grams_survive_eviction(self, monkeypatch):
        """Eviction is least-recently-INGESTED: a gram that keeps
        recurring re-inserts at the back of the order and outlives
        cold grams — the hot prompt grams are exactly the hits."""
        monkeypatch.setattr(sd, "_NGRAM_CAP", 8)
        idx = _NgramIndex()
        ctx = [42] + list(range(100)) + [42, 43]
        idx.propose(ctx, 4)
        assert (42,) in idx.maps[1]      # re-seen late: survived
        assert (0,) not in idx.maps[1]   # seen once, early: evicted
        assert idx.maps[1][(42,)] == 101  # and points at the LATEST spot


# ---------------------------------------------------------------------------
# Per-slot depth policy (jax-free)
# ---------------------------------------------------------------------------


class TestDepthPolicy:
    def test_full_accepts_grow_to_kmax(self):
        ema, k = 0.5, 4
        for _ in range(20):
            ema, k = spec_depth_update(ema, k or 1, k or 1, kmax=8)
        assert k == 8 and ema > 0.95

    def test_rejects_collapse_to_zero(self):
        ema, k = 1.0, 8
        seen = [k]
        for _ in range(30):
            ema, k = spec_depth_update(ema, max(k, 1), 0, kmax=8)
            seen.append(k)
        assert k == 0 and seen[0] > seen[len(seen) // 4] >= k

    def test_fixed_mode_tracks_ema_only(self):
        ema, k = spec_depth_update(0.0, 4, 4, kmax=0)
        assert k == 0 and ema > 0.0  # caller pins depth in fixed mode

    def test_config_validation(self):
        from omnia_tpu.engine.types import EngineConfig

        validate_spec_config(EngineConfig())  # off: dead knobs unvalidated
        with pytest.raises(ValueError, match="spec_decode_max"):
            validate_spec_config(EngineConfig(
                prefill_buckets=(32,), spec_decode=4, spec_decode_max=2))
        with pytest.raises(ValueError, match="spec window"):
            validate_spec_config(EngineConfig(
                prefill_buckets=(8,), spec_decode=4, spec_decode_max=16))
        with pytest.raises(ValueError, match="spec_gate_window"):
            validate_spec_config(EngineConfig(
                prefill_buckets=(32,), spec_decode=4, spec_gate_window=-1))


# ---------------------------------------------------------------------------
# Online self-gate (jax-free)
# ---------------------------------------------------------------------------


def _drive_gate(gate, phases):
    """Feed (rate tokens/s per tick-second) per phase; returns the
    permitted-flag history. One tick per simulated second."""
    t, toks, out = 0.0, 0, []
    for rate, ticks in phases:
        for _ in range(ticks):
            t += 1.0
            toks += rate
            out.append(gate.tick(t, toks))
    return out


class TestSpecGate:
    def test_window_zero_always_allows(self):
        g = _SpecGate(0)
        assert all(_drive_gate(g, [(1, 50)])) and g.state_code() == 0

    def test_slow_spec_disables_and_reports(self):
        g = _SpecGate(10)
        # Spec probe realizes 10 tok/s, plain probe 30 → disable.
        _drive_gate(g, [(10, 10), (30, 10)])
        assert g.state == _SpecGate.HOLD_OFF and not g.allows_spec()
        assert g.state_code() == 2 and g.disables == 1
        rep = g.report()
        assert rep["state"] == "off"
        assert rep["rate_plain_tok_s"] > rep["rate_spec_tok_s"]

    def test_fast_spec_stays_on(self):
        g = _SpecGate(10)
        _drive_gate(g, [(30, 10), (10, 10)])
        assert g.state == _SpecGate.HOLD_ON and g.allows_spec()
        assert g.state_code() == 1 and g.disables == 0

    def test_hold_expires_into_reprobe(self):
        g = _SpecGate(4, hold_factor=2)
        _drive_gate(g, [(1, 4), (9, 4)])   # decide: off
        assert g.state == _SpecGate.HOLD_OFF
        _drive_gate(g, [(9, 8)])           # hold (2×4 ticks) expires
        assert g.state == _SpecGate.PROBE_SPEC  # re-probing: spec allowed
        assert g.allows_spec() and g.decisions == 1


# ---------------------------------------------------------------------------
# MockEngine mirror (jax-free)
# ---------------------------------------------------------------------------


class TestMockMirror:
    def test_greedy_playback_books_spec_ledger(self):
        from omnia_tpu.engine.mock import MockEngine, Scenario
        from omnia_tpu.engine.types import SamplingParams

        # Gate off for the ledger assertions: probe phases are wall-
        # clock driven, so a gated mirror could legitimately spend the
        # whole short script in a suppressed window.
        m = MockEngine(
            [Scenario("hi", "ab ab ab ab ab ab ab ab")],
            spec_decode=3, spec_decode_max=6,
        )
        toks, fin = m.generate(
            m.tokenizer.encode("hi"), SamplingParams(temperature=0.0,
                                                     max_tokens=64)
        )
        # Scripted output EXACTLY unchanged by the mirror.
        assert m.tokenizer.decode(toks) == "ab ab ab ab ab ab ab ab"
        assert m.metrics["spec_steps"] > 0
        assert m.metrics["spec_accepted"] > 0
        assert 0.0 < m.metrics["spec_accept_ema"] <= 1.0
        assert m.metrics["spec_index_bytes"] > 0
        assert m.metrics["spec_gate_state"] in (0, 1, 2)

    def test_sampled_playback_never_engages_mirror(self):
        from omnia_tpu.engine.mock import MockEngine, Scenario
        from omnia_tpu.engine.types import SamplingParams

        m = MockEngine([Scenario("hi", "ab ab ab ab")], spec_decode=3)
        m.generate(m.tokenizer.encode("hi"),
                   SamplingParams(temperature=0.7, max_tokens=64))
        assert m.metrics["spec_steps"] == 0


# ---------------------------------------------------------------------------
# Engine-backed equivalence battery (importorskips jax)
# ---------------------------------------------------------------------------


def _engine(spec: int, params=None, **over):
    pytest.importorskip("jax")
    from omnia_tpu.engine import EngineConfig, InferenceEngine
    from omnia_tpu.models import get_config

    kw = dict(num_slots=2, max_seq=128, prefill_buckets=(16,),
              dtype="float32", decode_chunk=4, max_sessions=4,
              spec_decode=spec)
    kw.update(over)
    eng = InferenceEngine(
        get_config("test-tiny"), EngineConfig(**kw), params=params, seed=0
    )
    eng.warmup()
    return eng


def _sp(**kw):
    from omnia_tpu.engine import SamplingParams

    return SamplingParams(**kw)


GREEDY = dict(temperature=0.0, max_tokens=24)
# A prompt with strong n-gram repetition (the prompt-lookup sweet spot).
REPETITIVE = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6, 7, 8, 5, 6]
PLAIN = [9, 3, 14, 2, 7]


def eng_drive(eng, handle, max_steps=3000):
    """Drive steps inline until the handle has its final event queued."""
    for _ in range(max_steps):
        eng.step()
        if handle._queue.qsize() and any(
            ev.is_final for ev in list(handle._queue.queue)
        ):
            return
    raise AssertionError("request did not finish")


@pytest.mark.parametrize("prompt", [REPETITIVE, PLAIN])
def test_spec_greedy_identical_to_vanilla(prompt):
    """Same model, same prompt, greedy: spec decode must emit exactly
    the tokens vanilla decode emits (acceptance is lossless)."""
    vanilla = _engine(0)
    toks_ref, fin_ref = vanilla.generate(prompt, _sp(**GREEDY))
    spec = _engine(4)
    toks, fin = spec.generate(prompt, _sp(**GREEDY))
    assert toks == toks_ref, (toks, toks_ref)
    assert fin.finish_reason == fin_ref.finish_reason
    assert spec.metrics["spec_steps"] > 0, "spec path never engaged"


def test_spec_spends_fewer_weight_streams_on_repetition():
    """The roofline claim: tokens per weight stream must clearly beat 1
    once generation turns repetitive (greedy decode of the tiny model
    settles into a loop the n-gram lookup predicts)."""
    eng = _engine(4)
    toks, _fin = eng.generate(
        REPETITIVE, _sp(temperature=0.0, max_tokens=100))
    steps = eng.metrics["spec_steps"] + eng.metrics["decode_steps"]
    assert len(toks) == 100
    assert eng.metrics["spec_accepted"] > 0
    tokens_per_stream = len(toks) / steps
    assert tokens_per_stream > 1.4, (
        f"{tokens_per_stream:.2f} tok/stream — speculation isn't paying")


def test_adaptive_depth_stays_identical_and_accepts():
    """spec_decode_max lets depth follow the accept EMA; output must
    stay token-identical to vanilla while the ledger shows adaptation
    (accepts observed, engine-wide EMA moved, index bounded). The echo
    model + echo prompt make every proposal right by construction."""
    pytest.importorskip("jax")
    from echomodel import ECHO, echo_params

    params = echo_params()
    sp = _sp(temperature=0.0, max_tokens=100)
    ref, _ = _engine(0, params=params).generate(ECHO, sp)
    assert ref[:4] == [3, 4, 5, 6]  # the cycle, continued from the prompt
    eng = _engine(2, params=params, spec_decode_max=8)
    handle = eng.submit(ECHO, sp)
    index_bytes_peak = 0
    while eng.step():
        # A gauge over LIVE slots: a stream that finishes inside a
        # verify step leaves it at 0, so watch it while the stream runs.
        index_bytes_peak = max(
            index_bytes_peak, eng.metrics["spec_index_bytes"]
        )
    toks, _ = handle.collect_tokens(timeout=60)
    assert toks == ref
    assert eng.metrics["spec_accepted"] > 0
    assert eng.metrics["spec_accept_ema"] > 0.0
    assert 0 < index_bytes_peak <= (
        sd._ENTRY_BYTES * sd._NGRAM_CAP * sd._NGRAM_MAX
    )
    # Deep windows engaged: some step accepted more than the base depth
    # would ever allow (depth grew past spec_decode=2).
    assert eng.metrics["spec_proposed"] > 2 * eng.metrics["spec_steps"] or (
        eng.metrics["spec_accepted"] / max(eng.metrics["spec_steps"], 1) > 2
    )


def test_sampled_and_greedy_coexist_per_slot():
    """A sampled request in the batch no longer suspends speculation:
    the greedy slot verifies while the sampled slot rides the EXACT
    chunked sampling path fused into the same dispatch — and sampled
    output stays seed-reproducible bit-for-bit."""
    eng = _engine(4)
    eng.start()
    try:
        sampled = _sp(temperature=0.8, top_p=0.9, max_tokens=10, seed=7)
        h1 = eng.submit(PLAIN, sampled)
        h2 = eng.submit(REPETITIVE, _sp(**GREEDY))
        t1, _ = h1.collect_tokens(timeout=120)
        t2, _ = h2.collect_tokens(timeout=120)
        assert len(t1) == 10 and len(t2) == 24
    finally:
        eng.stop()
    ref = _engine(0)
    t1_ref, _ = ref.generate(PLAIN, sampled)
    assert t1 == t1_ref, "sampled reproducibility broken by spec"
    t2_ref, _ = _engine(0).generate(REPETITIVE, _sp(**GREEDY))
    assert t2 == t2_ref, "greedy stream diverged beside a sampled slot"


def test_spec_respects_stop_tokens_and_budget():
    """A stop id inside an accepted run must end the stream AT the stop
    token — speculation can't overshoot the contract."""
    eng = _engine(4)
    toks_ref, fin_ref = _engine(0).generate(
        REPETITIVE, _sp(temperature=0.0, max_tokens=24, stop_token_ids=(6,)))
    toks, fin = eng.generate(
        REPETITIVE, _sp(temperature=0.0, max_tokens=24, stop_token_ids=(6,)))
    assert toks == toks_ref and fin.finish_reason == fin_ref.finish_reason


def test_spec_sessions_reuse_stays_correct():
    """Cross-turn prefix reuse on top of spec decode: turn 2 reuses
    rows written by verify steps, so its output must match a fresh
    engine's answer for the same conversation."""
    eng = _engine(4)
    h1 = eng.submit(REPETITIVE, _sp(**GREEDY), session_id="sess")
    eng_drive(eng, h1)
    t1, _ = h1.collect_tokens(timeout=1)
    follow = REPETITIVE + t1 + [9]
    h2 = eng.submit(follow, _sp(**GREEDY), session_id="sess")
    eng_drive(eng, h2)
    t2, _ = h2.collect_tokens(timeout=1)
    assert eng.metrics["prefix_reuse_tokens"] > 0
    ref = _engine(0)
    t2_ref, _ = ref.generate(follow, _sp(**GREEDY))
    assert t2 == t2_ref


def test_spec_with_int8_kv_bit_identical():
    """spec-on int8 greedy output == spec-off int8 (the verify window
    quantizes through the same _write_kv seam as every other write)."""
    ref, _ = _engine(0, kv_quant="int8").generate(
        REPETITIVE, _sp(temperature=0.0, max_tokens=32))
    eng = _engine(4, kv_quant="int8")
    toks, _ = eng.generate(REPETITIVE, _sp(temperature=0.0, max_tokens=32))
    assert toks == ref
    assert eng.metrics["spec_steps"] > 0


def test_spec_with_interleave_bit_identical():
    """The verify window rides the fused mixed dispatches: a greedy slot
    keeps speculating while a second prompt's pieces stream, and both
    outputs match the spec-off interleaved engine exactly."""
    outs = {}
    for tag, spec in (("off", 0), ("on", 4)):
        eng = _engine(spec, num_slots=2, prefill_chunk_tokens=8,
                      prefill_buckets=(16, 32))
        h1 = eng.submit(REPETITIVE, _sp(temperature=0.0, max_tokens=40))
        eng.step()
        eng.step()
        h2 = eng.submit(  # long prompt arrives while decode is live
            list(range(60, 90)), _sp(temperature=0.0, max_tokens=8))
        while eng.step():
            pass
        outs[tag] = (
            h1.collect_tokens(timeout=60)[0],
            h2.collect_tokens(timeout=60)[0],
        )
        if spec:
            assert eng.metrics["spec_steps"] > 0, "spec never engaged"
            assert eng.metrics["mixed_steps"] > 0, "interleave never engaged"
    assert outs["off"] == outs["on"]


def test_spec_coexists_with_grammar_slot():
    """A grammar-constrained greedy slot speculates: the acceptance
    oracle is the device-masked argmax, so constrained output is
    token-identical to the non-spec masked path, every emitted token is
    admissible under the host FSM walk (the post-hoc validator never
    fires), and the unconstrained slot is unaffected."""
    import json

    import jsonschema

    pytest.importorskip("jax")
    from omnia_tpu.engine.grammar import compile_json_schema
    from omnia_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    schema = {"type": "object",
              "properties": {"a": {"type": "integer"},
                             "ok": {"type": "boolean"}},
              "required": ["a", "ok"]}
    g = compile_json_schema(schema, tok)
    over = dict(num_slots=2, grammar=True, grammar_max_states=512)
    sp_g = _sp(temperature=0.0, max_tokens=100, stop_token_ids=(0,))

    ref = _engine(0, **over)
    hg = ref.submit(tok.encode("make json"), sp_g, grammar=g)
    eng_drive(ref, hg)
    toks_ref, _ = hg.collect_tokens(timeout=1)

    eng = _engine(4, **over)
    hg = eng.submit(tok.encode("make json"), sp_g, grammar=g)
    hf = eng.submit(REPETITIVE, _sp(temperature=0.0, max_tokens=60))
    eng_drive(eng, hf)
    eng_drive(eng, hg)
    toks_f, _ = hf.collect_tokens(timeout=1)
    toks_g, fin_g = hg.collect_tokens(timeout=1)

    assert eng.metrics["spec_steps"] > 0, "grammar slot suspended spec"
    assert toks_g == toks_ref, "spec changed constrained greedy output"
    payload = [t for t in toks_g if t != 0]
    jsonschema.validate(json.loads(tok.decode(payload)), schema)
    view = g.view(eng.model_cfg.vocab_size, (0,))
    s = view.start
    for t in toks_g:
        assert view.allowed(s)[t], (s, t)
        s = view.advance(s, t)
    toks_f_ref, _ = _engine(0).generate(
        REPETITIVE, _sp(temperature=0.0, max_tokens=60))
    assert toks_f == toks_f_ref, "unconstrained slot diverged"


def test_mid_stream_deadline_and_cancel_keep_exact_ledgers():
    """A deadline or cancel landing between verify steps finishes the
    slot with its exact partial books: streamed tokens ==
    num_generated_tokens, and every submit reconciles to one finish."""
    eng = _engine(4)
    now = [1000.0]
    eng.clock = lambda: now[0]
    h = eng.submit(REPETITIVE, _sp(temperature=0.0, max_tokens=200),
                   deadline_s=50.0)
    for _ in range(6):
        eng.step()
    now[0] += 100.0  # deadline passes mid-generation
    eng_drive(eng, h)
    toks, fin = h.collect_tokens(timeout=1)
    assert fin.finish_reason.value == "deadline"
    assert fin.num_generated_tokens == len(toks) > 0
    assert eng.metrics["deadline_exceeded"] == 1

    h2 = eng.submit(REPETITIVE, _sp(temperature=0.0, max_tokens=200))
    for _ in range(6):
        eng.step()
    h2.cancel()
    eng_drive(eng, h2)
    toks2, fin2 = h2.collect_tokens(timeout=1)
    assert fin2.finish_reason.value == "cancelled"
    assert eng.metrics["requests_submitted"] == 2
    assert eng.metrics["requests_finished"] == 2
    assert eng.metrics["tokens_generated"] == len(toks) + len(toks2)


def test_spec_verify_flight_events():
    """Verify steps are flight-recorder-visible: spec_verify events
    carry per-step proposed/accepted counts and the dispatch-vs-sync
    wall split."""
    eng = _engine(4, flight_events=256)
    eng.generate(REPETITIVE, _sp(temperature=0.0, max_tokens=48))
    evs = eng._flight.events("spec_verify")
    assert len(evs) == eng.metrics["spec_steps"] > 0
    total_prop = sum(e.attrs["proposed"] for e in evs)
    total_acc = sum(e.attrs["accepted"] for e in evs)
    assert total_prop == eng.metrics["spec_proposed"]
    assert total_acc == eng.metrics["spec_accepted"]
    assert all(e.attrs["dispatch_s"] >= 0 and e.attrs["sync_s"] >= 0
               and e.attrs["slots"] >= 1 for e in evs)


def test_spec_verify_event_kind_is_registered():
    """The closed EVENTS vocabulary includes the new kind (jax-free)."""
    from omnia_tpu.engine.flight import EVENTS

    assert "spec_verify" in EVENTS


def test_spec_knobs_off_are_true_noop():
    """KNOB_GUARDS target: spec_decode=0 must keep a byte-identical
    lowered decode program and ZERO spec state regardless of the (dead)
    spec_decode_max / spec_gate_window values."""
    pytest.importorskip("jax")
    eng = _engine(0)
    eng2 = _engine(0, spec_decode_max=13, spec_gate_window=7)
    for e in (eng, eng2):
        assert e._verify_fn is None and e._verify_decode_fn is None
        assert e._mixed_spec_fns == {} and e._mixed_spec_sample_fns == {}
        assert e._spec_gate is None
        assert not e._spec_step()
        assert e.cfg.spec_window() == 0
        for key in ("spec_steps", "spec_proposed", "spec_accepted",
                    "spec_gate_state", "spec_index_bytes"):
            assert e.metrics[key] == 0, (key, e.metrics[key])
        assert e.metrics["spec_accept_ema"] == 0.0
        assert all(s.spec_index is None for s in e._slots)

    def lowered(e):
        return e._decode_fn_single.lower(
            e.params, e._ck, e._cv, e._tokens, e._positions, e._active,
            e._budget, e._stop_ids, e._key_data, e._temp, e._top_p,
            e._top_k,
        ).as_text()

    assert lowered(eng) == lowered(eng2)


def test_reprobe_cooldown_advances_once_per_step():
    """The up-to-two plan calls one scheduler step makes share a depths
    memo: a collapsed slot's re-probe cooldown must advance exactly
    once per step, never be burned by a discarded engage-probe plan."""
    eng = _engine(2, spec_decode_max=4)
    h = eng.submit(REPETITIVE, _sp(temperature=0.0, max_tokens=30))
    eng.step()  # placement: the slot is live with its first token out
    slot = next(s for s in eng._slots if s.active)
    slot.spec_k, slot.spec_cool = 0, 0
    depths: dict = {}
    eng._spec_plan(depths=depths)
    eng._spec_plan(depths=depths)
    assert slot.spec_cool == 1, "cooldown advanced per plan, not per step"
    # And the re-probe actually fires once the cadence elapses: the
    # probe depth (1) is granted and the cooldown resets — whether the
    # lookup then hits is the traffic's business, not the controller's.
    slot.spec_cool = sd._RETRY_STEPS - 1
    assert eng._slot_depth(slot) == 1
    assert slot.spec_cool == 0
    h.cancel()
    while eng.step():
        pass


def test_spec_gate_disable_is_observable_on_engine():
    """A configured gate surfaces its state in metrics; under an
    injected logical clock (lockstep) the gate is skipped entirely —
    speculation stays permitted and the state stays 0."""
    eng = _engine(4, spec_gate_window=4)
    eng.generate(REPETITIVE, _sp(temperature=0.0, max_tokens=60))
    assert eng.metrics["spec_gate_state"] in (0, 1, 2)
    assert eng._spec_gate is not None

    lk = _engine(4, spec_gate_window=4)
    lk.clock = lambda: 123.0  # injected clock: gate must never build
    lk.generate(REPETITIVE, _sp(temperature=0.0, max_tokens=30))
    assert lk._spec_gate is None
    assert lk.metrics["spec_gate_state"] == 0
    assert lk.metrics["spec_steps"] > 0


def test_spec_config_validation_on_engine():
    pytest.importorskip("jax")
    from omnia_tpu.engine import EngineConfig, InferenceEngine
    from omnia_tpu.models import get_config

    with pytest.raises(ValueError, match="spec"):
        InferenceEngine(
            get_config("test-tiny"),
            EngineConfig(num_slots=2, max_seq=64, prefill_buckets=(4,),
                         dtype="float32", spec_decode=8),
        )
