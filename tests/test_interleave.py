"""Stall-free batching: mixed prefill+decode equivalence battery.

The token-budget scheduler (``EngineConfig.prefill_chunk_tokens``,
engine/interleave.py) must be a pure LATENCY optimization: interleaved
prefill produces bit-identical greedy tokens AND resident KV rows to
monolithic prefill-first serving — under int8 KV, with grammar slots in
the batch, from a shared-prefix pool seed, and across mid-prefill
deadline/cancel aborts (partial books stay exact). Everything here is
hermetic (test-tiny model, CPU, single-threaded stepping).
"""

import numpy as np
import pytest

from omnia_tpu.engine import (
    EngineConfig,
    FinishReason,
    InferenceEngine,
    SamplingParams,
)
from omnia_tpu.models import get_config
from omnia_tpu.models.kv_quant import is_quant_kv

pytestmark = pytest.mark.interleave

CFG = get_config("test-tiny")
BASE = dict(
    num_slots=4, max_seq=128, prefill_buckets=(8, 16, 32), dtype="float32",
    max_sessions=4,
)


def _engine(chunk=0, **kw):
    merged = {**BASE, **kw}
    return InferenceEngine(
        CFG, EngineConfig(**merged, prefill_chunk_tokens=chunk), seed=0
    )


def _kv_rows(eng, slot, n):
    """Host copies of one slot's leading KV rows (QuantKV-aware)."""
    out = []
    for c in (eng._ck, eng._cv):
        if is_quant_kv(c):
            out.append(np.asarray(c.q)[:, slot, :n])
            out.append(np.asarray(c.s)[:, slot, :n])
        else:
            out.append(np.asarray(c)[:, slot, :n])
    return out


def _run_pair(eng, prompt_b, sp_b, warm_steps=3, **submit_b):
    """One long-running greedy decode (slot 0) + one arrival mid-stream:
    the arrival's prefill is the work under test. Returns both streams."""
    sp_a = SamplingParams(temperature=0.0, max_tokens=60)
    ha = eng.submit([1, 2, 3, 4], sp_a)
    for _ in range(warm_steps):
        eng.step()
    assert eng._slots[0].active  # decode live when the arrival lands
    hb = eng.submit(prompt_b, sp_b, **submit_b)
    while eng.step():
        pass
    return ha.collect_tokens(timeout=30), hb.collect_tokens(timeout=30)


PROMPT_B = list(range(5, 35))  # 30 tokens -> several 4-token pieces


class TestBitExactEquivalence:
    def test_tokens_and_kv_match_monolithic(self):
        base = _engine(0)
        mix = _engine(4)
        (ta0, _), (tb0, fb0) = _run_pair(
            base, PROMPT_B, SamplingParams(temperature=0.0, max_tokens=8)
        )
        (ta1, _), (tb1, fb1) = _run_pair(
            mix, PROMPT_B, SamplingParams(temperature=0.0, max_tokens=8)
        )
        # The interleaved arm actually interleaved...
        assert mix.metrics["mixed_steps"] >= 8  # ceil(30 / 4) pieces
        assert mix.metrics["interleaved_prefill_tokens"] == len(PROMPT_B)
        # ...and never stalled decode, while prefill-first did.
        assert mix.metrics["decode_stall_steps"] == 0
        assert base.metrics["decode_stall_steps"] > 0
        assert base.metrics["mixed_steps"] == 0
        # Identical token streams, exactly. The resident KV (prompt +
        # decoded rows) agrees to float32 rounding: a monolithic prefill
        # and a run of 4-token pieces are differently-shaped XLA
        # programs, which the installed XLA does not make bit-equal.
        assert ta0 == ta1 and tb0 == tb1
        assert fb0.finish_reason == fb1.finish_reason
        rows = len(PROMPT_B) + fb0.num_generated_tokens - 1
        for x, y in zip(_kv_rows(base, 1, rows), _kv_rows(mix, 1, rows)):
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)
        # prefill_tokens metered per piece sums to the monolithic count.
        assert (
            mix.metrics["prefill_tokens"] == base.metrics["prefill_tokens"]
        )

    def test_tokens_and_kv_match_under_int8_kv(self):
        # Prompt LONGER than the largest bucket so the monolithic arm
        # takes the chunked-extend path too: under int8 KV the extend
        # seam attends already-quantized resident rows, while a fresh
        # self-contained prefill attends its own FLOAT chunk — a
        # documented pre-existing ±1-LSB asymmetry (docs/serving.md "KV
        # cache precision", pinned since the int8 PR). Extend-vs-extend
        # is exactly chunk-size invariant, so interleaving stays
        # bit-identical to what monolithic serving stores.
        long_b = list(range(5, 45))  # 40 tokens > max bucket 32
        base = _engine(0, kv_quant="int8")
        mix = _engine(4, kv_quant="int8")
        (ta0, _), (tb0, _) = _run_pair(
            base, long_b, SamplingParams(temperature=0.0, max_tokens=8)
        )
        (ta1, _), (tb1, _) = _run_pair(
            mix, long_b, SamplingParams(temperature=0.0, max_tokens=8)
        )
        assert mix.metrics["mixed_steps"] >= 10
        assert ta0 == ta1 and tb0 == tb1
        # int8 rows AND their f32 scales bit-identical: the mixed
        # program quantizes at the same _write_kv seam.
        for x, y in zip(
            _kv_rows(base, 1, len(long_b)), _kv_rows(mix, 1, len(long_b))
        ):
            np.testing.assert_array_equal(x, y)
        # Short fresh prompts (monolithic takes the float-attending
        # fresh-prefill program) still emit identical greedy TOKENS.
        (_, _), (ts0, _) = _run_pair(
            _engine(0, kv_quant="int8"), PROMPT_B,
            SamplingParams(temperature=0.0, max_tokens=8),
        )
        (_, _), (ts1, _) = _run_pair(
            _engine(4, kv_quant="int8"), PROMPT_B,
            SamplingParams(temperature=0.0, max_tokens=8),
        )
        assert ts0 == ts1

    def test_multi_turn_session_reuse_matches_monolithic(self):
        """Turn 2 of a session extends from the turn-1 rows on both
        policies; the interleaved extend pieces must reproduce the
        monolithic suffix exactly."""
        turn1 = list(range(40, 60))
        results = []
        for chunk in (0, 4):
            eng = _engine(chunk)
            ha = eng.submit(
                [1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=90)
            )
            for _ in range(3):
                eng.step()
            h1 = eng.submit(
                turn1, SamplingParams(temperature=0.0, max_tokens=4),
                session_id="s",
            )
            while eng.step():
                pass
            t1, _ = h1.collect_tokens(timeout=30)
            # Turn 2: same session, prompt = turn1 + reply + new tokens.
            ha2 = eng.submit(
                [1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=60)
            )
            for _ in range(3):
                eng.step()
            turn2 = turn1 + t1 + [7, 8, 9]
            h2 = eng.submit(
                turn2, SamplingParams(temperature=0.0, max_tokens=4),
                session_id="s",
            )
            while eng.step():
                pass
            t2, _ = h2.collect_tokens(timeout=30)
            results.append((t1, t2, eng.metrics["prefix_reuse_tokens"]))
            ha.collect_tokens(timeout=30)
            ha2.collect_tokens(timeout=30)
        assert results[0] == results[1]
        assert results[0][2] > 0  # turn 2 really reused resident rows


class TestGrammarInterleave:
    @pytest.fixture(scope="class")
    def engines(self):
        kw = dict(
            num_slots=4, max_seq=128, prefill_buckets=(8, 16, 32),
            dtype="float32", max_sessions=0, grammar=True,
            grammar_max_states=512,
        )
        return (
            InferenceEngine(
                CFG, EngineConfig(**kw, prefill_chunk_tokens=0), seed=0
            ),
            InferenceEngine(
                CFG, EngineConfig(**kw, prefill_chunk_tokens=4), seed=0
            ),
        )

    def _grammar(self):
        from omnia_tpu.engine.grammar import compile_json_schema
        from omnia_tpu.engine.tokenizer import ByteTokenizer

        schema = {
            "type": "object",
            "properties": {"a": {"type": "integer"}},
            "required": ["a"],
        }
        return compile_json_schema(schema, ByteTokenizer())

    def test_active_grammar_slot_and_constrained_arrival(self, engines):
        """A grammar-constrained slot keeps decoding through mixed steps
        (FSM state rides the fused program), and an arriving request WITH
        a grammar gets its first-token start-state bias inside the final
        mixed piece — both bit-identical to prefill-first."""
        g = self._grammar()
        sp_g = SamplingParams(
            temperature=0.0, max_tokens=40, stop_token_ids=(0,)
        )
        streams = []
        for eng in engines:
            ha = eng.submit(list(b"make json"), sp_g, grammar=g)
            for _ in range(3):
                eng.step()
            assert eng._slots[0].active
            hb = eng.submit(PROMPT_B, SamplingParams(
                temperature=0.0, max_tokens=6))
            hc = eng.submit(list(b"second json goes here, a long prompt"),
                            sp_g, grammar=g)
            while eng.step():
                pass
            streams.append((
                ha.collect_tokens(timeout=30)[0],
                hb.collect_tokens(timeout=30)[0],
                hc.collect_tokens(timeout=30)[0],
            ))
        assert streams[0] == streams[1]
        mix = engines[1]
        assert mix.metrics["mixed_steps"] > 0
        assert mix.metrics["decode_stall_steps"] == 0
        # The constrained streams really walked the grammar.
        v = g.view(CFG.vocab_size, (0,))
        for toks in (streams[0][0], streams[0][2]):
            s = v.start
            for t in toks:
                assert v.allowed(s)[t]
                s = v.advance(s, t)


class TestPrefixSeededInterleave:
    SYS = list(range(1, 25))  # 24 tokens >= prefix_cache_min_tokens

    def _run(self, chunk):
        eng = _engine(chunk, prefix_cache_slots=2, max_sessions=0)
        eng.register_prefix(self.SYS)
        # Publish the registered prefix from an idle first placement
        # (monolithic on both arms — nothing to stall).
        h0 = eng.submit(
            self.SYS + [30], SamplingParams(temperature=0.0, max_tokens=2)
        )
        while eng.step():
            pass
        h0.collect_tokens(timeout=30)
        # A live decoder + a fresh seeded arrival: only the suffix
        # should prefill, interleaved.
        ha = eng.submit(
            [9, 9, 9], SamplingParams(temperature=0.0, max_tokens=40)
        )
        for _ in range(3):
            eng.step()
        hb = eng.submit(
            self.SYS + [31, 32, 33],
            SamplingParams(temperature=0.0, max_tokens=6),
        )
        while eng.step():
            pass
        ha.collect_tokens(timeout=30)
        return eng, hb.collect_tokens(timeout=30)

    def test_seeded_placement_matches_monolithic(self):
        base, (tb0, _) = self._run(0)
        mix, (tb1, _) = self._run(4)
        assert tb0 == tb1
        hit = base.metrics["prefix_cache_hit_tokens"]
        assert hit > 0  # the pool really served the head
        assert mix.metrics["prefix_cache_hit_tokens"] == hit
        # Seeded head + interleaved suffix: only the suffix rode mixed
        # steps, and decode never stalled for it.
        assert 0 < mix.metrics["interleaved_prefill_tokens"] < len(self.SYS) + 3
        assert mix.metrics["decode_stall_steps"] == 0


class TestMidPrefillAborts:
    def test_deadline_mid_prefill_partial_counts_stay_exact(self):
        eng = _engine(4)
        clock = [0.0]
        eng.clock = lambda: clock[0]
        ha = eng.submit(
            [1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=60)
        )
        for _ in range(3):
            eng.step()
        pb = list(range(10, 40))
        prefill0 = eng.metrics["prefill_tokens"]  # A's own prefill
        hb = eng.submit(
            pb, SamplingParams(temperature=0.0, max_tokens=4),
            session_id="s1", deadline_s=5.0,
        )
        eng.step()  # begins the interleave + consumes the first piece
        assert eng._prefilling is not None
        consumed = eng.metrics["interleaved_prefill_tokens"]
        assert 0 < consumed < len(pb)
        clock[0] = 6.0  # TTL expires mid-prefill
        eng.step()
        assert eng._prefilling is None
        toks, fin = hb.collect_tokens(timeout=30)
        assert fin.finish_reason is FinishReason.DEADLINE and toks == []
        assert fin.num_prompt_tokens == len(pb)
        assert eng.metrics["deadline_exceeded"] == 1
        # Partial books exact: only consumed pieces were ever counted.
        assert eng.metrics["prefill_tokens"] - prefill0 == consumed
        assert eng.metrics["interleaved_prefill_tokens"] == consumed
        # The consumed rows stay genuinely valid: the retry on the same
        # session reuses exactly the consumed frontier and still emits
        # the fresh-prefill greedy tokens.
        hb2 = eng.submit(
            pb, SamplingParams(temperature=0.0, max_tokens=4),
            session_id="s1",
        )
        while eng.step():
            pass
        t2, fin2 = hb2.collect_tokens(timeout=30)
        assert fin2.finish_reason is FinishReason.LENGTH
        assert eng.metrics["prefix_reuse_tokens"] == consumed
        ha.collect_tokens(timeout=30)
        ref = _engine(0)
        rt, _ = ref.generate(pb, SamplingParams(temperature=0.0, max_tokens=4))
        assert t2 == rt

    def test_cancel_mid_prefill_frees_the_slot(self):
        eng = _engine(4)
        ha = eng.submit(
            [1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=60)
        )
        for _ in range(3):
            eng.step()
        hb = eng.submit(
            list(range(10, 40)), SamplingParams(temperature=0.0, max_tokens=4)
        )
        eng.step()
        assert eng._prefilling is not None
        hb.cancel()
        eng.step()
        assert eng._prefilling is None
        _toks, fin = hb.collect_tokens(timeout=30)
        assert fin.finish_reason is FinishReason.CANCELLED
        # The slot is immediately reusable.
        hc = eng.submit(
            list(range(50, 70)), SamplingParams(temperature=0.0, max_tokens=4)
        )
        while eng.step():
            pass
        _t, fin_c = hc.collect_tokens(timeout=30)
        assert fin_c.finish_reason is FinishReason.LENGTH
        ha.collect_tokens(timeout=30)
        # Books balance: every submit reached exactly one terminal.
        assert (
            eng.metrics["requests_finished"]
            == eng.metrics["requests_submitted"] == 3
        )

    def test_drain_completes_half_prefilled_request(self):
        eng = _engine(4)
        ha = eng.submit(
            [1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=30)
        )
        for _ in range(3):
            eng.step()
        hb = eng.submit(
            list(range(10, 40)), SamplingParams(temperature=0.0, max_tokens=4)
        )
        eng.step()
        assert eng._prefilling is not None
        eng.stop(drain=True)  # threadless drain steps the engine inline
        _toks, fin = hb.collect_tokens(timeout=30)
        assert fin.finish_reason is FinishReason.LENGTH
        ha.collect_tokens(timeout=30)


class TestWarmupCoversMixedPrograms:
    def test_no_compiles_during_interleaved_placement(self):
        """The mixed family is AOT-compiled by warmup (TTFT discipline):
        an interleaved placement on a warm engine must trigger zero
        compiles."""
        import io
        import logging as _logging

        import jax as _jax

        eng = _engine(4)
        eng.warmup()
        with _jax.log_compiles():
            stream = io.StringIO()
            handler = _logging.StreamHandler(stream)
            logger = _logging.getLogger("jax._src.dispatch")
            logger.addHandler(handler)
            try:
                ha = eng.submit(
                    [1, 2, 3], SamplingParams(temperature=0.0, max_tokens=40)
                )
                for _ in range(3):
                    eng.step()
                hb = eng.submit(
                    PROMPT_B, SamplingParams(temperature=0.0, max_tokens=4)
                )
                while eng.step():
                    pass
                ha.collect_tokens(timeout=30)
                hb.collect_tokens(timeout=30)
            finally:
                logger.removeHandler(handler)
            logged = stream.getvalue()
        assert eng.metrics["mixed_steps"] > 0
        assert "Compiling" not in logged, logged


class TestLoadSignal:
    def test_engine_reports_prompt_token_backlog(self):
        eng = _engine(4)
        ha = eng.submit(
            [1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=60)
        )
        for _ in range(3):
            eng.step()
        pb = list(range(10, 40))
        eng.submit(pb, SamplingParams(temperature=0.0, max_tokens=4))
        assert eng.pending_prefill_tokens() == len(pb)  # still queued
        eng.step()  # interleave begins; some pieces consumed
        pf = eng._prefilling
        assert pf is not None
        assert (
            eng.pending_prefill_tokens() == len(pb) - pf.frontier > 0
        )
        while eng.step():
            pass
        assert eng.pending_prefill_tokens() == 0
        ha.collect_tokens(timeout=30)

    def test_coordinator_load_counts_token_backlog(self):
        """Four 8k-prompt requests must not route like four 10-token
        ones: the load signal folds the prompt-token backlog in."""
        from omnia_tpu.engine.coordinator import EngineCoordinator
        from omnia_tpu.engine.mock import MockEngine

        a, b = MockEngine(), MockEngine()
        coord = EngineCoordinator([a, b])
        with a._lock:
            a._live_prompt_tokens = 4 * 8192  # queued prefill WORK
        assert coord._load(0) > coord._load(1) + 1.0
        # A fresh short request routes to the token-idle worker.
        assert coord._pick(None, [1, 2, 3]) == 1

    def test_coordinator_load_tolerates_legacy_workers(self):
        from omnia_tpu.engine.coordinator import EngineCoordinator

        class Legacy:
            def queue_depth(self):
                return 2

            def active_slots(self):
                return 1

            def healthy(self):
                return True

            def start(self):
                pass

            def stop(self, drain=False):
                pass

        coord = EngineCoordinator([Legacy()])
        assert coord._load(0) == 3.0  # count-only load, no raise


class TestMockParity:
    def test_mock_mirrors_interleave_metrics(self):
        from omnia_tpu.engine import MockEngine

        mock = MockEngine(prefill_chunk_tokens=8)
        prompt = list(b"hello mock interleave")  # 21 tokens -> 3 pieces
        _toks, fin = mock.generate(prompt)
        assert fin.finish_reason is not None
        assert mock.metrics["mixed_steps"] == 3
        assert mock.metrics["interleaved_prefill_tokens"] == len(prompt)
        assert mock.metrics["decode_stall_steps"] == 0
        assert mock.pending_prefill_tokens() == 0

    def test_mock_counts_stalls_without_budget(self):
        import time as _time

        from omnia_tpu.engine import MockEngine
        from omnia_tpu.engine.mock import Scenario

        mock = MockEngine([Scenario(".*", reply="x" * 30,
                                    delay_per_token_s=0.005)])
        h1 = mock.submit(list(b"one"), SamplingParams(max_tokens=30))
        _time.sleep(0.02)  # first playback live when the second prefills
        h2 = mock.submit(list(b"two"), SamplingParams(max_tokens=30))
        h1.collect_tokens(timeout=10)
        h2.collect_tokens(timeout=10)
        assert mock.metrics["decode_stall_steps"] >= 1
        assert mock.metrics["mixed_steps"] == 0
