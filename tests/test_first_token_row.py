"""A placement's head runs over the one prompt row it samples.

The first-token programs (``prefill_insert``, ``extend``, the interleaved
prefill's last piece, the ring prefill) hand the prompt's last real row to
the model (``row=``), which takes it out of the stream BEFORE the final
norm and the head. Three guards, all on the CPU at tiny size:

- the seam: for each family the row-selected logits equal the same row of
  the all-rows logits, to the tolerance the numerics tests use;
- the served first token: through an engine, each program's first token is
  the token sampled from the all-rows logits at the prompt's last real row
  (the benchmark's ``correct`` judges ``model.forward`` on a cache of its
  own, not what a placement samples: this is the only guard it has);
- the waste cannot come back: no ``[1, T, V]`` value in the lowered
  first-token programs, no product with V columns in ``extend_nosample``.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.engine import EngineConfig, InferenceEngine, SamplingParams
from omnia_tpu.models import get_config, llama, model_module
from omnia_tpu.models.quant import quantize_params
from omnia_tpu.ops.sampling import (
    _NEG_INF,
    make_slot_key_data,
    sample_tokens_per_slot,
)

T, S = 16, 32  # a prefill bucket and a cache of the tiny models
TOL = dict(rtol=2e-4, atol=2e-4)  # tests/test_llama.py's, float32


def _family(name):
    """(cfg, params) of one head: plain, tied to the embedding, int8, and
    the latent family's with one residual copy and with four (folded after
    the row is taken)."""
    preset = {"mla": "test-tiny-mla", "mla-hc4": "test-tiny-hc"}.get(name, "test-tiny")
    cfg = get_config(preset)
    if name == "llama-tied":
        cfg = dataclasses.replace(cfg, tie_embeddings=True)
    params = jax.jit(lambda key: model_module(cfg).init_params(cfg, key, dtype=jnp.float32))(
        jax.random.key(3))
    if name == "llama-int8-head":
        params = quantize_params(params, cfg, "int8")
    return cfg, params


FAMILIES = ("llama", "llama-tied", "llama-int8-head", "mla", "mla-hc4")


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    cfg, params = _family(request.param)
    model = model_module(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(1, cfg.vocab_size, size=(1, T)), jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    cache = model.init_kv_cache(cfg, 1, S, dtype=jnp.float32)
    start = jnp.zeros((1,), jnp.int32)
    entries = {
        "forward_prefill": lambda **kw: model.forward_prefill(
            params, cfg, tokens, pos, **kw)[0],
        "forward": lambda **kw: model.forward(
            params, cfg, tokens, pos, *cache, start, **kw)[0],
    }
    every_row = {k: np.asarray(jax.jit(f)()) for k, f in entries.items()}
    # The row is an operand: one compile an entry serves both rows.
    one_row = {k: jax.jit(lambda r, f=f: f(row=r)) for k, f in entries.items()}
    return cfg, one_row, every_row


@pytest.mark.parametrize("row", [T // 2 - 1, T - 1], ids=["middle", "end"])
@pytest.mark.parametrize("entry", ["forward_prefill", "forward"])
def test_the_row_taken_before_the_head_is_that_row_of_every_rows_logits(
        family, entry, row):
    cfg, one_row, every_row = family
    assert every_row[entry].shape == (1, T, cfg.vocab_size)  # the default: all rows
    got = one_row[entry](jnp.int32(row))
    assert got.shape == (1, cfg.vocab_size) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), every_row[entry][:, row], **TOL)
    # A row's logits are its own: the neighbour's would not pass.
    assert np.abs(np.asarray(got) - every_row[entry][:, row - 1]).max() > 1e-2


# -- the served first token -------------------------------------------------

CFG = get_config("test-tiny")
ENGINE = dict(
    num_slots=4, max_seq=128, prefill_buckets=(8, 16, 32), dtype="float32",
    max_sessions=4, grammar=True, grammar_max_states=512,
)


@pytest.fixture(scope="module")
def engines():
    return {
        chunk: InferenceEngine(
            CFG, EngineConfig(**ENGINE, prefill_chunk_tokens=chunk), seed=0)
        for chunk in (0, 4)
    }


@pytest.fixture(scope="module")
def grammar():
    from omnia_tpu.engine.grammar import compile_json_schema
    from omnia_tpu.engine.tokenizer import ByteTokenizer

    schema = {"type": "object", "properties": {"a": {"type": "integer"}},
              "required": ["a"]}
    return compile_json_schema(schema, ByteTokenizer())


def _drain(eng, handle):
    while eng.step():
        pass
    return handle.collect_tokens(timeout=30)[0]


def _first_token(eng, program, prompt, sp, **submit):
    """The first token the engine serves for ``prompt`` through ``program``,
    and a check that the placement really went that way."""
    before = dict(eng.metrics)
    if program == "extend":
        # Turn 1 leaves the prompt's head resident; turn 2 extends over it.
        head = prompt[:5]
        _drain(eng, eng.submit(head, SamplingParams(temperature=0.0, max_tokens=1),
                               session_id="turns"))
        toks = _drain(eng, eng.submit(prompt, sp, session_id="turns", **submit))
        assert eng.metrics["prefix_reuse_tokens"] - before["prefix_reuse_tokens"] >= 5
    elif program == "mixed_sample":
        # A decode in flight, so the arrival's prompt goes in pieces and its
        # last piece (2 tokens in a bucket of 8) samples.
        busy = eng.submit([1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=40))
        for _ in range(3):
            eng.step()
        toks = _drain(eng, eng.submit(prompt, sp, **submit))
        busy.collect_tokens(timeout=30)
        assert eng.metrics["mixed_steps"] - before["mixed_steps"] >= len(prompt) // 4
    else:
        toks = _drain(eng, eng.submit(prompt, sp, **submit))
        assert eng.metrics["prefix_reuse_tokens"] == before["prefix_reuse_tokens"]
        assert eng.metrics["mixed_steps"] == before["mixed_steps"]
    return toks[0]


def _sampled_from_every_rows_logits(eng, prompt, sp, grammar, row=None):
    """The token ``sp`` samples from row ``row`` (the last real one) of the
    whole prompt's all-rows logits, as the programs did before the row moved
    in front of the head."""
    n = len(prompt)
    bucket = eng.cfg.bucket_for(n)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = prompt
    logits, _, _ = llama.forward_prefill(
        eng.params, CFG, jnp.asarray(toks), jnp.arange(bucket, dtype=jnp.int32)[None])
    assert logits.shape == (1, bucket, CFG.vocab_size)
    bias = None
    if grammar is not None:
        view = grammar.view(CFG.vocab_size, sp.stop_token_ids)
        bias = jnp.where(jnp.asarray(view.table[view.start]) < 0, _NEG_INF, 0.0)[None]
    key = make_slot_key_data(sp.seed if sp.seed is not None else 0)
    tok, _ = sample_tokens_per_slot(
        logits[:, n - 1 if row is None else row], jnp.asarray(key)[None],
        jnp.float32(sp.temperature)[None], jnp.float32(sp.top_p)[None],
        jnp.int32(sp.top_k)[None], mask_bias=bias,
    )
    return int(tok[0])


SAMPLERS = {
    "greedy": dict(temperature=0.0),
    "seeded-temperature": dict(temperature=0.9, seed=1234),
    "grammar-start-bias": dict(temperature=0.0, stop_token_ids=(0,)),
}
# Each shorter than its bucket: 11 of 16; 14 as 5 resident + 9 of 16; 30 as
# seven pieces of 4 and a last one of 2, each in a bucket of 8.
PROMPTS = {
    "prefill_insert": list(range(40, 51)),
    "extend": list(range(60, 74)),
    "mixed_sample": list(range(5, 35)),
}


@pytest.mark.parametrize("sampler", SAMPLERS)
@pytest.mark.parametrize("program", PROMPTS)
def test_the_first_token_is_the_one_sampled_at_the_last_real_row(
        engines, grammar, program, sampler):
    eng = engines[4 if program == "mixed_sample" else 0]
    prompt = PROMPTS[program]
    sp = SamplingParams(max_tokens=2, **SAMPLERS[sampler])
    g = grammar if sampler == "grammar-start-bias" else None
    got = _first_token(eng, program, prompt, sp, **({"grammar": g} if g else {}))
    assert got == _sampled_from_every_rows_logits(eng, prompt, sp, g)
    if sampler == "greedy":
        # The prompt's row, not the bucket's: a pad row's argmax is another.
        bucket = eng.cfg.bucket_for(len(prompt))
        assert got != _sampled_from_every_rows_logits(eng, prompt, sp, g, row=bucket - 1)


# -- the waste cannot come back ---------------------------------------------


def _operands(eng, bucket):
    toks = np.zeros((1, bucket), np.int32)
    pos = np.arange(bucket, dtype=np.int32)[None]
    sp = SamplingParams()
    first = (eng._key_data[0], np.float32(sp.temperature), np.float32(sp.top_p),
             np.int32(sp.top_k), *eng._grammar_args(None, sp))
    return toks, pos, np.int32(0), first


def _lowered(eng, program, bucket):
    toks, pos, zero, first = _operands(eng, bucket)
    if program == "prefill_insert":
        return eng._prefill_insert_fn.lower(
            eng.params, *eng._cache, toks, pos, zero, zero, *first)
    if program == "extend":
        return eng._extend_fn.lower(
            eng.params, *eng._cache, toks, pos, zero, zero, zero, *first)
    if program == "extend_nosample":
        return eng._extend_nosample_fn.lower(
            eng.params, *eng._cache, toks, pos, zero, zero)
    assert program == "mixed_sample"
    return eng._mixed_sample_fns[bucket].lower(
        eng.params, eng._ck, eng._cv, eng._tokens, eng._positions, eng._active,
        eng._budget, eng._stop_ids, eng._key_data, eng._temp, eng._top_p,
        eng._top_k, toks, pos, zero, zero, zero, *first,
        eng._gstate, eng._gtable, eng._gactive)


@pytest.mark.parametrize("program,chunk,bucket", [
    ("prefill_insert", 0, 16), ("extend", 0, 16), ("mixed_sample", 4, 8)])
def test_no_first_token_program_holds_every_rows_logits(engines, program, chunk, bucket):
    text = _lowered(engines[chunk], program, bucket).as_text()
    V = CFG.vocab_size
    assert re.search(rf"tensor<1x{V}xf32>", text)  # the one row's logits
    assert not re.search(rf"tensor<1x{bucket}x{V}x", text), (
        f"{program} computes [1, {bucket}, {V}] logits to sample one row of them")


def test_a_piece_that_samples_nothing_has_no_head(engines):
    """``extend_nosample`` drops its logits, and XLA the product behind
    them: nothing V columns wide is left in the compiled program."""
    text = _lowered(engines[0], "extend_nosample", 16).compile().as_text()
    assert not re.search(rf"[,\[]{CFG.vocab_size}\]", text)
