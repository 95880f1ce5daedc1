"""The latent family with layers of several kinds (models/mla.py: gated
delta-rule linear-attention layers, KDA, beside latent attention without
rotary position, in any order; a recurrent state and a short convolution's
tail in the slot's cache beside the latent rows; a leading dense layer and
then the dropless expert share) against the plain reference of the model it
was written for, `benchmark/reference/kimi_linear_ref.py`, at `test-tiny-kda`'s
size: K(dense) K M K, 8 experts of which rank 1 of 2 holds 4.

Logits are compared and never tokens. Everything is float32 on the CPU, so
the program and the reference differ by the order of their sums alone: TOL
is 1e-5 of the reference's logit range (readings here are 1e-7 to 8e-7; the
chunk-wise rule against the recurrence reads 3e-7 absolute on outputs of 0.5),
and every planted fault has to move the number named for it by a hundred
times that. A state or a decay rounded to bfloat16 is among the faults: it
reads 1e-3 and more."""
import dataclasses
import functools
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.engine.engine import InferenceEngine
from omnia_tpu.engine.family import refuse_unported
from omnia_tpu.engine.programs import build_programs
from omnia_tpu.engine.types import EngineConfig, SamplingParams
from omnia_tpu.models import cache_arrays, decode_counters, get_config, mla, model_module
from omnia_tpu.ops import attention as attn
from omnia_tpu.ops import kda, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.manifest import reference_sizes  # noqa: E402


def _reference(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "reference", name + ".py"))
    mod = importlib.util.module_from_spec(spec)  # the benchmark's plain reference
    spec.loader.exec_module(mod)
    return mod


ref = _reference("kimi_linear_ref")
CFG = get_config("test-tiny-kda")
# The same model with a second latent layer, K M K M: the latent cache's
# index (the count of latent layers before) differs from the model's.
CFG_KMKM = dataclasses.replace(CFG, layer_types=(
    "linear_attention", "full_attention", "linear_attention", "full_attention"))
PREFILL, DECODE = 40, 24
TOL = 1e-5
# How a prompt of PREFILL tokens is placed: (real rows, bucket) a piece. The
# tests' chunk is 8 tokens (CHUNK below), so 12 and 20 are no multiples of it.
PLACEMENTS = {
    "one bucket": [(PREFILL, PREFILL)],
    "pieces, the last padded": [(12, 12), (20, 20), (8, 16)],
    "one padded piece": [(PREFILL, 64)],
}
CHUNK = 8


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """Chunks of 8 tokens, so that 40 tokens are five chunks and a piece's
    end falls inside one."""
    monkeypatch.setattr(kda, "CHUNK", CHUNK)
    monkeypatch.setattr(kda.kda_chunked, "__defaults__", (CHUNK,))


def file_of(cfg) -> dict:
    """The keys of a configuration file that the reference reads, for `cfg`:
    the layer kinds as the source states them, in lists that count from 1."""
    kinds = cfg.attention_kinds
    return {
        "num_hidden_layers": cfg.num_layers, "first_k_dense_replace": cfg.num_dense_layers,
        "linear_attn_config": {
            "kda_layers": [l + 1 for l, kind in enumerate(kinds) if kind == "kda"],
            "full_attn_layers": [l + 1 for l, kind in enumerate(kinds) if kind == "full"],
            "num_heads": cfg.kda_num_heads, "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.kda_conv_kernel},
        "num_attention_heads": cfg.num_heads, "kv_lora_rank": cfg.kv_rank, "q_lora_rank": None,
        "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "mla_use_nope": not cfg.rope_on_full_layers,
        "expert_rank": cfg.expert_rank, "moe_router_activation_func": cfg.router_scoring,
        "moe_renormalize": True, "num_expert_group": 1, "topk_group": 1,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "assumed": {"l2norm_eps": 1e-6},
    }


def _programs():
    """`step`, `piece`, `whole` and `fresh` under `jax.jit`, the configuration
    a static argument: new functions a call, so traced anew. SOUND is the set
    every case on the sound path shares (a configuration, a placement's
    shapes and `pad_is_real` each compile once a module); a case that patches
    a function of the model, or routes the kernels, makes its own, because
    the shared set would hand it the trace of the sound path."""
    def step(p, c, toks, start, *, cfg):
        pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        return mla.forward(p, cfg, toks, pos, *c, jnp.reshape(start, (1,)))

    def piece(p, c, toks, start, last, *, cfg, pad_is_real):
        """Every row's logits, the cache written as a placement writes it."""
        pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        every, *_ = mla.forward(p, cfg, toks, pos, *c, jnp.reshape(start, (1,)))
        _, *c = mla.forward(p, cfg, toks, pos, *c, jnp.reshape(start, (1,)),
                            row=None if pad_is_real else last)
        return every, *c

    def whole(p, toks, *, cfg):
        """The uncached forward over the whole sequence."""
        return mla.forward_prefill(p, cfg, toks, jnp.arange(toks.shape[1], dtype=jnp.int32)[None])

    def fresh(p, toks, row, *, cfg):
        """`whole` over a padded bucket whose last real row is `row`."""
        return mla.forward_prefill(p, cfg, toks, jnp.arange(toks.shape[1], dtype=jnp.int32)[None],
                                   row=row)

    return {"step": jax.jit(step, static_argnames="cfg"),
            "piece": jax.jit(piece, static_argnames=("cfg", "pad_is_real")),
            "whole": jax.jit(whole, static_argnames="cfg"),
            "fresh": jax.jit(fresh, static_argnames="cfg")}


SOUND = _programs()


def served_logits(params, cfg, tokens, placement, rows: int = 128, pad_is_real=False,
                  between=None, poison=False, programs=SOUND):
    """The prompt placed into a cache piece by piece (a padded piece names
    its last real row, as engine/programs.py::extend does), then one token a
    step through the cache: float32 [T, V]. Each piece gives the logits of
    its real rows. `between(cache)` stands between two calls; `poison` starts
    from a cache another tenant has left full."""
    cache = mla.init_kv_cache(cfg, 1, rows, dtype=params["embed"].dtype)
    if poison:
        cache = tuple(c + 3.0 for c in cache)
    step, piece = programs["step"], programs["piece"]
    out, at = [], 0
    for take, bucket in placement:
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :take] = tokens[at:at + take]
        logits, *cache = piece(params, cache, jnp.asarray(toks), jnp.int32(at),
                               jnp.int32(take - 1), cfg=cfg, pad_is_real=pad_is_real)
        out.append(np.asarray(logits[0, :take], np.float32))
        at += take
        if between:
            cache = between(cache)
    for t in range(at, len(tokens)):
        logits, *cache = step(params, cache, jnp.asarray(tokens[None, t:t + 1]), jnp.int32(t),
                              cfg=cfg)
        out.append(np.asarray(logits[0], np.float32))
        if between:
            cache = between(cache)
    return np.concatenate(out)


@functools.partial(jax.jit, static_argnames="cfg")
def seeded_params(key, *, cfg):
    return mla.init_params(cfg, key, dtype=jnp.float32)


def _seeded(cfg):
    params = seeded_params(jax.random.key(0), cfg=cfg)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, PREFILL + DECODE)
    tokens = tokens.astype(np.int32)
    sizes = reference_sizes(cfg, file_of(cfg))
    want = np.asarray(ref.forward(params, sizes, jnp.asarray(tokens)))
    return params, tokens, sizes, want


@pytest.fixture(scope="module")
def seeded():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def seeded_kmkm():
    return _seeded(CFG_KMKM)


def over_range(got, want):
    return float(np.abs(got - want).max() / (want.max() - want.min()))


def numbers(seeded, cfg=CFG, params=None, placement="pieces, the last padded", programs=SOUND,
            only=("whole", "prefill", "decode"), **how) -> dict:
    """The three numbers a fault is caught by, each a largest |logit
    difference| as a share of the reference's logit range: the uncached
    forward (`whole`: `forward_prefill` over the whole sequence), and the
    prompt's positions and the decode positions through the cache. A case
    that judges one of them names it in `only`, and the path of the others
    is not traced for it."""
    own, tokens, _, want = seeded
    params = own if params is None else params
    out = {}
    if "whole" in only:
        whole = np.asarray(programs["whole"](params, jnp.asarray(tokens[None]), cfg=cfg)[0][0])
        out["whole"] = over_range(whole, want)
    if "prefill" in only or "decode" in only:
        got = served_logits(params, cfg, tokens, PLACEMENTS[placement], programs=programs, **how)
        out["prefill"] = over_range(got[:PREFILL], want[:PREFILL])
        out["decode"] = over_range(got[PREFILL:], want[PREFILL:])
    return out


# -- (a) the program against the reference ------------------------------------


def test_the_preset_is_the_shape_the_issue_names():
    assert model_module(CFG) is mla and mla.has_kinds(CFG) and CFG.has_state_layers
    assert mla.stack_kinds(CFG) == ("dense_kda", "sparse_kda", "sparse_mla")
    assert mla.layer_order(CFG) == ((0, 0), (1, 0), (2, 0), (1, 1))
    assert (CFG.num_experts, CFG.experts_held, CFG.num_dense_layers, CFG.q_rank) == (8, 4, 1, 0)
    assert decode_counters(CFG) == ("moe_assignments_held", "moe_experts_hit", "decode_kda_slots")
    assert cache_arrays(CFG) == 3
    rows, states, tails = mla.init_kv_cache(CFG, 3, 64)
    assert rows.shape == (1, 3, 64, 128) and rows.dtype == jnp.bfloat16   # the latent layer's
    assert states.shape == (3, 3, 4, 16, 16) and states.dtype == jnp.float32
    assert tails.shape == (3, 3, 3, 3 * 4 * 16) and tails.dtype == jnp.bfloat16
    for preset in ("test-tiny-mla", "test-tiny-hc"):  # the family as it was: one array
        assert not mla.has_kinds(get_config(preset)) and cache_arrays(get_config(preset)) == 1
        assert decode_counters(get_config(preset)) == mla.DECODE_COUNTERS


def test_the_lists_that_count_layers_from_one_and_the_flat_copies_agree():
    """The configuration's file: `linear_attn_config.kda_layers` and
    `.full_attn_layers` count from 1 and are what the reference reads; the
    flat copies under `assumed` are what harness/manifest.py builds the
    program's ModelConfig from (the `rope_theta` precedent)."""
    with open(os.path.join(BENCH, "configs", "kimi-linear-48b-a3b.json")) as f:
        m = json.load(f)
    linear, assumed = m["linear_attn_config"], m["assumed"]
    assert sorted(linear["kda_layers"] + linear["full_attn_layers"]) == list(range(1, 28))
    assert assumed["layer_types"] == [
        "linear_attention" if l + 1 in linear["kda_layers"] else "full_attention"
        for l in range(27)]
    assert (assumed["kda_num_heads"], assumed["kda_head_dim"], assumed["kda_conv_kernel"]) == (
        linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"])
    assert assumed["rope_on_full_layers"] is (not m["mla_use_nope"])
    assert m["q_lora_rank"] is None and assumed["q_rank"] == 0
    run = assumed["layer_types"][:m["num_hidden_layers"]]
    assert (run.count("linear_attention"), run.count("full_attention")) == (
        m["num_kda_layers"], m["num_mla_layers"]) == (6, 2)
    sizes = {"config": m}
    assert ref.layer_order(sizes) == ((0, 0), (1, 0), (1, 1), (2, 0), (1, 2), (1, 3), (1, 4),
                                      (2, 1))
    assert ref.layer_order({"config": {**m, **m["rehearsal"]}}) == mla.layer_order(CFG)


def test_the_seeded_decay_lies_where_a_trained_models_does():
    """`a_log` = log U(1, 16) a head, `dt_bias` the inverse softplus of a step
    log-uniform in 1e-3 to 0.1: a token's decay at a zero input is between
    exp(-1.6) = 0.2 and 0.999, never near 0 (which would empty the state
    every token)."""
    params = seeded_params(jax.random.key(5), cfg=CFG)
    for stack in params["layers"][:2]:
        a = stack["attn"]
        rate = np.exp(np.asarray(a["a_log"]))
        step = np.asarray(jax.nn.softplus(a["dt_bias"]))
        assert 1.0 <= rate.min() and rate.max() <= 16.0
        assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
        alpha = np.exp(-rate[:, :, None] * step.reshape(*rate.shape, -1))
        assert 0.2 <= alpha.min() and alpha.max() <= 0.9991


def test_the_uncached_forward_agrees_with_the_reference(seeded):
    assert numbers(seeded, only=("whole",))["whole"] <= TOL


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_prefill_then_decode_through_the_cache_agrees_with_the_reference(seeded, placement):
    """The prompt in one bucket, in a padded one, or in pieces that end
    inside a chunk and whose last is padded: the state and the three-row tail
    handed from piece to piece and on to 24 decode steps equal one pass over
    the whole sequence."""
    got = numbers(seeded, placement=placement, only=("prefill", "decode"))
    assert got["prefill"] <= TOL and got["decode"] <= TOL, got


def test_a_second_latent_layer_lies_at_its_count_among_the_latent_layers(seeded_kmkm):
    assert mla.layer_order(CFG_KMKM) == ((0, 0), (2, 0), (1, 0), (2, 1))
    got = numbers(seeded_kmkm, CFG_KMKM)
    assert max(got.values()) <= TOL, got


def test_a_fresh_prefill_returns_the_state_it_would_have_written(seeded):
    """`forward_prefill`'s chunks are `prefill_insert`'s operands: rows,
    states and tails a slot's worth, the pad behind `row` in none of them,
    and decode from them agrees."""
    params, tokens, _, want = seeded
    n, bucket = 21, 32
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = tokens[:n]
    last, rows, states, tails = SOUND["fresh"](params, jnp.asarray(toks), jnp.int32(n - 1), cfg=CFG)
    assert over_range(np.asarray(last[0]), want[n - 1]) <= TOL
    assert rows.shape == (1, 1, bucket, 128) and states.shape == (3, 1, 4, 16, 16)
    assert tails.shape == (3, 1, 3, 192)
    cache = [jax.lax.dynamic_update_slice(c + 3.0, chunk, (0,) * c.ndim)  # over a poisoned slot
             for c, chunk in zip(mla.init_kv_cache(CFG, 1, 128, dtype=jnp.float32),
                                 (rows, states, tails))]
    for t in range(n, n + 12):
        logits, *cache = SOUND["step"](params, cache, jnp.asarray(tokens[None, t:t + 1]),
                                       jnp.int32(t), cfg=CFG)
        assert over_range(np.asarray(logits[0, 0]), want[t]) <= TOL, t


def test_a_model_cut_out_of_the_period_keeps_its_stacks(seeded):
    """`with_layer_order`, as harness/correct.py cuts one- and two-layer
    models: the stacks stay, some with none, and the cache has an array of
    no layers where a kind is absent."""
    cut = mla.with_layer_order(CFG, ((2, 0),))
    assert mla.stack_kinds(cut) == mla.stack_kinds(CFG) and cut.num_dense_layers == 0
    assert mla.layer_order(cut) == ((2, 0),) and cut.has_state_layers
    assert [c.shape[0] for c in mla.init_kv_cache(cut, 1, 16)] == [1, 0, 0]
    assert [c.shape[0] for c in mla.init_kv_cache(mla.with_layer_order(CFG, ((0, 0),)), 1, 16)
            ] == [0, 1, 1]
    pair = mla.with_layer_order(CFG, ((1, 0), (2, 0)))
    assert mla.layer_order(pair) == ((1, 0), (2, 0))
    with pytest.raises(ValueError, match="dense layers first"):
        mla.with_layer_order(CFG, ((1, 0), (0, 0)))
    params, tokens, sizes, _ = seeded
    sub = {**params, "layers": [jax.tree_util.tree_map(lambda a: a[:0], params["layers"][0]),
                                jax.tree_util.tree_map(lambda a: a[:1], params["layers"][1]),
                                params["layers"][2]]}
    want = np.asarray(ref.forward(sub, {**sizes, "layer_order": ((1, 0), (2, 0))},
                                  jnp.asarray(tokens)))
    got = served_logits(sub, pair, tokens, PLACEMENTS["pieces, the last padded"])
    assert over_range(got, want) <= TOL


# -- (b) the rule three ways ---------------------------------------------------


def _rule_inputs(B, T, H, d, seed=0, decay=None, shared=0.0):
    """``shared``: the mean of every channel of the draws that queries and
    keys are normalised from; at 3 two keys' product is 0.9 in the mean (a
    model's keys come out of an activation and do share a direction)."""
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (B, T, H, d)) + shared) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, d)) + shared)
    v = jax.random.normal(ks[2], (B, T, H, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, d), minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    if decay is not None:
        g = jnp.full_like(g, -decay)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, H, d, d))


# The weakest decay at d = 128 over 256 tokens: the parent's pairwise form
# reads 6.0e-6 on the state on these inputs (6.2e-6 on ISSUE 49's), the block
# form 5.8e-6; it is the order of the recurrence's own sums, not the chunk's.
WEAKEST_AT_128 = 7e-6


@pytest.mark.parametrize("T,chunk,d,decay,shared,state_tol", [
    (128, 64, 16, None, 0.0, 5e-6), (100, 64, 16, None, 0.0, 5e-6),
    (7, 64, 16, None, 0.0, 5e-6), (24, 4, 16, None, 0.0, 5e-6), (1, 64, 16, None, 0.0, 5e-6),
    (40, 24, 16, None, 0.0, 5e-6),               # a chunk that SUB does not divide
    (48, 16, 16, None, 0.0, 5e-6),               # a chunk of exactly one sub-block
    (256, 64, 128, 1.6, 0.0, 5e-6),              # the served head, the strongest decay
    (256, 64, 128, 1e-3, 0.0, WEAKEST_AT_128),   # and the weakest
    # Keys that share a direction under the weakest decay: Diag(β)·A has
    # entries near 1 all over its triangle. (I − N)(I + N²)(I + N⁴)… over the
    # whole chunk reads 8e6 on these outputs at `shared` 3 and 3e-3 at 1
    # (powers of N of 1e7 and more cancel to an inverse of order 1); the
    # parent's solve 3.8e-7 | 9.5e-6 and 4.8e-7 | 5.1e-6, the order of the
    # recurrence's own sums again, and the inverse by halves the same.
    (256, 64, 64, 1e-3, 3.0, 1.2e-5),
    (256, 64, 64, 1e-3, 1.0, WEAKEST_AT_128),
])
def test_chunk_wise_equals_per_token(T, chunk, d, decay, shared, state_tol):
    """Lengths that are and are not multiples of the chunk, chunks that are
    and are not whole sub-blocks, keys that do and do not share a direction,
    from a state that is not zero: outputs and the state left behind."""
    x = _rule_inputs(2, T, 3, d, decay=decay, shared=shared)
    o1, S1 = kda.kda_recurrent(*x)
    o2, S2 = jax.jit(lambda *a: kda.kda_chunked(*a, chunk=chunk))(*x)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-6)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S1), atol=state_tol)


def _factorised_chunk(q, k, v, g, beta, S0):
    """The textbook form, `q exp(G)` against `k exp(-G)`: one chunk."""
    G = jnp.cumsum(g, axis=1)
    qe, ke = q * jnp.exp(G), k * jnp.exp(-G)
    return jnp.einsum("bihd,bjhd->bhij", qe, ke)


def test_the_strongest_seeded_decay_overflows_the_factorised_form_and_not_this_one():
    """1.6 a token (A_log = log 16, a step of 0.1) over a 64-token chunk is
    exp(102): the factorised products are not finite in float32; differences
    of cumulative log-decays are, and equal the recurrence."""
    x = _rule_inputs(1, 128, 2, 16, seed=1, decay=1.6)
    assert not np.isfinite(np.asarray(_factorised_chunk(*x))).all()
    o1, S1 = kda.kda_recurrent(*x)
    o2, S2 = kda.kda_chunked(*x, chunk=64)
    assert np.isfinite(np.asarray(o2)).all() and np.isfinite(np.asarray(S2)).all()
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-6)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S1), atol=5e-6)


def _avals(jaxpr):
    """Every result of a jaxpr and of the jaxprs inside it (a scan's body)."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(inner)


def test_the_pairwise_decay_tensor_is_a_sub_blocks_and_never_a_chunks():
    """[C, C, dk] a head was 2 MB a chunk at the served sizes and half the
    rule's time. Now only the diagonal [SUB, SUB, dk] blocks are pairwise
    over dk, a chunk's at a time inside the scan: they are the largest
    arrays of the program, a quarter of C x C x dk, and nothing outside the
    scan is larger than its inputs."""
    B, T, H, d, C = 1, 128, 2, 8, 64
    x = _rule_inputs(B, T, H, d)
    jaxpr = jax.make_jaxpr(lambda *a: kda.kda_chunked(*a, chunk=C))(*x)
    shapes = [a.shape for a in _avals(jaxpr.jaxpr)]
    pairwise = {s[-3:] for s in shapes if len(s) >= 3 and s[-1] == d and s[-2] == s[-3]}
    assert pairwise == {(kda.SUB, kda.SUB, d)}
    blocks = B * H * (C // kda.SUB) * kda.SUB * kda.SUB * d      # one chunk's
    assert max(int(np.prod(s)) for s in shapes) == blocks == B * H * C * C * d // 4
    outside = [v.aval.size for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name != "scan"
               for v in eqn.outvars]
    assert max(outside) <= max(B * T * H * d, B * H * d * d)     # the inputs, the state


def test_the_hand_scripts_rehearsal_runs_both_forms(tmp_path, capsys):
    """`chip_kda_chunk.py --rehearse-cpu`: the parent's frozen chunk and the
    tree's at a tiny size on both draws, a line each, none called a
    measurement."""
    import chip_kda_chunk

    out = tmp_path / "kda_chunk.jsonl"
    assert chip_kda_chunk.main(["--rehearse-cpu", "--out", str(out)]) == 0
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert [(ln["draw"], ln["form"]) for ln in lines] == [
        (draw, form) for draw in chip_kda_chunk.DRAWS for form in ("parent", "tree")]
    for ln in lines:
        assert not ln["measured"] and ln["device"].startswith("cpu")
        assert ln["outputs_distance"] <= 2e-6 and ln["state_distance"] <= 5e-6
    assert capsys.readouterr().out.count("\n") == len(lines)


@pytest.mark.parametrize("route", ["jnp", "kernel"])
def test_a_decode_step_equals_the_rule_and_skips_dead_slots(route):
    """`decode_kda_state`, by `kda_step` and by the Pallas kernel
    interpreted, at the served head width (32 heads: two groups of 16), a
    middle layer of three: equal to one step of the recurrence for the live
    slots; a dead slot's state and every other layer's are bit for bit what
    they were."""
    B, H, d = 5, 32, 128
    q, k, v, g, beta, S0 = _rule_inputs(B, 1, H, d, seed=3)
    state = jnp.stack([S0 * 0.5, S0, S0 * 2])
    live = jnp.asarray([True, False, True, True, False])
    want_o, want_S = kda.kda_recurrent(q, k, v, g, beta, S0)
    o, new = kda.decode_kda_state(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                                  jnp.int32(1), live, kernel=route == "kernel",
                                  interpret=True)
    alive = np.asarray(live)
    np.testing.assert_allclose(np.asarray(o)[alive], np.asarray(want_o[:, 0])[alive], atol=2e-6)
    np.testing.assert_allclose(np.asarray(new[1])[alive], np.asarray(want_S)[alive], atol=5e-6)
    assert np.array_equal(np.asarray(new[1])[~alive], np.asarray(state[1])[~alive])
    assert np.array_equal(np.asarray(new[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(new[2]), np.asarray(state[2]))


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "interpret")
    attn._pallas_decode_mode.cache_clear()
    yield
    attn._pallas_decode_mode.cache_clear()


def test_decode_through_the_kernels_agrees_with_the_reference(seeded, interpreted):
    """Both decode kernels interpreted (the latent layer's
    `decode_mla_attention`, the linear-attention layers' `decode_kda_state`)."""
    got = numbers(seeded, placement="one bucket", programs=_programs(), only=("decode",))
    assert got["decode"] <= TOL, got


# -- (c) planted faults --------------------------------------------------------


def _rounded(which):
    """`kda_chunked` and `decode_kda_state` with the state (in and out) or the
    log-decay rounded to bfloat16."""
    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    def chunked(q, k, v, g, beta, S):
        if which == "decay":
            return kda.kda_chunked(q, k, v, bf16(g), beta, S)
        o, S = kda.kda_chunked(q, k, v, g, beta, bf16(S))
        return o, bf16(S)

    def state(states, q, k, v, g, beta, layer, live=None, **how):
        if which == "decay":
            return kda.decode_kda_state(states, q, k, v, bf16(g), beta, layer, live, **how)
        o, states = kda.decode_kda_state(bf16(states), q, k, v, g, beta, layer, live, **how)
        return o, bf16(states)

    return chunked, state


def _decay_after_the_update(q, k, v, g, beta, S0):
    """S ← Diag(α)·(S + β k (v − Sᵀk)ᵀ): the decay behind the update."""
    def body(S, x):
        q, k, v, g, beta = x
        r = jnp.einsum("bhkv,bhk->bhv", S, k)
        S = S + k[..., :, None] * (beta[..., None] * (v - r))[..., None, :]
        S = S * jnp.exp(g)[..., :, None]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    S, o = jax.lax.scan(body, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _drop(index):
    def between(cache):
        cache = list(cache)
        cache[index] = jnp.zeros_like(cache[index])
        return tuple(cache)
    return between


def _never_fresh(write_start):
    return jnp.zeros_like(write_start, dtype=bool)


_RUNS = mla.kinds.runs


def _runs_by_model_index(cfg, all_kinds, names=None):
    """`kinds.runs` with a layer's index in the MODEL as its cache index."""
    found, at = [], 0
    for stack, kind, first, length, _ in _RUNS(cfg, all_kinds, names):
        found.append((stack, kind, first, length, at))
        at += length
    return found


def _patch_rule(which):
    chunked, state = _rounded(which)
    return [(mla, "kda_chunked", chunked), (mla, "decode_kda_state", state)]


# name -> (the number it must show in, which model, what to replace in its
# config, [(module, attribute, replacement)] to patch, keywords for
# `served_logits`)
FAULTS = {
    "a padded piece's pad rows enter the state and the tail": (
        "decode", CFG, {}, [], {"pad_is_real": True}),
    "the state is not handed from piece to piece": (
        "prefill", CFG, {}, [], {"between": _drop(1)}),
    "the convolution's tail is not handed from piece to piece": (
        "prefill", CFG, {}, [], {"between": _drop(2)}),
    "a first piece keeps the last tenant's state and tail": (
        "prefill", CFG, {}, [(mla, "_fresh", _never_fresh)], {"poison": True}),
    "the decay behind the update": (
        "whole", CFG, {}, [(mla, "kda_chunked", _decay_after_the_update)], {}),
    "the layer lists read as counting from 0": (
        "whole", CFG, {"layer_types": ("linear_attention", "full_attention",
                                       "linear_attention", "linear_attention")}, [], {}),
    "the latent cache indexed by the model's layer": (
        "decode", CFG_KMKM, {}, [(mla.kinds, "runs", _runs_by_model_index)], {}),
    "rotary position on the latent layer": ("whole", CFG, {"rope_on_full_layers": True}, [], {}),
    "s + b used as a weight": (
        "whole", CFG, {}, [(moe, "top_k_weights", lambda logits, k, scoring="softmax", bias=None: (
            lambda w, i: (w / w.sum(-1, keepdims=True), i))(
                *jax.lax.top_k(jax.nn.sigmoid(logits) + bias.astype(logits.dtype), k)))], {}),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_by_a_hundred_tolerances(seeded, seeded_kmkm, fault, monkeypatch):
    number, cfg, replace, patches, how = FAULTS[fault]
    for patch in patches:
        monkeypatch.setattr(*patch)
    got = numbers(seeded_kmkm if cfg is CFG_KMKM else seeded, dataclasses.replace(cfg, **replace),
                  programs=_programs() if patches else SOUND, only=(number,), **how)
    assert got[number] >= 100 * TOL, (fault, got)


@pytest.mark.parametrize("which", ["state", "decay"])
def test_a_state_or_a_decay_in_bfloat16_fails_the_tolerance(seeded, which, monkeypatch):
    """The configuration states a float32 state and float32 decays. Rounded
    to bfloat16 (the state on its way in and out of every piece and step;
    the log-decay before the rule) the decode positions read 2e-3 and 2.6e-4
    of the logit range here: both fail TOL by more than ten times, so
    neither precision can pass for the other."""
    for patch in _patch_rule(which):
        monkeypatch.setattr(*patch)
    got = numbers(seeded, programs=_programs(), only=("prefill", "decode"))
    assert got["decode"] >= 10 * TOL and got["prefill"] >= 10 * TOL, (which, got)


def test_the_sound_run_passes_where_each_fault_is_looked_for(seeded, seeded_kmkm):
    got = numbers(seeded, poison=True)  # whatever the last tenant left
    assert max(got.values()) <= TOL, got
    assert max(numbers(seeded_kmkm, CFG_KMKM).values()) <= TOL


def test_a_dead_slots_decode_step_leaves_its_state_and_tail_alone(seeded, monkeypatch):
    """A slot that is not live is between tenants or between its placement's
    pieces while other slots decode: a decode step leaves its state and its
    tail bit for bit (nothing masks a state by position afterwards), and the
    counter counts the live slots' states alone. The planted fault, `live`
    not passed on, changes both by far more than a hundred tolerances."""
    params, tokens, _, _ = seeded
    cache = tuple(c + 1.0 for c in mla.init_kv_cache(CFG, 2, 32, dtype=jnp.float32))
    live = jnp.asarray([True, False])

    @jax.jit
    def step(live):
        return mla.forward(params, CFG, jnp.asarray(tokens[:2, None]),
                           jnp.full((2, 1), 11, jnp.int32), *cache,
                           jnp.full((2,), 11, jnp.int32), live=live, counters=True)

    _, rows, states, tails, counts = step(live)
    assert np.all(np.asarray(states[:, 1]) == 1.0) and np.all(np.asarray(tails[:, 1]) == 1.0)
    assert np.abs(np.asarray(states[:, 0]) - 1.0).max() > 100 * TOL
    assert np.abs(np.asarray(tails[:, 0, -1]) - 1.0).max() > 100 * TOL
    assert int(counts[2]) == 3                           # one live slot, three KDA layers
    _, _, states, tails, counts = step(None)             # the fault
    assert np.abs(np.asarray(states[:, 1]) - 1.0).max() > 100 * TOL
    assert np.abs(np.asarray(tails[:, 1]) - 1.0).max() > 100 * TOL and int(counts[2]) == 6


def test_decode_steps_between_a_placements_pieces_do_not_reach_its_state(seeded):
    """Slot 0 is placed in two pieces through the engine's own programs
    (`extend_nosample`, `extend`: `_take_slot` / `_put_back` move a state's
    axis 2, the heads, whole) while slot 1 decodes in between: slot 0's cache
    is what two pieces back to back leave (its latent row of that step lands
    at its frontier, where the scheduler parks a placing slot's position and
    the next piece overwrites it), and its first piece starts from zero
    whatever the slot's last tenant left there."""
    params, tokens, _, _ = seeded
    ecfg = EngineConfig(num_slots=2, max_seq=64, prefill_buckets=(16, 32), max_sessions=0,
                        decode_chunk=1, dtype="float32")
    programs = build_programs(CFG, ecfg, None)

    def place(cache, slot, between):
        toks = lambda lo, n, b: (np.pad(tokens[lo:lo + n], (0, b - n))[None].astype(np.int32),
                                 (lo + np.arange(b, dtype=np.int32))[None])
        cache = programs.extend_nosample(params, *cache, *toks(0, 16, 16), np.int32(slot),
                                         np.int32(0))
        cache = between(tuple(cache))
        *cache, tok, _ = programs.extend(
            params, *cache, *toks(16, 9, 16), np.int32(slot), np.int32(16), np.int32(8),
            jnp.zeros((2,), jnp.uint32), np.float32(0), np.float32(1), np.int32(0))
        return tuple(cache), int(tok)

    def decode_slot_1(cache):
        from omnia_tpu.engine.types import MAX_DEVICE_STOP_IDS

        B = 2
        out = programs.decode_fns[1](
            params, *cache, jnp.asarray([0, 7], jnp.int32), jnp.asarray([16, 5], jnp.int32),
            jnp.asarray([False, True]), jnp.full((B,), 9, jnp.int32),
            jnp.full((B, MAX_DEVICE_STOP_IDS), -1, jnp.int32), jnp.zeros((B, 2), jnp.uint32),
            jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.int32))
        return tuple(out[:3])

    def fresh():
        return tuple(c + 2.0 for c in mla.init_kv_cache(CFG, 2, 64, dtype=jnp.float32))

    plain, tok_plain = place(fresh(), 0, lambda c: c)
    mixed, tok_mixed = place(fresh(), 0, decode_slot_1)
    assert tok_plain == tok_mixed
    for a, b in zip(plain[1:], mixed[1:]):               # slot 0's states and tails
        assert np.array_equal(np.asarray(a[:, 0]), np.asarray(b[:, 0]))
    # and they are a zero-started pass's, not the poisoned slot's
    _, _, states, tails = SOUND["whole"](params, jnp.asarray(tokens[None, :25]), cfg=CFG)
    np.testing.assert_allclose(np.asarray(plain[1][:, 0]), np.asarray(states[:, 0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(plain[2][:, 0]), np.asarray(tails[:, 0]), atol=1e-5)


# -- (d) the share -------------------------------------------------------------


@pytest.mark.parametrize("ranks", [1, 2, 8])
def test_the_shares_routed_parts_and_the_shared_expert_once_equal_the_uncut_layer(seeded, ranks):
    """`model-configs` section 4 through this family's own stacks
    (`expert_ffn` on a sparse stack of `mla.init_params`): what each of
    `ranks` chips computes for the experts it holds, less the shared expert
    that every chip computes alike, summed over the chips, plus the shared
    expert once, is the layer of a chip that holds all 8, which is the
    reference's uncut layer."""
    whole = dataclasses.replace(CFG, num_experts_held=0, expert_rank=0)
    params = seeded_params(jax.random.key(4), cfg=whole)
    stack = params["layers"][1]
    scanned, experts = moe.unstack_experts(stack)
    mlp = jax.tree_util.tree_map(lambda a: a[0], scanned["mlp"])
    h = jax.random.normal(jax.random.key(5), (1, 24, CFG.hidden_size))
    shared = moe.swiglu(h, mlp["shared"])
    held = CFG.num_experts // ranks
    total, assignments = shared, 0
    for rank in range(ranks):
        cfg = dataclasses.replace(CFG, num_experts_held=held, expert_rank=rank)
        mine = {k: v[:, rank * held:(rank + 1) * held] for k, v in experts.items()}
        y, counts = moe.expert_ffn(h, mlp, mine, 0, cfg)
        total = total + (y - shared)
        assignments += int(counts[0])
    assert assignments == 24 * CFG.num_experts_per_tok  # each lands on exactly one chip
    sizes = reference_sizes(whole, file_of(whole))
    layer = jax.tree_util.tree_map(lambda a: a[0], stack["mlp"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._experts(h[0], layer, sizes, jnp.float32)[0])
    np.testing.assert_allclose(np.asarray(total[0]), want, atol=2e-6, rtol=1e-4)


# -- (e) through the engine ---------------------------------------------------


def test_the_engine_serves_it_through_pieces_states_and_reused_slots():
    """`InferenceEngine` on the normal path: prompts longer than the largest
    bucket (placed through `extend` in pieces whose last is padded, the
    state handed from piece to piece), one that fits a bucket
    (`prefill_insert`), 24 decode steps each, and two more rounds of requests
    into the same two slots: a state must not leak the previous tenant's.
    Every served token is the largest logit of the module's own uncached
    forward over the tokens before it, to within the two paths' rounding."""
    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(16, 32), max_sessions=0,
                        decode_chunk=4, dtype="float32")
    engine = InferenceEngine(CFG, ecfg, seed=3)
    assert engine.model_module is mla and len(engine._cache) == 3
    assert engine.kv_bytes_per_token() == 1 * 128 * 4         # the latent layer's rows alone
    engine.warmup()
    engine.start()
    rng = np.random.default_rng(0)
    forward = jax.jit(lambda p, t: mla.forward_prefill(
        p, CFG, t, jnp.arange(t.shape[1], dtype=jnp.int32)[None])[0])
    try:
        for _ in range(3):
            prompts = [[int(t) for t in rng.integers(0, 256, size=n)] for n in (45, 70, 13)]
            handles = [engine.submit(p, SamplingParams(max_tokens=24, temperature=0.0,
                                                       stop_token_ids=())) for p in prompts]
            for prompt, handle in zip(prompts, handles):
                out = [ev.token_id for ev in handle.events()
                       if getattr(ev, "token_id", None) is not None]
                assert len(out) == 24
                logits = np.asarray(forward(engine.params, jnp.asarray([prompt + out]))[0])
                rows = logits[len(prompt) - 1:len(prompt) + 23]
                assert np.all(rows.max(-1) - rows[np.arange(24), out] <= 1e-4)
    finally:
        engine.stop()
    m = engine.metrics
    assert m["extend_steps"] > 0 and m["decode_steps"] > 0
    assert 0 < m["moe_experts_hit"] <= m["decode_steps"] * 3 * CFG.experts_held
    # every live slot's state is updated once a linear-attention layer a step
    # (the device counts the slots live at each step, the host those at dispatch)
    assert 0 < m["decode_kda_slots"] <= 3 * m["decode_slot_steps"]


# -- (f) what is refused -------------------------------------------------------

REFUSED = {"max_sessions": {"max_sessions": 4}, "prefix_cache_slots": {"prefix_cache_slots": 2},
           "kv_pages": {"kv_pages": 8}, "spec_decode": {"spec_decode": 4},
           "prefill_chunk_tokens": {"prefill_chunk_tokens": 64}}


@pytest.mark.parametrize("feature", list(REFUSED))
def test_a_model_with_a_recurrent_state_refuses_what_assumes_rows_by_name(feature):
    """A state has no rows to offload, seed, page or roll back: each of the
    five is refused with its reason, where the latent family without a state
    raises with none."""
    ecfg = EngineConfig(**{"num_slots": 2, "max_seq": 256, "prefill_buckets": (64,),
                           "max_sessions": 0, **REFUSED[feature]})
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}=.*not ported to "
                                                  r"the latent-attention family.*\): \w.*stat"):
        refuse_unported(CFG, ecfg)
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}="):
        InferenceEngine(CFG, ecfg)
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}=.*test-tiny-mla'\)$"):
        refuse_unported(get_config("test-tiny-mla"), ecfg)


# -- (g) models of this family that have no kinds are what they were ----------

# Each program's lowered text (StableHLO) by its number of lines, of operations,
# and a digest of how many there are of each operation, as the parent commit
# (1fc7519) gave them for these shapes; `python tests/test_kimi_linear.py`
# prints them anew.
ALIKE = EngineConfig(num_slots=4, max_seq=256, prefill_buckets=(32,), max_sessions=0,
                     decode_chunk=4)


def _lowered(name: str, preset: str):
    from omnia_tpu.engine.types import MAX_DEVICE_STOP_IDS

    cfg = get_config(preset)
    programs = build_programs(cfg, ALIKE, None)
    params = jax.eval_shape(lambda: mla.init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: mla.init_kv_cache(cfg, ALIKE.num_slots, ALIKE.max_seq))
    B = ALIKE.num_slots

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((B, *tail), dtype)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    tokens = (arg(jnp.int32, 1, 32), arg(jnp.int32, 1, 32))
    if name == "decode_chunk":
        lowered = programs.decode_fns[4].lower(
            params, *cache, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), vec(jnp.int32),
            vec(jnp.int32, MAX_DEVICE_STOP_IDS), vec(jnp.uint32, 2), vec(jnp.float32),
            vec(jnp.float32), vec(jnp.int32))
    elif name == "extend_nosample":
        lowered = programs.extend_nosample.lower(params, *cache, *tokens, arg(jnp.int32),
                                                 arg(jnp.int32))
    else:
        lowered = programs.prefill_insert.lower(
            params, *cache, *tokens, arg(jnp.int32), arg(jnp.int32), arg(jnp.uint32, 2),
            arg(jnp.float32), arg(jnp.float32), arg(jnp.int32))
    return lowered.as_text()


def _census(text: str) -> list:
    import collections
    import hashlib
    import re

    ops = collections.Counter(re.findall(r"= \"?((?:stablehlo|func|chlo)\.[\w.]+)", text))
    digest = hashlib.sha256(json.dumps(dict(ops), sort_keys=True).encode()).hexdigest()[:16]
    return [len(text.splitlines()), sum(ops.values()), digest]


PARENT_PROGRAMS = {
    "test-tiny-mla.decode_chunk": [
        1308,
        1135,
        "d12f67153f293c56"
    ],
    "test-tiny-mla.prefill_insert": [
        1178,
        1015,
        "a094d718deb62f0f"
    ],
    "test-tiny-mla.extend_nosample": [
        678,
        601,
        "db3886b5a6f067ac"
    ],
    "test-tiny-hc.decode_chunk": [
        13094,
        12905,
        "c4b3b257518fcfd7"
    ],
    "test-tiny-hc.prefill_insert": [
        12878,
        12699,
        "178dbb88e51720d2"
    ],
    "test-tiny-hc.extend_nosample": [
        12388,
        12295,
        "52182a4f9ca67b84"
    ]
}

PROGRAMS = ("decode_chunk", "prefill_insert", "extend_nosample")


@pytest.mark.parametrize("preset", ["test-tiny-mla", "test-tiny-hc"])
@pytest.mark.parametrize("program", PROGRAMS)
def test_a_model_without_kinds_compiles_to_the_parents_program(program, preset):
    """One tree of layers all alike (`test-tiny-mla`) and the two stacks of a
    leading dense layer behind four residual copies (`test-tiny-hc`): the
    decode chunk, the fresh prefill and a placement's piece lower to the
    parent's text, line for line."""
    assert _census(_lowered(program, preset)) == PARENT_PROGRAMS[f"{preset}.{program}"]


if __name__ == "__main__":
    print(json.dumps({f"{preset}.{program}": _census(_lowered(program, preset))
                      for preset in ("test-tiny-mla", "test-tiny-hc")
                      for program in PROGRAMS}, indent=1))
