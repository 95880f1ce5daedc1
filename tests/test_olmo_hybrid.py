"""The pair family with linear-attention layers (models/stacks.py: the gated
delta rule with one decay a head, ops/delta.py, keys and values of different
widths, beside un-rotated full attention with a QK-norm over the whole width;
both of a block's norms on its sublayers' outputs; float32 states and
convolution tails in the slot's cache beside the full layers' K and V) against
the plain reference of the model it was written for,
`benchmark/reference/olmo_hybrid_ref.py`, at `test-tiny-delta`'s size: L L L F,
six heads of 8 x 16.

Logits are compared and never tokens. Everything is float32 on the CPU, so
the program and the reference differ by the order of their sums alone: TOL
is 1e-5 of the reference's logit range (readings here are 1e-6 and under; the
chunk-wise rule against the recurrence reads 1e-6 absolute on outputs of 2),
and every planted fault has to move the number named for it by a hundred
times that. A state rounded to bfloat16 is among the faults."""
import dataclasses
import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.engine.engine import InferenceEngine
from omnia_tpu.engine.family import refuse_unported
from omnia_tpu.engine.types import EngineConfig, SamplingParams
from omnia_tpu.models import cache_arrays, decode_counters, get_config, llama, model_module, stacks
from omnia_tpu.ops import attention as attn
from omnia_tpu.ops import delta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.manifest import reference_sizes  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "olmo_hybrid_ref", os.path.join(BENCH, "reference", "olmo_hybrid_ref.py"))
ref = importlib.util.module_from_spec(_spec)  # the benchmark's plain reference
_spec.loader.exec_module(ref)

CFG = get_config("test-tiny-delta")
# The same model with ten un-grouped heads: a cached row then holds sixteen
# (`stacks.cache_kv_heads`), six of them zeros.
CFG_TEN = dataclasses.replace(CFG, hidden_size=160, num_heads=10, num_kv_heads=10)
# The same model with values 64 wide: two heads' are a whole 128 lanes, so the
# cache holds its states two heads side by side (`stacks.state_heads_a_row`).
CFG_PACKED = dataclasses.replace(CFG, linear_value_head_dim=64)
PREFILL, DECODE = 40, 24
TOL = 1e-5
# How a prompt of PREFILL tokens is placed: (real rows, bucket) a piece. The
# tests' chunk is 8 tokens, so 12 and 20 are no multiples of it.
PLACEMENTS = {
    "one bucket": [(PREFILL, PREFILL)],
    "pieces of unequal length, the last padded": [(12, 12), (20, 20), (8, 16)],
    "one padded piece": [(PREFILL, 64)],
    "two pieces, the second padded": [(24, 24), (16, 32)],
}
CHUNK = 8


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """Chunks of 8 tokens, so that 40 tokens are five chunks and a piece's
    end falls inside one."""
    monkeypatch.setattr(delta, "CHUNK", CHUNK)
    monkeypatch.setattr(delta.delta_chunked, "__defaults__", (CHUNK,))


def file_of(cfg) -> dict:
    """The keys of a configuration file that the reference reads, for `cfg`."""
    return {
        "num_hidden_layers": cfg.num_layers, "layer_types": list(cfg.layer_types),
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "linear_num_key_heads": cfg.linear_num_heads,
        "linear_num_value_heads": cfg.linear_num_heads,
        "linear_key_head_dim": cfg.linear_key_head_dim,
        "linear_value_head_dim": cfg.linear_value_head_dim,
        "linear_conv_kernel_dim": cfg.linear_conv_kernel,
        "linear_allow_neg_eigval": cfg.linear_allow_neg_eigval,
        "rope_parameters": {"rope_theta": None},
        "assumed": {"head_dim": cfg.head_dim, "l2norm_eps": 1e-6, "norm_placement": "post",
                    "qk_norm_whole": True},
    }


def _programs():
    """`step`, `piece` and `whole` under `jax.jit`, the configuration a static
    argument: new functions a call, so traced anew. SOUND is the set every
    case on the sound path shares; a case that patches a function of the
    model, or routes the kernels, makes its own."""
    def step(p, c, toks, start, *, cfg):
        pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        return llama.forward(p, cfg, toks, pos, *c, jnp.reshape(start, (1,)))

    def piece(p, c, toks, start, last, *, cfg, pad_is_real):
        """Every row's logits, the cache written as a placement writes it."""
        pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        every, *_ = llama.forward(p, cfg, toks, pos, *c, jnp.reshape(start, (1,)))
        _, *c = llama.forward(p, cfg, toks, pos, *c, jnp.reshape(start, (1,)),
                              row=None if pad_is_real else last)
        return every, *c

    def whole(p, toks, *, cfg):
        """The engine's fresh prefill over the whole sequence."""
        return llama.forward_prefill(p, cfg, toks,
                                     jnp.arange(toks.shape[1], dtype=jnp.int32)[None])

    return {"step": jax.jit(step, static_argnames="cfg"),
            "piece": jax.jit(piece, static_argnames=("cfg", "pad_is_real")),
            "whole": jax.jit(whole, static_argnames="cfg")}


SOUND = _programs()


def served_logits(params, cfg, tokens, placement, rows: int = 128, pad_is_real=False,
                  between=None, poison=False, programs=SOUND):
    """The prompt placed into a cache piece by piece (a padded piece names
    its last real row, as engine/programs.py::extend does), then one token a
    step through the cache: float32 [T, V]. `between(cache)` stands between
    two calls; `poison` starts from a cache another tenant has left full."""
    cache = llama.init_kv_cache(cfg, 1, rows, dtype=params["embed"].dtype)
    if poison:
        cache = tuple(c + 3.0 for c in cache)
    out, at = [], 0
    for take, bucket in placement:
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :take] = tokens[at:at + take]
        logits, *cache = programs["piece"](params, cache, jnp.asarray(toks), jnp.int32(at),
                                           jnp.int32(take - 1), cfg=cfg, pad_is_real=pad_is_real)
        out.append(np.asarray(logits[0, :take], np.float32))
        at += take
        if between:
            cache = between(cache)
    for t in range(at, len(tokens)):
        logits, *cache = programs["step"](params, cache, jnp.asarray(tokens[None, t:t + 1]),
                                          jnp.int32(t), cfg=cfg)
        out.append(np.asarray(logits[0], np.float32))
        if between:
            cache = between(cache)
    return np.concatenate(out)


@functools.partial(jax.jit, static_argnames="cfg")
def seeded_params(key, *, cfg):
    return llama.init_params(cfg, key, dtype=jnp.float32)


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


def over_range(got, want):
    return float(np.abs(got - want).max() / (want.max() - want.min()))


def numbers(seeded, cfg=CFG, params=None, programs=SOUND,
            placement="pieces of unequal length, the last padded",
            only=("whole", "prefill", "decode"), **how) -> dict:
    """The three numbers a fault is caught by, each a largest |logit
    difference| as a share of the reference's logit range: the fresh prefill
    (`whole`: `forward_prefill` over the whole sequence), and the prompt's
    positions and the decode positions through the cache."""
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
    assert CFG.attention_kinds == ("delta", "delta", "delta", "full")
    assert CFG.has_state_layers and not CFG.has_window_layers and not CFG.is_latent
    assert llama.is_stacked(CFG) and model_module(CFG) is llama
    assert llama.stack_kinds(CFG) == ("dense_full", "dense_delta")
    assert llama.layer_order(CFG) == ((1, 0), (1, 1), (1, 2), (0, 0))
    assert [(kind, n) for _, kind, _, n, _ in stacks._runs(CFG)] == [
        ("dense_delta", 3), ("dense_full", 1)]
    assert (CFG.linear_num_heads, CFG.linear_key_head_dim, CFG.linear_value_head_dim) == (6, 8, 16)
    assert CFG.linear_allow_neg_eigval and CFG.norm_placement == "post" and CFG.qk_norm_whole
    assert cache_arrays(CFG) == 4 and decode_counters(CFG) == ("decode_delta_slots",)
    cache = jax.eval_shape(lambda: llama.init_kv_cache(CFG, 2, 32, dtype=jnp.bfloat16))
    assert [(c.shape, c.dtype.name) for c in cache] == [
        ((1, 2, 32, 6, 16), "bfloat16"), ((1, 2, 32, 6, 16), "bfloat16"),
        ((3, 2, 6, 8, 16), "float32"), ((3, 2, 3, 6 * 32), "bfloat16")]
    assert stacks.state_heads_a_row(CFG) == 1 and stacks.state_shape(CFG) == (6, 8, 16)
    assert stacks.cache_kv_heads(CFG) == 6 and stacks.cache_kv_heads(CFG_TEN) == 16
    assert stacks.cache_kv_heads(dataclasses.replace(CFG, num_kv_heads=30)) == 32
    # the latent family's linear kind keeps its name
    assert set(get_config("test-tiny-kda").attention_kinds) == {"kda", "full"}
    tree = jax.eval_shape(lambda: llama.init_params(CFG, jax.random.key(0)))
    assert CFG.num_params() == sum(x.size for x in jax.tree.leaves(tree))
    assert tree["layers"][0]["attn"]["qn"].shape == (1, CFG.q_dim)


def test_the_seeded_decay_lies_where_a_trained_models_does(seeded):
    a = seeded[0]["layers"][1]["attn"]
    alpha = np.exp(-np.exp(np.asarray(a["a_log"])) * np.log1p(np.exp(np.asarray(a["dt_bias"]))))
    assert 0.15 < alpha.min() and alpha.max() < 0.9995 and a["a_log"].shape == (3, 6)


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_prefill_then_decode_through_the_cache_agrees_with_the_reference(seeded, placement):
    """The fresh prefill whole, and the prompt placed whole, in pieces of
    unequal length and in one padded piece, then decode through the cache."""
    got = numbers(seeded, placement=placement,
                  only=("whole", "prefill", "decode") if placement == "one bucket"
                  else ("prefill", "decode"))
    assert max(got.values()) <= TOL, got


def test_ten_heads_are_cached_as_sixteen_and_agree_with_the_reference():
    seeded = _seeded(CFG_TEN)
    cache = llama.init_kv_cache(CFG_TEN, 1, 8)
    assert cache[0].shape == (1, 1, 8, 16, 16)
    got = numbers(seeded, CFG_TEN)
    assert max(got.values()) <= TOL, got


def _with_heads(H, dk, dv):
    return dataclasses.replace(CFG, linear_num_heads=H, linear_key_head_dim=dk,
                               linear_value_head_dim=dv)


@pytest.mark.parametrize("H,dv,p", [
    (30, 192, 2),     # the served heads: 384 lanes, three whole tiles
    (6, 64, 2), (8, 32, 4), (3, 128, 1), (4, 256, 1),
    (6, 16, 1),       # no divisor of 6 makes 16s a whole 128
    (5, 192, 1),      # 2 does not divide 5, and 5 x 192 is no whole 128
    (7, 96, 1),
    (12, 96, 4),      # the smallest that does, not the largest
])
def test_heads_share_a_row_of_lanes_where_that_fills_whole_tiles(H, dv, p):
    """`state_heads_a_row` from shapes alone, and the cache's states in it:
    the same elements, slots on axis 1."""
    cfg = _with_heads(H, 8, dv)
    assert stacks.state_heads_a_row(cfg) == p
    assert stacks.state_shape(cfg) == (H // p, 8, p * dv)
    states = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 2, 16))[2]
    assert states.shape == (3, 2, H // p, 8, p * dv) and states.dtype == jnp.float32


@pytest.mark.parametrize("p", [1, 2, 3])
def test_pack_then_unpack_is_the_identity(p):
    """... and packed head j holds heads j p ... j p + p - 1 side by side."""
    S = jax.random.normal(jax.random.key(p), (2, 3, 6, 8, 16))
    packed = delta.pack_state(S, p)
    assert packed.shape == (2, 3, 6 // p, 8, p * 16)
    assert np.array_equal(np.asarray(delta.unpack_state(packed, p)), np.asarray(S))
    for c in range(p):
        assert np.array_equal(np.asarray(packed[..., 1, :, c * 16:(c + 1) * 16]),
                              np.asarray(S[..., p + c, :, :]))


@pytest.mark.parametrize("route", ["jnp", "kernels"])
def test_two_heads_a_row_through_pieces_and_decode_agree_with_the_reference(route, request):
    """`CFG_PACKED` (states [3, B, 3, 8, 128]): the prompt in two pieces, the
    second padded (each unpacks its slot's state in front of the chunk-wise
    rule and packs it behind), then decode through the packed cache, by
    `delta_step` and by the kernel interpreted, against the reference's
    recurrence; and a fresh prefill returns the state packed, the one the
    pieces leave."""
    assert stacks.state_heads_a_row(CFG_PACKED) == 2
    if route == "kernels":
        request.getfixturevalue("interpreted")
    seeded, programs, caches = _seeded(CFG_PACKED), _programs(), []
    got = numbers(seeded, CFG_PACKED, programs=programs, only=("prefill", "decode"),
                  placement="two pieces, the second padded",
                  between=lambda cache: caches.append(cache) or cache)
    assert max(got.values()) <= TOL, got
    placed = caches[1][2]                 # the states behind the second piece
    params, tokens, _, _ = seeded
    _, _, _, states, _ = programs["whole"](params, jnp.asarray(tokens[None, :PREFILL]),
                                           cfg=CFG_PACKED)
    assert states.shape == placed.shape == (3, 1, 3, 8, 128)
    np.testing.assert_allclose(np.asarray(states), np.asarray(placed), atol=1e-5)


def test_a_fresh_prefill_returns_the_state_it_would_have_written(seeded):
    """`forward_prefill` over a padded bucket gives a slot's worth of every
    cache array: the state and the tail are those of the real rows alone."""
    params, tokens, _, _ = seeded
    fresh = jax.jit(lambda p, t, row: llama.forward_prefill(
        p, CFG, t, jnp.arange(t.shape[1], dtype=jnp.int32)[None], row=row))
    padded = np.zeros((1, 32), np.int32)
    padded[0, :21] = tokens[:21]
    _, k, v, states, tails = fresh(params, jnp.asarray(padded), jnp.int32(20))
    _, k2, v2, states2, tails2 = SOUND["whole"](params, jnp.asarray(tokens[None, :21]), cfg=CFG)
    assert states.shape == (3, 1, 6, 8, 16) and tails.shape == (3, 1, 3, 192)
    np.testing.assert_allclose(np.asarray(states), np.asarray(states2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(tails), np.asarray(tails2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(k[:, :, :21]), np.asarray(k2), atol=1e-5)


# -- (b) the rule three ways ---------------------------------------------------


def _rule_inputs(B, T, H, dk, dv, seed, decay=(1e-3, 1.6), shared=False, beta_shift=2.0):
    """q, k L2-normed, beta = 2 sigmoid(. + beta_shift) (near 2 at the
    default), log-decays log-uniform in `decay`; `shared`: every key a small
    step from one direction, the case that breaks a closed-form inverse."""
    ks = jax.random.split(jax.random.key(seed), 7)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk))
    if shared:
        k = jax.random.normal(ks[5], (B, 1, H, dk)) + 0.05 * k
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H), minval=np.log(decay[0]),
                                    maxval=np.log(decay[1])))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)) + beta_shift)
    S0 = 0.1 * jax.random.normal(ks[6], (B, H, dk, dv))
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("T,chunk,dk,dv,decay,shared", [
    (150, 64, 8, 16, (1e-3, 1.6), False),     # a last chunk of 22
    (40, 8, 8, 16, (1e-3, 1.6), False),
    (128, 64, 96, 192, (1e-3, 1.6), False),   # the served head
    (128, 64, 24, 48, (1e-4, 1e-3), True),    # weak decay, keys that share a direction
    (7, 64, 8, 16, (1e-3, 1.6), False),       # shorter than a chunk
])
def test_chunk_wise_equals_per_token(T, chunk, dk, dv, decay, shared):
    """`delta_chunked` against `delta_recurrent` at beta near 2, from a state
    that is not zero: outputs and the state handed on. Keys that share a
    direction under a weak decay are PR 49's case (the inverse by halves
    stays at 1e-5; a closed product of powers would not)."""
    q, k, v, g, beta, S0 = _rule_inputs(2, T, 3, dk, dv, seed=T, decay=decay, shared=shared)
    assert float(beta.max()) > 1.9
    want_o, want_S = delta.delta_recurrent(q, k, v, g, beta, S0)
    o, S = delta.delta_chunked(q, k, v, g, beta, S0, chunk)
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(o - want_o).max()) <= (2e-4 if shared else 2e-5) * max(scale, 1.0)
    assert float(jnp.abs(S - want_S).max()) <= (2e-4 if shared else 2e-5) * max(
        float(jnp.abs(want_S).max()), 1.0)


def test_a_row_with_no_strength_and_no_decay_leaves_the_state_alone():
    q, k, v, g, beta, S0 = _rule_inputs(1, 16, 2, 8, 16, seed=5)
    real = jnp.arange(16) < 11
    g, beta = jnp.where(real[None, :, None], g, 0.0), jnp.where(real[None, :, None], beta, 0.0)
    _, S = delta.delta_chunked(q, k, v, g, beta, S0, 8)
    _, want = delta.delta_recurrent(q[:, :11], k[:, :11], v[:, :11], g[:, :11], beta[:, :11], S0)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("route", ["jnp", "kernel"])
@pytest.mark.parametrize("H,dk,dv,p", [
    (30, 96, 192, 2),   # the served heads, two a row: 15 packed heads, three blocks of 5
    (4, 16, 128, 1),    # a 128-wide value: a head a row, the plain [L, B, H, dk, dv]
    (5, 8, 192, 1),     # no divisor of 5 fills whole tiles: a head a row
    (8, 8, 32, 4),      # four a row (eleven rows of step vectors: two tiles)
])
def test_a_decode_step_equals_the_rule_and_skips_dead_slots(route, H, dk, dv, p):
    """`decode_delta_state`, by `delta_step` and by the Pallas kernel
    interpreted, over the state in the layout the cache holds for those
    shapes (`stacks.state_heads_a_row`), a middle layer of three: equal to
    one step of the recurrence for the live slots; a dead slot's packed
    blocks and every other layer's are bit for bit what they were."""
    B = 5
    assert stacks.state_heads_a_row(_with_heads(H, dk, dv)) == p
    assert delta.head_block(15, 96, 384) == 5 and delta.head_block(6, 8, 16) == 6
    q, k, v, g, beta, S0 = _rule_inputs(B, 1, H, dk, dv, seed=3)
    state = delta.pack_state(jnp.stack([S0 * 0.5, S0, S0 * 2]), p)
    assert state.shape == (3, B, H // p, dk, p * dv)
    live = jnp.asarray([True, False, True, True, False])
    want_o, want_S = delta.delta_recurrent(q, k, v, g, beta, S0)
    o, new = delta.decode_delta_state(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                                      jnp.int32(1), live, kernel=route == "kernel",
                                      interpret=True)
    alive = np.asarray(live)
    np.testing.assert_allclose(np.asarray(o)[alive], np.asarray(want_o[:, 0])[alive], atol=2e-6)
    np.testing.assert_allclose(np.asarray(delta.unpack_state(new[1], p))[alive],
                               np.asarray(want_S)[alive], atol=5e-6)
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
    """Both decode kernels interpreted (the full layer's `decode_gqa_attention`
    at a group of one query head, the delta layers' `decode_delta_state`)."""
    got = numbers(seeded, placement="one bucket", programs=_programs(), only=("decode",))
    assert got["decode"] <= TOL, got


# -- (c) planted faults --------------------------------------------------------


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _state_in_bfloat16():
    def chunked(q, k, v, g, beta, S):
        o, S = delta.delta_chunked(q, k, v, g, beta, _bf16(S))
        return o, _bf16(S)

    def state(states, q, k, v, g, beta, layer, live=None, **how):
        o, states = delta.decode_delta_state(_bf16(states), q, k, v, g, beta, layer, live, **how)
        return o, _bf16(states)

    return [(stacks, "delta_chunked", chunked), (stacks, "decode_delta_state", state)]


def _decay_behind_the_update(q, k, v, g, beta, S0):
    """S ← α·(S + β k (v − Sᵀk)ᵀ): the decay behind the update."""
    def body(S, x):
        q, k, v, g, beta = x
        r = jnp.einsum("bhkv,bhk->bhv", S, k)
        S = S + k[..., :, None] * (beta[..., None] * (v - r))[..., None, :]
        S = S * jnp.exp(g)[..., None, None]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    S, o = jax.lax.scan(body, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _a_decay_a_channel(q, k, v, g, beta, S0):
    """The head's scalar applied as KDA would apply a channel's: here the
    first channel keeps it and the others decay twice as fast."""
    from omnia_tpu.ops import kda

    per = g[..., None] * jnp.where(jnp.arange(q.shape[-1]) == 0, 1.0, 2.0)
    return kda.kda_recurrent(q, k, v, per, beta, S0)


def _swapped_widths(q, k, v, g, beta, S0):
    """A state [dv, dk]: the rule run with keys and values changed over
    wherever the shapes allow it (v's first dk columns as the key)."""
    dk = q.shape[-1]
    o, S = delta.delta_recurrent(q, v[..., :dk] / 4, jnp.pad(k, [(0, 0)] * 3 + [(0, dk)]),
                                 g, beta, S0)
    return o, S


def _drop(index):
    def between(cache):
        cache = list(cache)
        cache[index] = jnp.zeros_like(cache[index])
        return tuple(cache)
    return between


def _patched_silu(fake):
    """`jax.nn.silu` as `stacks` sees it replaced where a call's operand has
    the gate's width; the convolution's SiLU (wider) stays."""
    class nn:
        softplus, sigmoid = jax.nn.softplus, jax.nn.sigmoid

        @staticmethod
        def silu(x):
            gate = x.shape[-1] == CFG.linear_num_heads * CFG.linear_value_head_dim
            return fake(x) if gate else jax.nn.silu(x)

    class fake_jax:
        def __getattr__(self, name):
            return nn if name == "nn" else getattr(jax, name)

    return fake_jax()


def _rms_norm_a_head(x, w, eps=1e-5):
    """`rms_norm`, but a gain as wide as the whole projection norms each head
    of 16 on its own."""
    from omnia_tpu.ops.norms import rms_norm

    if w.shape[-1] == CFG.q_dim and x.shape[-1] == CFG.q_dim:
        heads = x.reshape(*x.shape[:-1], CFG.num_heads, CFG.head_dim)
        return rms_norm(heads, w[:CFG.head_dim], eps).reshape(x.shape)
    return rms_norm(x, w, eps)


def _never_fresh_mixer():
    """`_delta_mixer` that takes no piece for a new tenant's first."""
    sound = stacks._delta_mixer

    def mixer(h, a, cfg, cache, cache_layer, write_start, n_real, live):
        return sound(h, a, cfg, cache, cache_layer, write_start + 1, n_real, live)

    return mixer


# name -> (the number it must show in, what to replace in the config,
# [(module, attribute, replacement)] to patch, keywords for `served_logits`)
FAULTS = {
    "beta not doubled": ("whole", {"linear_allow_neg_eigval": False}, [], {}),
    "the decay behind the update": (
        "whole", {}, [(stacks, "delta_chunked", _decay_behind_the_update)], {}),
    "a scalar decay applied a channel": (
        "whole", {}, [(stacks, "delta_chunked", _a_decay_a_channel)], {}),
    "the norms in front of the sublayers": ("whole", {"norm_placement": "pre"}, [], {}),
    "the QK-norm a head": ("whole", {}, [(stacks, "rms_norm", _rms_norm_a_head)], {}),
    "rotary position on the full layer": ("whole", {"rope_on_full_layers": True}, [], {}),
    "the state in bfloat16": ("decode", {}, _state_in_bfloat16(), {}),
    "a padded piece's pad rows enter the state and the tail": (
        "decode", {}, [], {"pad_is_real": True}),
    "sigmoid for SiLU in the output gate": (
        "whole", {}, [(stacks, "jax", _patched_silu(jax.nn.sigmoid))], {}),
    "q unscaled": ("whole", {}, [(stacks, "delta_chunked", lambda q, *rest: delta.delta_chunked(
        q * CFG.linear_key_head_dim ** 0.5, *rest))], {}),
    "dk and dv swapped": ("whole", {}, [(stacks, "delta_chunked", _swapped_widths)], {}),
    "the state is not handed from piece to piece": ("prefill", {}, [], {"between": _drop(2)}),
    "the convolution's tail is not handed from piece to piece": (
        "prefill", {}, [], {"between": _drop(3)}),
    "a first piece keeps the last tenant's state and tail": (
        "prefill", {}, [(stacks, "_delta_mixer", _never_fresh_mixer())], {"poison": True}),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_by_a_hundred_tolerances(seeded, fault, monkeypatch):
    number, replace, patches, how = FAULTS[fault]
    for patch in patches:
        monkeypatch.setattr(*patch)
    got = numbers(seeded, dataclasses.replace(CFG, **replace),
                  programs=_programs() if patches else SOUND, only=(number,), **how)
    assert got[number] >= 100 * TOL, (fault, got)


def test_the_sound_run_passes_where_each_fault_is_looked_for(seeded):
    got = numbers(seeded, poison=True)  # whatever the last tenant left
    assert max(got.values()) <= TOL, got


def test_a_dead_slots_decode_step_leaves_its_state_and_tail_alone(seeded):
    """A slot that is not live is between tenants or between its placement's
    pieces while other slots decode: a decode step leaves its state and its
    tail bit for bit, and the counter counts the live slots' states alone.
    The planted fault, `live` not passed on, changes both by far more than a
    hundred tolerances."""
    params, tokens, _, _ = seeded
    cache = tuple(c + 1.0 for c in llama.init_kv_cache(CFG, 2, 32, dtype=jnp.float32))

    @jax.jit
    def step(live):
        return llama.forward(params, CFG, jnp.asarray(tokens[:2, None]),
                             jnp.full((2, 1), 11, jnp.int32), *cache,
                             jnp.full((2,), 11, jnp.int32), live=live, counters=True)

    _, _, _, states, tails, counts = step(jnp.asarray([True, False]))
    assert np.all(np.asarray(states[:, 1]) == 1.0) and np.all(np.asarray(tails[:, 1]) == 1.0)
    assert np.abs(np.asarray(states[:, 0]) - 1.0).max() > 100 * TOL
    assert np.abs(np.asarray(tails[:, 0, -1]) - 1.0).max() > 100 * TOL
    assert counts.shape == (1,) and int(counts[0]) == 3  # one live slot, three delta layers
    _, _, _, states, tails, counts = step(None)          # the fault
    assert np.abs(np.asarray(states[:, 1]) - 1.0).max() > 100 * TOL
    assert np.abs(np.asarray(tails[:, 1]) - 1.0).max() > 100 * TOL and int(counts[0]) == 6


# -- (d) through the engine ----------------------------------------------------


@pytest.mark.parametrize("cfg", [CFG, CFG_PACKED], ids=["a head a row", "two heads a row"])
def test_the_engine_serves_it_through_pieces_states_and_reused_slots(cfg):
    """`InferenceEngine` on the normal path, the states a head a row and two
    heads side by side (a fresh prefill's packed state put into its slot,
    pieces through the slot's view, decode over the whole array): prompts
    longer than the largest bucket (placed through `extend` in pieces of unequal length, the state
    and the tail handed from piece to piece), one that fits a bucket
    (`prefill_insert`), 24 decode steps each, and two more rounds of requests
    into the same two slots: a state must not leak the previous tenant's.
    Every served token is the largest logit of the REFERENCE's full forward
    over the tokens before it, to within the two paths' rounding."""
    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(16, 32), max_sessions=0,
                        decode_chunk=4, dtype="float32")
    engine = InferenceEngine(cfg, ecfg, seed=3)
    assert engine.model_module is llama and len(engine._cache) == 4
    assert engine._cache[2].shape == (3, 2, *stacks.state_shape(cfg))
    assert engine.kv_bytes_per_token() == 1 * 2 * 6 * 16 * 4   # the full layer's rows alone
    engine.warmup()
    engine.start()
    rng = np.random.default_rng(0)
    sizes = reference_sizes(cfg, file_of(cfg))
    forward = jax.jit(lambda p, t: ref.forward(p, sizes, t))
    try:
        for _ in range(3):
            prompts = [[int(t) for t in rng.integers(0, 256, size=n)] for n in (45, 70, 13)]
            handles = [engine.submit(p, SamplingParams(max_tokens=24, temperature=0.0,
                                                       stop_token_ids=())) for p in prompts]
            for prompt, handle in zip(prompts, handles):
                out = [ev.token_id for ev in handle.events()
                       if getattr(ev, "token_id", None) is not None]
                assert len(out) == 24
                logits = np.asarray(forward(engine.params, jnp.asarray(prompt + out)))
                rows = logits[len(prompt) - 1:len(prompt) + 23]
                assert np.all(rows.max(-1) - rows[np.arange(24), out] <= 1e-4)
    finally:
        engine.stop()
    m = engine.metrics
    assert m["extend_steps"] > 0 and m["decode_steps"] > 0 and m["prefill_steps"] > 0
    # every live slot's state is updated once a delta layer a step (the device
    # counts the slots live at each step, the host those at dispatch)
    assert 0 < m["decode_delta_slots"] <= 3 * m["decode_slot_steps"]
    assert m["decode_kda_slots"] == 0


# -- (e) what is refused -------------------------------------------------------

REFUSED = {"max_sessions": {"max_sessions": 4}, "prefix_cache_slots": {"prefix_cache_slots": 2},
           "kv_pages": {"kv_pages": 8}, "spec_decode": {"spec_decode": 4},
           "prefill_chunk_tokens": {"prefill_chunk_tokens": 64}}


@pytest.mark.parametrize("feature", list(REFUSED))
def test_a_model_with_a_recurrent_state_refuses_what_assumes_rows_by_name(feature):
    """A state has no rows to offload, seed, page or roll back: a pair-family
    model with state layers is refused each of the five by name with the
    state's reason; `test-tiny-kda` (the latent family) reads as it did; a
    pair-family model of several kinds without a state keeps its own reason."""
    ecfg = EngineConfig(**{"num_slots": 2, "max_seq": 256, "prefill_buckets": (64,),
                           "max_sessions": 0, **REFUSED[feature]})
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}=.*not ported to a "
                                                  r"model of several kinds of layers.*"
                                                  r"test-tiny-delta'\): \w.*stat"):
        refuse_unported(CFG, ecfg)
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}="):
        InferenceEngine(CFG, ecfg)
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}=.*not ported to "
                                                  r"the latent-attention family.*\): \w.*stat"):
        refuse_unported(get_config("test-tiny-kda"), ecfg)
    with pytest.raises(NotImplementedError) as window:
        refuse_unported(get_config("test-tiny-window"), ecfg)
    assert "recurrent state" not in str(window.value)


def test_what_the_stacks_refuse_besides_holds_for_this_model_too():
    for name, value in (("kv_quant", "int8"), ("tp", 2)):
        ecfg = EngineConfig(**{"num_slots": 2, "max_seq": 256, "prefill_buckets": (64,),
                               "max_sessions": 0, name: value})
        with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{name}="):
            refuse_unported(CFG, ecfg)
    with pytest.raises(NotImplementedError, match="window layers beside"):
        llama.init_params(dataclasses.replace(
            CFG, sliding_window=8, layer_types=("sliding_attention", "linear_attention",
                                                "linear_attention", "full_attention")),
            jax.random.key(0))


def test_a_stacked_model_that_names_no_counter_gets_none():
    """A dense model with norms on its sublayers' outputs is a model of
    stacks with neither experts nor states: `forward(..., counters=True)`
    hands back as many counts as `decode_counters` names, none."""
    cfg = dataclasses.replace(get_config("test-tiny"), name="tiny-post", norm_placement="post")
    assert llama.is_stacked(cfg) and decode_counters(cfg) == ()
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    cache = llama.init_kv_cache(cfg, 1, 16, dtype=jnp.float32)
    *_, counts = llama.forward(params, cfg, jnp.zeros((1, 1), jnp.int32),
                               jnp.zeros((1, 1), jnp.int32), *cache,
                               jnp.zeros((1,), jnp.int32), counters=True)
    assert counts.shape == (0,)


@pytest.mark.parametrize("field", [{"norm_placement": "post"}, {"qk_norm_whole": True}])
def test_the_latent_family_refuses_the_blocks_new_switches_by_name(field):
    """models/mla.py norms a sublayer's input and has no q or k of a whole
    width: a latent-family model that states either switch is refused when the
    engine is built, not served as if it had not."""
    (name,) = field
    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64,), max_sessions=0)
    cfg = dataclasses.replace(get_config("test-tiny-kda"), **field)
    with pytest.raises(NotImplementedError, match=rf"ModelConfig\.{name}=.*latent-attention"):
        refuse_unported(cfg, ecfg)
    refuse_unported(get_config("test-tiny-kda"), ecfg)  # as it was
