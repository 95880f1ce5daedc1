"""The pair family with state-space layers (models/stacks.py: the Mamba-1
selective scan, ops/mamba.py, behind a depthwise convolution with a bias and
three inner RMSNorms, beside un-rotated attention of several query heads on
ONE KV head; float32 states with the channels along the lanes and flat
convolution tails in the slot's cache beside the attention layers' K and V;
the head tied to the table) against the plain reference of the model it was
written for, `benchmark/reference/jamba_ref.py`, at `test-tiny-mamba`'s size:
M M A M M, 128 channels of 16 state numbers.

Logits are compared and never tokens. Everything is float32 on the CPU, so
the program and the reference differ by the order of their sums alone: TOL is
1e-5 of the reference's logit range (readings here are 1e-6 and under; the
chunked scan against the recurrence reads 1e-6 absolute on outputs of 3), and
every planted fault has to move the number named for it by a hundred times
that. A state rounded to bfloat16 is among the faults: it reads ten tolerances
(a hundred times the sound reading) and is held to five, because at this
size a Mamba layer's output is mostly the skip D u' and the state's share of it
a tenth; on the chip at the published widths it is
benchmark/tests/chip_long_mamba.py's float32 section that separates it."""
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
from omnia_tpu.ops import mamba

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.manifest import reference_sizes  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "jamba_ref", os.path.join(BENCH, "reference", "jamba_ref.py"))
ref = importlib.util.module_from_spec(_spec)  # the benchmark's plain reference
_spec.loader.exec_module(ref)

CFG = get_config("test-tiny-mamba")
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
    monkeypatch.setattr(mamba, "CHUNK", CHUNK)
    monkeypatch.setattr(mamba.mamba_chunked, "__defaults__", (CHUNK,))


def file_of(cfg) -> dict:
    """The keys of a configuration file that the reference reads, for `cfg`."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "mamba_d_state": cfg.mamba_d_state, "mamba_d_conv": cfg.mamba_d_conv,
        "mamba_dt_rank": cfg.mamba_dt_rank, "mamba_expand": cfg.mamba_expand,
        "mamba_conv_bias": cfg.mamba_conv_bias, "mamba_proj_bias": cfg.mamba_proj_bias,
        "assumed": {"head_dim": cfg.head_dim, "layer_types": list(cfg.layer_types),
                    "rope_on_full_layers": cfg.rope_on_full_layers,
                    "mamba_inner_norms": cfg.mamba_inner_norms},
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
    assert CFG.attention_kinds == ("mamba", "mamba", "full", "mamba", "mamba")
    assert CFG.has_state_layers and not CFG.has_window_layers and not CFG.is_latent
    assert llama.is_stacked(CFG) and model_module(CFG) is llama
    assert llama.stack_kinds(CFG) == ("dense_full", "dense_mamba")
    assert llama.layer_order(CFG) == ((1, 0), (1, 1), (0, 0), (1, 2), (1, 3))
    # both joins, and the cache's layer index across the attention layer
    assert stacks._runs(CFG) == [(1, "dense_mamba", 0, 2, 0), (0, "dense_full", 0, 1, 0),
                                 (1, "dense_mamba", 2, 2, 2)]
    assert (CFG.mamba_channels, CFG.mamba_d_state, CFG.mamba_dt_rank) == (128, 16, 8)
    assert CFG.num_kv_heads == 1 and CFG.num_heads == 4 and CFG.tie_embeddings
    assert cache_arrays(CFG) == 4 and decode_counters(CFG) == ("decode_mamba_slots",)
    cache = jax.eval_shape(lambda: llama.init_kv_cache(CFG, 2, 32, dtype=jnp.bfloat16))
    assert [(c.shape, c.dtype.name) for c in cache] == [
        ((1, 2, 32, 1, 16), "bfloat16"), ((1, 2, 32, 1, 16), "bfloat16"),
        ((4, 2, 16, 128), "float32"), ((4, 2, 3 * 128), "bfloat16")]
    assert stacks.state_shape(CFG) == (16, 128) and stacks.tail_shape(CFG) == (384,)
    # the delta kind keeps its name, its counter and its shapes
    olmo = get_config("test-tiny-delta")
    assert decode_counters(olmo) == ("decode_delta_slots",)
    assert stacks.state_shape(olmo) == (6, 8, 16) and stacks.tail_shape(olmo) == (3, 192)
    tree = jax.eval_shape(lambda: llama.init_params(CFG, jax.random.key(0)))
    assert CFG.num_params() == sum(x.size for x in jax.tree.leaves(tree))
    assert "lm_head" not in tree and tree["layers"][1]["attn"]["a_log"].shape == (4, 16, 128)


def test_the_seeded_decay_lies_where_a_trained_models_does(seeded):
    a = seeded[0]["layers"][1]["attn"]
    step = np.log1p(np.exp(np.asarray(a["dt_bias"])))                  # [4, E]
    decay = np.exp(-np.exp(np.asarray(a["a_log"])) * step[:, None, :])
    assert 0.15 < decay.min() and decay.max() < 0.9995
    assert np.allclose(np.exp(np.asarray(a["a_log"]))[0, :, 5], np.arange(1, 17))
    assert np.all(np.asarray(a["d"]) == 1.0) and np.abs(np.asarray(a["conv_b"])).max() <= 0.5


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_prefill_then_decode_through_the_cache_agrees_with_the_reference(seeded, placement):
    """The fresh prefill whole, and the prompt placed whole, in pieces of
    unequal length and in one padded piece, then decode through the cache."""
    got = numbers(seeded, placement=placement,
                  only=("whole", "prefill", "decode") if placement == "one bucket"
                  else ("prefill", "decode"))
    assert max(got.values()) <= TOL, got


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
    assert states.shape == (4, 1, 16, 128) and tails.shape == (4, 1, 384)
    np.testing.assert_allclose(np.asarray(states), np.asarray(states2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(tails), np.asarray(tails2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(k[:, :, :21]), np.asarray(k2), atol=1e-5)


# -- (b) the rule three ways ---------------------------------------------------


def _rule_inputs(B, T, N, E, seed, steps=(1e-3, 0.5)):
    """u, B, C normal; steps log-uniform in `steps`; A = -(1 .. N) times a
    channel's own factor in 0.5 .. 2; D normal; a state that is not zero."""
    ks = jax.random.split(jax.random.key(seed), 8)
    u = jax.random.normal(ks[0], (B, T, E))
    dt = jnp.exp(jax.random.uniform(ks[1], (B, T, E), minval=np.log(steps[0]),
                                    maxval=np.log(steps[1])))
    Bv, Cv = jax.random.normal(ks[2], (B, T, N)), jax.random.normal(ks[3], (B, T, N))
    A = -jnp.arange(1, N + 1, dtype=jnp.float32)[:, None] * jnp.exp(
        jax.random.uniform(ks[4], (1, E), minval=np.log(0.5), maxval=np.log(2.0)))
    D = jax.random.normal(ks[5], (E,))
    S0 = 0.5 * jax.random.normal(ks[6], (B, N, E))
    return u, dt, Bv, Cv, A, D, S0


@pytest.mark.parametrize("T,chunk,N,E", [
    (150, 16, 16, 128),    # a last chunk of 6
    (40, 8, 16, 128),
    (64, 16, 16, 5120),    # the served channels
    (37, 16, 8, 256),
    (7, 16, 16, 128),      # shorter than a chunk
])
def test_chunked_equals_recurrent_equals_repeated_steps(T, chunk, N, E):
    """`mamba_chunked` against `mamba_recurrent` against `mamba_step` a token,
    from a state that is not zero, at lengths that are no multiple of the
    chunk: outputs and the state handed on."""
    u, dt, Bv, Cv, A, D, S0 = _rule_inputs(2, T, N, E, seed=T)
    want_y, want_S = mamba.mamba_recurrent(u, dt, Bv, Cv, A, D, S0)
    y, S = mamba.mamba_chunked(u, dt, Bv, Cv, A, D, S0, chunk)
    scale = max(float(jnp.abs(want_y).max()), 1.0)
    assert float(jnp.abs(y - want_y).max()) <= 2e-6 * scale
    assert float(jnp.abs(S - want_S).max()) <= 2e-6 * max(float(jnp.abs(want_S).max()), 1.0)
    S, ys = S0, []
    for t in range(min(T, 12)):
        y_t, S = mamba.mamba_step(S, u[:, t], dt[:, t], Bv[:, t], Cv[:, t], A, D)
        ys.append(y_t)
    np.testing.assert_allclose(np.stack(ys, 1), np.asarray(want_y[:, :len(ys)]),
                               atol=2e-6 * scale)


def test_a_row_with_no_step_leaves_the_state_alone():
    u, dt, Bv, Cv, A, D, S0 = _rule_inputs(1, 16, 16, 128, seed=5)
    real = jnp.arange(16) < 11
    dt = jnp.where(real[None, :, None], dt, 0.0)
    _, S = mamba.mamba_chunked(u, dt, Bv, Cv, A, D, S0, 8)
    _, want = mamba.mamba_recurrent(u[:, :11], dt[:, :11], Bv[:, :11], Cv[:, :11], A, D, S0)
    assert np.array_equal(np.asarray(S), np.asarray(want))


@pytest.mark.parametrize("route", ["jnp", "kernel"])
@pytest.mark.parametrize("B,N,E", [
    (16, 16, 5120),    # the served state: two groups of eight slots, two blocks of 2560 lanes
    (24, 16, 256),     # three groups, one of them with no live slot
    (5, 16, 128),      # fewer slots than a group: the array's own
    (12, 16, 128),     # no whole groups: `mamba_step` whatever the route
    (8, 8, 384),
])
def test_a_decode_step_equals_the_rule_in_place_and_skips_dead_slots(route, B, N, E):
    """`decode_mamba_state`, by `mamba_step` and by the Pallas kernel
    interpreted, over a middle layer of three: equal to one step of the
    recurrence for the live slots; a dead slot's state (in a group that is
    visited and in one that is not) and every other layer's are bit for bit
    what they were."""
    u, dt, Bv, Cv, A, D, S0 = _rule_inputs(B, 1, N, E, seed=B)
    state = jnp.stack([S0 * 0.5, S0, S0 * 2])
    live = np.arange(B) % 3 != 1
    live[8:16] = False                     # a whole group of eight dead
    want_y, want_S = mamba.mamba_recurrent(u, dt, Bv, Cv, A, D, S0)
    assert mamba.kernel_takes(B, N, E) == (B != 12)
    y, new = mamba.decode_mamba_state(state, u[:, 0], dt[:, 0], Bv[:, 0], Cv[:, 0], A, D,
                                      jnp.int32(1), jnp.asarray(live),
                                      kernel=route == "kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y[:, 0])[live], atol=5e-6)
    np.testing.assert_allclose(np.asarray(new[1])[live], np.asarray(want_S)[live], atol=5e-6)
    assert np.array_equal(np.asarray(new[1])[~live], np.asarray(state[1])[~live])
    assert np.array_equal(np.asarray(new[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(new[2]), np.asarray(state[2]))
    # every slot live: the same numbers with no list of the living
    y, new = mamba.decode_mamba_state(state, u[:, 0], dt[:, 0], Bv[:, 0], Cv[:, 0], A, D,
                                      jnp.int32(1), None, kernel=route == "kernel",
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(new[1]), np.asarray(want_S), atol=5e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y[:, 0]), atol=5e-6)


@pytest.mark.parametrize("B,T,N,E", [
    (1, 256, 16, 5120),    # the served channels: four blocks of 1280 lanes, two of 128 tokens
    (2, 128, 16, 256),
    (1, 384, 8, 384),
])
def test_the_scan_kernel_equals_the_recurrence(B, T, N, E):
    """`mamba_scan`, the Pallas kernel interpreted, against `mamba_recurrent`
    from a state that is not zero, with pad rows (Δ = 0) behind the real ones:
    outputs, and the state handed on is the last REAL row's."""
    u, dt, Bv, Cv, A, D, S0 = _rule_inputs(B, T, N, E, seed=T + E)
    real = T - 37
    dt = jnp.where(jnp.arange(T)[None, :, None] < real, dt, 0.0)
    assert mamba.scan_takes(T, N, E) and not mamba.scan_takes(T - 8, N, E)
    assert not mamba.scan_takes(1, N, E) and mamba._scan_lanes(5120) == 1280
    want_y, _ = mamba.mamba_recurrent(u, dt, Bv, Cv, A, D, S0)
    _, want_S = mamba.mamba_recurrent(u[:, :real], dt[:, :real], Bv[:, :real], Cv[:, :real],
                                      A, D, S0)
    y, S = mamba.mamba_scan(u, dt, Bv, Cv, A, D, S0, interpret=True)
    scale = max(float(jnp.abs(want_y).max()), 1.0)
    assert float(jnp.abs(y - want_y).max()) <= 2e-6 * scale
    assert float(jnp.abs(S - want_S).max()) <= 2e-6 * max(float(jnp.abs(want_S).max()), 1.0)


def test_the_kernels_blocks_are_whole_tiles_under_the_limit():
    assert mamba.lane_block(16, 5120) == 2560 and mamba.lane_block(16, 5120, rows=1) == 5120
    assert mamba.lane_block(16, 128) == 128 and mamba.lane_block(64, 8192) == 512
    assert 8 * 16 * mamba.lane_block(16, 5120) * 4 <= mamba.BLOCK_BYTES
    assert not mamba.kernel_takes(8, 12, 128) and not mamba.kernel_takes(8, 16, 192)
    with pytest.raises(ValueError, match="is not that of"):
        mamba.decode_mamba_state(jnp.zeros((1, 2, 16, 128)), jnp.zeros((2, 64)),
                                 jnp.zeros((2, 64)), jnp.zeros((2, 16)), jnp.zeros((2, 16)),
                                 jnp.zeros((16, 128)), jnp.zeros((128,)), 0)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "interpret")
    attn._pallas_decode_mode.cache_clear()
    yield
    attn._pallas_decode_mode.cache_clear()


def test_decode_through_the_kernels_agrees_with_the_reference(seeded, interpreted):
    """Both decode kernels interpreted (the attention layer's
    `decode_gqa_attention` at a group of four query heads on one KV head, the
    Mamba layers' `decode_mamba_state` over one slot)."""
    got = numbers(seeded, placement="one bucket", programs=_programs(), only=("decode",))
    assert got["decode"] <= TOL, got


def test_pieces_of_whole_blocks_go_through_the_scan_kernel(interpreted, monkeypatch):
    """With the kernels routed (interpreted here) a piece of 128 tokens is
    `mamba_scan`'s and one of 40 `mamba_chunked`'s: a prompt of 168 placed as
    128 + 40 (the second padded to 64) and 8 decode steps agree with the
    reference, and the kernel was traced for the first piece alone."""
    calls = []
    sound = stacks.mamba_scan
    monkeypatch.setattr(stacks, "mamba_scan",
                        lambda u, *rest, **how: calls.append(u.shape) or sound(u, *rest, **how))
    params = seeded_params(jax.random.key(0), cfg=CFG)
    tokens = np.random.default_rng(1).integers(0, CFG.vocab_size, 176).astype(np.int32)
    sizes = reference_sizes(CFG, file_of(CFG))
    want = np.asarray(ref.forward(params, sizes, jnp.asarray(tokens)))
    got = served_logits(params, CFG, tokens, [(128, 128), (40, 64)], rows=256,
                        programs=_programs())
    assert over_range(got, want) <= TOL
    assert calls and set(calls) == {(1, 128, 128)}


# -- (c) planted faults --------------------------------------------------------


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _state_in_bfloat16():
    def chunked(u, dt, Bv, Cv, A, D, S):
        y, S = mamba.mamba_chunked(u, dt, Bv, Cv, A, D, _bf16(S))
        return y, _bf16(S)

    def state(states, *args, **how):
        y, states = mamba.decode_mamba_state(_bf16(states), *args, **how)
        return y, _bf16(states)

    return [(stacks, "mamba_chunked", chunked), (stacks, "decode_mamba_state", state)]


def _without_the_skip():
    """y = Σ S·C alone: the D·u' term left out, of a piece and of a step."""
    def chunked(u, dt, Bv, Cv, A, D, S):
        return mamba.mamba_chunked(u, dt, Bv, Cv, A, jnp.zeros_like(D), S)

    def state(states, u, dt, Bv, Cv, A, D, *rest, **how):
        return mamba.decode_mamba_state(states, u, dt, Bv, Cv, A, jnp.zeros_like(D),
                                        *rest, **how)

    return [(stacks, "mamba_chunked", chunked), (stacks, "decode_mamba_state", state)]


def _the_decay_a_layer(u, dt, Bv, Cv, A, D, S):
    """One decay a channel (A's first row for every state number): the
    diagonal recurrence with a state number's own rate lost."""
    return mamba.mamba_chunked(u, dt, Bv, Cv, jnp.broadcast_to(A[:1], A.shape), D, S)


def _input_without_the_step(u, dt, Bv, Cv, A, D, S):
    """S ← exp(Δ A) S + B u': the input not scaled by Δ."""
    y, S = mamba.mamba_recurrent(u / jnp.maximum(dt, 1e-9), dt, Bv, Cv, A, D * 0.0, S)
    return y + D * u, S


def _drop(index):
    def between(cache):
        cache = list(cache)
        cache[index] = jnp.zeros_like(cache[index])
        return tuple(cache)
    return between


def _never_fresh_mixer():
    """`_mamba_mixer` that takes no piece for a new tenant's first."""
    sound = stacks._mamba_mixer

    def mixer(h, a, cfg, cache, cache_layer, write_start, n_real, live):
        return sound(h, a, cfg, cache, cache_layer, write_start + 1, n_real, live)

    return mixer


def _without(*leaves):
    """The seeded parameters with those leaves of the Mamba stack zeroed: a
    bias that is zero is a bias that is missing."""
    def params(own):
        stack = dict(own["layers"][1])
        stack["attn"] = {name: jnp.zeros_like(a) if name in leaves else a
                         for name, a in stack["attn"].items()}
        return {**own, "layers": [own["layers"][0], stack]}
    return params


# name -> (the number it must show in, what to replace in the config,
# [(module, attribute, replacement)] to patch, keywords for `served_logits`,
# what to do to the parameters the PROGRAM is handed)
FAULTS = {
    "the state in bfloat16": ("decode", {}, _state_in_bfloat16(), {}, None),
    "no inner norms": ("whole", {"mamba_inner_norms": False}, [], {}, None),
    "no D u' term": ("whole", {}, _without_the_skip(), {}, None),
    "no convolution bias": ("whole", {"mamba_conv_bias": False}, [], {}, None),
    "one decay a channel for every state number": (
        "whole", {}, [(stacks, "mamba_chunked", _the_decay_a_layer)], {}, None),
    "the input not scaled by the step": (
        "whole", {}, [(stacks, "mamba_chunked", _input_without_the_step)], {}, None),
    "rotary position on the attention layer": ("whole", {"rope_on_full_layers": True}, [], {},
                                               None),
    "a head of its own where the table is tied": (
        "whole", {"tie_embeddings": False}, [], {},
        lambda own: {**own, "lm_head": jnp.roll(own["embed"].T, 1, axis=1)}),
    "a padded piece's pad rows enter the state and the tail": (
        "decode", {}, [], {"pad_is_real": True}, None),
    "the state is not handed from piece to piece": ("prefill", {}, [], {"between": _drop(2)},
                                                    None),
    "the convolution's tail is not handed from piece to piece": (
        "prefill", {}, [], {"between": _drop(3)}, None),
    "a first piece keeps the last tenant's state and tail": (
        "prefill", {}, [(stacks, "_mamba_mixer", _never_fresh_mixer())], {"poison": True}, None),
    "the step's bias left out": ("whole", {}, [], {}, _without("dt_bias")),
}


# Tolerances a fault must fail by where that is not a hundred (the module docstring).
FAILS_BY = {"the state in bfloat16": 5}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_by_a_hundred_tolerances(seeded, fault, monkeypatch):
    number, replace, patches, how, params = FAULTS[fault]
    for patch in patches:
        monkeypatch.setattr(*patch)
    got = numbers(seeded, dataclasses.replace(CFG, **replace),
                  params=params(seeded[0]) if params else None,
                  programs=_programs() if patches else SOUND, only=(number,), **how)
    assert got[number] >= FAILS_BY.get(fault, 100) * TOL, (fault, got)


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
    assert np.abs(np.asarray(tails[:, 0, -128:]) - 1.0).max() > 100 * TOL
    assert counts.shape == (1,) and int(counts[0]) == 4  # one live slot, four Mamba layers
    _, _, _, states, tails, counts = step(None)          # the fault
    assert np.abs(np.asarray(states[:, 1]) - 1.0).max() > 100 * TOL
    assert np.abs(np.asarray(tails[:, 1]) - 1.0).max() > 100 * TOL and int(counts[0]) == 8


# -- (d) through the engine ----------------------------------------------------


@pytest.mark.parametrize("route", ["jnp", "kernels"])
def test_the_engine_serves_it_through_pieces_states_and_reused_slots(route, request):
    """`InferenceEngine` on the normal path, by `mamba_step` and by both decode
    kernels interpreted (eight slots: one group of the state kernel, most of
    its slots dead): prompts longer than the largest bucket (placed through
    `extend` in pieces of unequal length, the last padded, the state and the
    tail handed from piece to piece), one that fits a bucket
    (`prefill_insert`), 24 decode steps each, and two more rounds of requests
    into the same slots: a state must not leak the previous tenant's. Every
    served token is the largest logit of the REFERENCE's full forward over
    the tokens before it, an unbatched run, to within the two paths' rounding."""
    if route == "kernels":
        request.getfixturevalue("interpreted")
    slots = 8 if route == "kernels" else 2
    ecfg = EngineConfig(num_slots=slots, max_seq=256, prefill_buckets=(16, 32), max_sessions=0,
                        decode_chunk=4, dtype="float32")
    engine = InferenceEngine(CFG, ecfg, seed=3)
    assert engine.model_module is llama and len(engine._cache) == 4
    assert engine._cache[2].shape == (4, slots, 16, 128)
    assert engine._cache[3].shape == (4, slots, 384)
    assert engine.kv_bytes_per_token() == 1 * 2 * 1 * 16 * 4   # the attention layer's rows alone
    engine.warmup()
    engine.start()
    rng = np.random.default_rng(0)
    sizes = reference_sizes(CFG, file_of(CFG))
    forward = jax.jit(lambda p, t: ref.forward(p, sizes, t))
    try:
        for _ in range(3 if route == "jnp" else 1):
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
    # every live slot's state is updated once a Mamba layer a step (the device
    # counts the slots live at each step, the host those at dispatch)
    assert 0 < m["decode_mamba_slots"] <= 4 * m["decode_slot_steps"]
    assert m["decode_kda_slots"] == 0 and m["decode_delta_slots"] == 0


# -- (e) what is refused -------------------------------------------------------

REFUSED = {"max_sessions": {"max_sessions": 4}, "prefix_cache_slots": {"prefix_cache_slots": 2},
           "kv_pages": {"kv_pages": 8}, "spec_decode": {"spec_decode": 4},
           "prefill_chunk_tokens": {"prefill_chunk_tokens": 64}}


@pytest.mark.parametrize("feature", list(REFUSED))
def test_a_model_with_state_space_layers_refuses_what_assumes_rows_by_name(feature):
    """A state has no rows to offload, seed, page or roll back: a model with
    state-space layers is refused each of the five by name with the state's
    reason, in its own kind's words; a delta model reads as it did."""
    ecfg = EngineConfig(**{"num_slots": 2, "max_seq": 256, "prefill_buckets": (64,),
                           "max_sessions": 0, **REFUSED[feature]})
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}=.*not ported to a "
                                                  r"model of several kinds of layers.*"
                                                  r"test-tiny-mamba'\): \w.*stat") as mine:
        refuse_unported(CFG, ecfg)
    if feature != "prefill_chunk_tokens":
        assert "state-space layers keep a recurrent state" in str(mine.value)
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}="):
        InferenceEngine(CFG, ecfg)
    with pytest.raises(NotImplementedError) as delta:
        refuse_unported(get_config("test-tiny-delta"), ecfg)
    assert "state-space" not in str(delta.value)


def test_what_the_stacks_refuse_besides_holds_for_this_model_too():
    for name, value in (("kv_quant", "int8"), ("tp", 2), ("quant", "int8")):
        ecfg = EngineConfig(**{"num_slots": 2, "max_seq": 256, "prefill_buckets": (64,),
                               "max_sessions": 0, name: value})
        with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{name}="):
            refuse_unported(CFG, ecfg)
    key = jax.random.key(0)
    with pytest.raises(NotImplementedError, match="window layers beside"):
        llama.init_params(dataclasses.replace(
            CFG, sliding_window=8, layer_types=("sliding_attention", "mamba", "mamba",
                                                "full_attention", "mamba")), key)
    with pytest.raises(NotImplementedError, match="linear-attention layers beside state-space"):
        llama.init_params(dataclasses.replace(
            CFG, linear_num_heads=2, linear_key_head_dim=8, linear_value_head_dim=16,
            layer_types=("linear_attention", "mamba", "mamba", "full_attention", "mamba")), key)
    with pytest.raises(NotImplementedError, match="a sparse FFN beside state-space"):
        llama.init_params(dataclasses.replace(
            CFG, num_experts=4, moe_ffn_hidden_size=32, num_experts_per_tok=2), key)
    with pytest.raises(NotImplementedError, match="mamba_proj_bias"):
        llama.init_params(dataclasses.replace(CFG, mamba_proj_bias=True), key)
