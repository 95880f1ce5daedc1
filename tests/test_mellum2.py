"""The pair family's stacks as Mellum 2 needs them (models/stacks.py: sparse
stacks only, every expert held, a softmax router's greedy top-k renormalised,
no shared expert, QK-norm, and a rotary table a kind of attention layer:
plain RoPE on the window layers, YaRN with cos and sin scaled on the full
ones) against the plain reference of that model,
`benchmark/reference/mellum2_ref.py`, at `test-tiny-yarn`'s size: S S S F, a
window of 8 rows, so rings of 8, which sequences of 72 tokens wrap nine times.

Logits are compared and never tokens. Everything is float32 on the CPU, so
the program and the reference differ by the order of their sums alone: TOL
is 1e-5 of the reference's logit range (readings here are 2e-7 to 6e-7; the
same program in bfloat16 reads 1e-2, a thousand times TOL), and every planted
fault has to move the number named for it by a hundred times TOL."""
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
from omnia_tpu.models import get_config, llama, model_module, stacks
from omnia_tpu.ops import attention as attn
from omnia_tpu.ops import moe
from omnia_tpu.ops.norms import rms_norm
from omnia_tpu.ops.rope import apply_rope

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


ref = _reference("mellum2_ref")
CFG = get_config("test-tiny-yarn")
PREFILL, DECODE = 40, 32
SHORT = 5          # a prompt that leaves its ring of 8 partly filled
TOL = 1e-5
# How a prompt of PREFILL tokens is placed: (real rows, bucket) a piece.
PLACEMENTS = {
    "one bucket": [(PREFILL, PREFILL)],
    # (the second starts at position 12: row 4 of a ring of 8)
    "pieces, the last padded": [(12, 16), (20, 32), (8, 16)],
    "pieces of exactly one ring, the last padded": [(8, 8)] * 4 + [(8, 16)],
}


def file_of(cfg) -> dict:
    """The keys of a configuration file that the reference reads, for `cfg`."""
    factor, original, fast, slow, attention_factor = cfg.rope_full_yarn
    return {
        "num_hidden_layers": cfg.num_layers, "layer_types": list(cfg.layer_types),
        "mlp_layer_types": ["sparse"] * cfg.num_layers,
        "sliding_window": cfg.sliding_window, "norm_topk_prob": True,
        "rope_parameters": {
            "sliding_attention": {"rope_type": "default", "rope_theta": cfg.rope_theta},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta, "factor": factor,
                "original_max_position_embeddings": original, "beta_fast": fast,
                "beta_slow": slow, "attention_factor": attention_factor}},
        "assumed": {"qk_norm": cfg.qk_norm},
    }


def _programs():
    """`step`, `piece`, `train` and `fresh` under `jax.jit`, the configuration
    a static argument: new functions a call, so traced anew. SOUND is the set
    every case on the sound path shares; a case that patches a function of
    the model, or routes the kernels, makes its own, because the shared set
    would hand it the trace of the sound path."""
    def step(p, c, toks, start, live=None, *, cfg):
        """One decode step of every slot of the cache: toks [B, 1], start [B]."""
        return llama.forward(p, cfg, toks, start[:, None], *c, start, live=live)

    def piece(p, c, toks, start, last, *, cfg, pad_is_real):
        """Every row's logits, the cache written as a placement writes it."""
        pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        every, *_ = llama.forward(p, cfg, toks, pos, *c, jnp.reshape(start, (1,)))
        _, *c = llama.forward(p, cfg, toks, pos, *c, jnp.reshape(start, (1,)),
                              row=None if pad_is_real else last)
        return every, *c

    def train(p, toks, *, cfg):
        """The uncached forward over the whole sequence."""
        return llama.forward_train(p, cfg, toks)

    def fresh(p, toks, row, *, cfg):
        """`forward_prefill` over a padded bucket whose last real row is `row`."""
        return llama.forward_prefill(p, cfg, toks,
                                     jnp.arange(toks.shape[1], dtype=jnp.int32)[None], row=row)

    return {"step": jax.jit(step, static_argnames="cfg"),
            "piece": jax.jit(piece, static_argnames=("cfg", "pad_is_real")),
            "train": jax.jit(train, static_argnames="cfg"),
            "fresh": jax.jit(fresh, static_argnames="cfg")}


SOUND = _programs()


def _padded(tokens, bucket):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(tokens)] = tokens
    return jnp.asarray(toks)


def served_logits(params, cfg, tokens, placement, cache=None, pad_is_real=False,
                  programs=SOUND):
    """The prompt placed into a cache (fresh, unless one is handed in) piece
    by piece (a padded piece names its last real row, as
    engine/programs.py::extend does), then one token a step through the
    cache: float32 [T, V]. Each piece gives the logits of its real rows."""
    if cache is None:
        cache = llama.init_kv_cache(cfg, 1, 128, dtype=params["embed"].dtype)
    step, piece = programs["step"], programs["piece"]
    out, at = [], 0
    for take, bucket in placement:
        logits, *cache = piece(params, cache, _padded(tokens[at:at + take], bucket),
                               jnp.int32(at), jnp.int32(take - 1), cfg=cfg,
                               pad_is_real=pad_is_real)
        out.append(np.asarray(logits[0, :take], np.float32))
        at += take
    for t in range(at, len(tokens)):
        logits, *cache = step(params, cache, jnp.asarray(tokens[None, t:t + 1]),
                              jnp.full((1,), t, jnp.int32), cfg=cfg)
        out.append(np.asarray(logits[0], np.float32))
    return np.concatenate(out)


@functools.partial(jax.jit, static_argnames="cfg")
def seeded_params(key, *, cfg=CFG):
    """`init_params` with the QK-norm's gains drawn around 1: with every gain
    1 a norm before and one after a rotation by an unscaled table are the
    same number, and the order could not be told."""
    params = llama.init_params(cfg, key, dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.fold_in(key, 7), 2 * len(params["layers"])))
    for stack in params["layers"]:
        for gain in ("qn", "kn"):
            stack["attn"][gain] = 1.0 + 0.5 * jax.random.normal(
                next(keys), stack["attn"][gain].shape, jnp.float32)
    return params


@pytest.fixture(scope="module")
def seeded():
    params = seeded_params(jax.random.key(0))
    tokens = np.random.default_rng(0).integers(0, CFG.vocab_size, PREFILL + DECODE)
    tokens = tokens.astype(np.int32)
    sizes = reference_sizes(CFG, file_of(CFG))
    want = np.asarray(ref.forward(params, sizes, jnp.asarray(tokens)))
    return params, tokens, sizes, want


def over_range(got, want):
    return float(np.abs(got - want).max() / (want.max() - want.min()))


def _dirty(cfg, dtype, slots=1):
    """A cache whose every row is a previous tenant's: large, so that one
    row of it read moves the logits by far more than any tolerance."""
    return tuple(3.0 + jax.random.normal(jax.random.key(9 + i), c.shape, dtype)
                 for i, c in enumerate(llama.init_kv_cache(cfg, slots, 128, dtype=dtype)))


def numbers(seeded, cfg=CFG, params=None, placement="pieces, the last padded",
            pad_is_real=False, programs=SOUND) -> dict:
    """The four numbers a fault is caught by, each a largest |logit
    difference| as a share of the reference's logit range: the uncached
    forward (`train`), the prompt's positions and the decode positions
    through the cache, and (`tenant`) the decode positions behind a prompt
    shorter than the ring, placed into a slot that another request left."""
    own, tokens, _, want = seeded
    params = own if params is None else params
    got = served_logits(params, cfg, tokens, PLACEMENTS[placement], pad_is_real=pad_is_real,
                        programs=programs)
    train = np.asarray(programs["train"](params, jnp.asarray(tokens[None]), cfg=cfg)[0])
    short = served_logits(params, cfg, tokens[:SHORT + 12], [(SHORT, 16)],
                          cache=_dirty(cfg, params["embed"].dtype), programs=programs)
    return {"train": over_range(train, want),
            "prefill": over_range(got[:PREFILL], want[:PREFILL]),
            "decode": over_range(got[PREFILL:], want[PREFILL:]),
            "tenant": over_range(short[SHORT:], want[SHORT:SHORT + 12])}


# -- (a) the program against the reference ------------------------------------


def test_the_preset_is_a_model_of_sparse_stacks_only():
    """No `dense_*` stack, no shared expert, no held share, no selection
    bias: the model is taken as what it is, and not as a cut of one that has
    them."""
    assert model_module(CFG) is llama and llama.is_stacked(CFG)
    assert llama.stack_kinds(CFG) == ("sparse_window", "sparse_full")
    assert llama.layer_order(CFG) == ((0, 0), (0, 1), (0, 2), (1, 0))
    assert llama.ring_rows(CFG) == CFG.sliding_window == 8
    assert (CFG.num_experts, CFG.experts_held, CFG.num_dense_layers) == (8, 8, 0)
    assert not CFG.router_bias and CFG.router_scoring == "softmax"
    assert llama.decode_counters(CFG) == ("moe_assignments_held", "moe_experts_hit")
    k, v, rk, rv = llama.init_kv_cache(CFG, 3, 64)
    assert k.shape == v.shape == (1, 3, 64, 2, 16)     # the full layer: whole contexts
    assert rk.shape == rv.shape == (3, 3, 8, 2, 16)    # the window layers: rings
    tree = jax.eval_shape(lambda: llama.init_params(CFG, jax.random.key(0)))
    assert [sorted(stack["mlp"]) for stack in tree["layers"]] == [["router", "wd", "wg", "wu"]] * 2
    assert tree["layers"][0]["mlp"]["wg"].shape == (3, 8, 64, 32)
    assert CFG.num_params() == sum(x.size for x in jax.tree.leaves(tree))
    specs = llama.param_specs(CFG)
    assert jax.tree.structure(specs, is_leaf=lambda s: not isinstance(s, (dict, list))) \
        == jax.tree.structure(tree)


def test_the_rotary_tables_are_one_a_kind_and_the_published_formula():
    """`rope_tables`: the window layers' plain pair and the full layers'
    YaRN pair, each equal to the reference's own table from the file's
    group; every model before this one keeps one pair for the kinds that
    rotate."""
    pos = jnp.arange(0, 400, 3, dtype=jnp.int32)   # (float32 angles: small positions)
    tables = stacks.rope_tables(CFG, pos)
    groups = file_of(CFG)["rope_parameters"]
    for kind, group in (("window", "sliding_attention"), ("full", "full_attention")):
        for got, want in zip(tables[kind], ref.rotary_table(groups[group], pos, CFG.head_dim)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    cos, sin = tables["full"]
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2),
                               CFG.rope_full_yarn[4] ** 2, rtol=1e-6)
    assert float(jnp.abs(tables["full"][0] - tables["window"][0]).max()) > 0.1
    older = get_config("test-tiny-window")
    assert sorted(stacks.rope_tables(older, pos)) == ["window"]   # NoPE full layers
    both = stacks.rope_tables(dataclasses.replace(older, rope_on_full_layers=True), pos)
    assert both["full"] is both["window"]


def test_the_uncached_forward_agrees_with_the_reference(seeded):
    assert numbers(seeded)["train"] <= TOL


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_prefill_then_decode_through_the_rings_agrees_with_the_reference(seeded, placement):
    """72 positions through rings of 8 rows: nine wraps, the prompt in one
    bucket or in pieces whose last is padded (one of them in pieces of
    exactly one ring, as a piece of 1,024 rows is of the served model's)."""
    got = numbers(seeded, placement=placement)
    assert max(got.values()) <= TOL, got


def test_a_prompt_placed_whole_beside_one_placed_in_pieces_in_one_batch(seeded):
    """Two slots of one cache, as the served queue fills them: slot 0 by one
    fresh prefill of a prompt shorter than its ring (`forward_prefill`'s
    chunks put where `prefill_insert` puts them: the ring partly filled, the
    rest of it whatever the chunk held), slot 1 in pieces that wrap its ring
    five times; then 40 decode steps of both in one batch, slot 0 reaching
    its ring's end and wrapping it while slot 1 is far past it. Both slots
    held a previous tenant's rows."""
    params, tokens, sizes, want = seeded
    short = np.random.default_rng(1).integers(0, CFG.vocab_size, SHORT + 40).astype(np.int32)
    want_short = np.asarray(ref.forward(params, sizes, jnp.asarray(short)))
    cache = list(_dirty(CFG, jnp.float32, slots=2))
    last, *chunks = SOUND["fresh"](params, _padded(short[:SHORT], 16), jnp.int32(SHORT - 1),
                                   cfg=CFG)
    assert over_range(np.asarray(last[0]), want_short[SHORT - 1]) <= TOL
    cache = [jax.lax.dynamic_update_slice(c, chunk, (0,) * 5) for c, chunk in zip(cache, chunks)]
    at = 0
    for take, bucket in PLACEMENTS["pieces, the last padded"]:
        view = [c[:, 1:2] for c in cache]
        _, *view = SOUND["piece"](params, view, _padded(tokens[at:at + take], bucket),
                                  jnp.int32(at), jnp.int32(take - 1), cfg=CFG,
                                  pad_is_real=False)
        cache = [c.at[:, 1:2].set(v) for c, v in zip(cache, view)]
        at += take
    for t in range(DECODE):
        start = jnp.asarray([SHORT + t, PREFILL + t], jnp.int32)
        toks = jnp.asarray([[short[SHORT + t]], [tokens[PREFILL + t]]])
        logits, *cache = SOUND["step"](params, cache, toks, start, cfg=CFG)
        assert over_range(np.asarray(logits[0, 0]), want_short[SHORT + t]) <= TOL, t
        assert over_range(np.asarray(logits[1, 0]), want[PREFILL + t]) <= TOL, t


def test_a_pair_of_sparse_window_layers_is_cut_out_as_the_check_cuts_it(seeded):
    """`with_layer_order`, as harness/correct.py cuts one- and two-layer
    models: here both layers of the pair are sparse window layers, the full
    stack is left with none, and the cache's whole-context arrays have no
    layer."""
    pair = llama.with_layer_order(CFG, ((0, 0), (0, 1)))
    assert llama.stack_kinds(pair) == llama.stack_kinds(CFG) and pair.num_dense_layers == 0
    assert llama.layer_order(pair) == ((0, 0), (0, 1)) and pair.has_window_layers
    assert [c.shape[0] for c in llama.init_kv_cache(pair, 1, 16)] == [0, 0, 2, 2]
    alone = llama.with_layer_order(CFG, ((1, 0),))
    assert [c.shape[0] for c in llama.init_kv_cache(alone, 1, 16)] == [1, 1, 0, 0]
    params, tokens, sizes, _ = seeded
    for cut, order in ((pair, ((0, 0), (0, 1))), (alone, ((1, 0),))):
        keep = [sum(s == k for s, _ in order) for k in range(2)]
        sub = {**params, "layers": [jax.tree_util.tree_map(lambda a, n=n: a[:n], stack)
                                    for stack, n in zip(params["layers"], keep)]}
        want = np.asarray(ref.forward(sub, {**sizes, "layer_order": order}, jnp.asarray(tokens)))
        got = served_logits(sub, cut, tokens, PLACEMENTS["pieces, the last padded"])
        assert over_range(got, want) <= TOL


def test_bfloat16_where_float32_is_stated_fails_the_tolerance(seeded):
    """The tolerance is tight enough to tell the precision: the same sound
    program on the same weights rounded to bfloat16 is a thousand times out."""
    params, tokens, _, want = seeded
    half = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    got = np.asarray(SOUND["train"](half, jnp.asarray(tokens[None]), cfg=CFG)[0], np.float32)
    assert over_range(got, want) >= 100 * TOL


# -- (b) planted faults --------------------------------------------------------

YARN = CFG.rope_full_yarn


_rope_tables = stacks.rope_tables


def _full_table_everywhere(cfg, positions):
    tables = _rope_tables(cfg, positions)
    return {"window": tables["full"], "full": tables["full"]}


def _norm_after_rotation():
    """`rms_norm` of a head ([B, T, H, d]) hands its input on unnormed and
    `apply_rope` norms what it has rotated: QK-norm after the rotation."""
    gains = []

    def norm(x, gain, eps):
        if x.ndim != 4:
            return rms_norm(x, gain, eps)
        gains.append((gain, eps))
        return x

    def rope(x, cos, sin):
        return rms_norm(apply_rope(x, cos, sin), *gains.pop(0))

    return norm, rope


def _ring_read_whole(q, ring_k, ring_v, q_positions, layer, live, window):
    """`ring_decode_attention`'s einsum without "a row the slot's positions
    have not reached is the previous tenant's"."""
    B, _, H, D = q.shape
    R, Hkv = ring_k.shape[2:4]
    k = jax.lax.dynamic_index_in_dim(ring_k, layer, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(ring_v, layer, 0, keepdims=False)
    scores = jnp.einsum("bhgd,bshd->bhgs", q[:, 0].reshape(B, Hkv, H // Hkv, D), k,
                        preferred_element_type=jnp.float32) * (D**-0.5)
    back = (q_positions - jnp.arange(R, dtype=jnp.int32)[None, :]) & (R - 1)
    probs = jax.nn.softmax(jnp.where((back < window)[:, None, None, :], scores, -1e30), axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", probs.astype(v.dtype), v).reshape(B, 1, H, D)


def _not_renormalised(logits, k, scoring="softmax", bias=None):
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)


def _with_shared(params):
    """A shared expert's matrices in every stack: `expert_ffn` adds its term."""
    def shared(stack, key):
        mlp = stack["mlp"]
        keys = jax.random.split(key, 3)
        made = {name: 0.02 * jax.random.normal(k, (mlp[name].shape[0], *mlp[name].shape[2:]))
                for name, k in zip(("wg", "wu", "wd"), keys)}
        return {**stack, "mlp": {**mlp, "shared": made}}

    return {**params, "layers": [shared(stack, jax.random.key(20 + i))
                                 for i, stack in enumerate(params["layers"])]}


def _patches(fault):
    """(module, attribute, replacement) for the faults planted in code."""
    if fault == "QK-norm after rotation":
        norm, rope = _norm_after_rotation()
        return [(stacks, "rms_norm", norm), (stacks, "apply_rope", rope)]
    return {
        "the full layers' table on a window layer": [
            (llama, "rope_tables", _full_table_everywhere)],   # `_embed` calls llama's name
        "a previous tenant's ring row read": [
            (attn, "ring_decode_attention", _ring_read_whole)],
        "the kept weights not renormalised": [(moe, "top_k_weights", _not_renormalised)],
    }.get(fault, [])


# name -> (the number it must show in, what to replace in CFG, the parameters'
# change, whether pad rows count as real); what is planted in code: `_patches`
FAULTS = {
    "the window layers' table on a full layer": ("train", {"rope_full_yarn": None}, None, False),
    "the full layers' table on a window layer": ("train", {}, None, False),
    "the attention factor left out": ("train", {"rope_full_yarn": (*YARN[:4], 1.0)}, None, False),
    "the factor folded in once, not squared": (
        "train", {"rope_full_yarn": (*YARN[:4], YARN[4] ** 0.5)}, None, False),
    "YaRN's blend with beta_fast and beta_slow swapped": (
        "train", {"rope_full_yarn": (YARN[0], YARN[1], YARN[3], YARN[2], YARN[4])}, None, False),
    "a window one row too wide": ("train", {"sliding_window": 9}, None, False),
    "a ring row written from a pad row": ("decode", {}, None, True),
    "a previous tenant's ring row read": ("tenant", {}, None, False),
    "QK-norm after rotation": ("train", {}, None, False),
    "the kept weights not renormalised": ("train", {}, None, False),
    "sigmoid for softmax": ("train", {"router_scoring": "sigmoid"}, None, False),
    "a shared expert's term added": ("train", {}, _with_shared, False),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_by_a_hundred_tolerances(seeded, fault, monkeypatch):
    number, replace, change, pad_is_real = FAULTS[fault]
    patches = _patches(fault)
    for patch in patches:
        monkeypatch.setattr(*patch)
    params = change(seeded[0]) if change else None
    got = numbers(seeded, dataclasses.replace(CFG, **replace), params, pad_is_real=pad_is_real,
                  programs=_programs() if patches else SOUND)
    assert got[number] >= 100 * TOL, (fault, got)


def test_the_sound_run_passes_where_each_fault_is_looked_for(seeded):
    got = numbers(seeded)
    assert len(FAULTS) >= 10 and all(got[FAULTS[f][0]] <= TOL for f in FAULTS), got


# -- the decode kernel over a ring of several blocks ---------------------------


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "interpret")
    attn._pallas_decode_mode.cache_clear()
    yield
    attn._pallas_decode_mode.cache_clear()


@pytest.mark.parametrize("H,Hkv,D,flat", [(4, 2, 16, False), (32, 4, 128, True)],
                         ids=["2x16", "the-served-4x128"])
def test_the_window_kernel_over_a_ring_of_four_blocks(interpreted, monkeypatch, H, Hkv, D, flat):
    """`decode_window_attention` (interpreted) at the served ring: 1,024 rows
    in four blocks of 256, the window the whole ring, at the tiny widths (a
    block `[256, 2, 16]`) and at the served 32 heads on 4 of 128 (a block
    `[1024, 128]`, the heads among the rows). Slots that have not
    reached the ring's end (positions 3, 300 and 1,022: the kernel spans one,
    two and four blocks) beside slots that have wrapped it once and five
    times, and a dead one; the rows a slot's positions have not reached are
    poisoned, in the blocks it reads and in those it must not."""
    from omnia_tpu.ops.decode_attention import flat_rows

    B, R = 6, 1024
    assert attn.decode_block_rows(R) == 256 and flat_rows(Hkv, D, jnp.float32, 256) == flat
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (B, 1, H, D))
    rk = jax.random.normal(keys[1], (2, B, R, Hkv, D))
    rv = jax.random.normal(keys[2], (2, B, R, Hkv, D))
    positions = [3, 300, 1022, 1024, 5500, 77]
    for slot, p in enumerate(positions[:3]):
        rk, rv = rk.at[:, slot, p + 1:].set(1e9), rv.at[:, slot, p + 1:].set(1e9)
    pos = jnp.asarray(positions, jnp.int32)[:, None]
    live = jnp.asarray([1, 1, 1, 1, 1, 0])
    got = np.asarray(attn.ring_decode_attention(q, rk, rv, pos, 1, live, R))
    attn._pallas_decode_mode.cache_clear()
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "0")
    want = np.asarray(attn.ring_decode_attention(q, rk, rv, pos, 1, live, R))
    assert np.all(got[5] == 0) and np.isfinite(got).all()
    np.testing.assert_allclose(got[:5], want[:5], atol=2e-6, rtol=1e-5)
    # by hand, the slot that wrapped five times: positions 4477..5500 at rows p % R
    p = np.arange(5500 - R + 1, 5501)
    k, v = np.asarray(rk[1, 4])[p % R], np.asarray(rv[1, 4])[p % R]
    s = np.einsum("hgd,shd->hgs", np.asarray(q[4, 0]).reshape(Hkv, H // Hkv, D), k) / np.sqrt(D)
    w = np.exp(s - s.max(-1, keepdims=True))
    by_hand = np.einsum("hgs,shd->hgd", w / w.sum(-1, keepdims=True), v).reshape(H, D)
    np.testing.assert_allclose(got[4, 0], by_hand, atol=2e-6, rtol=1e-5)
    # the counter the window kernel's roofline reads spans the same blocks
    cfg = dataclasses.replace(CFG, sliding_window=R)
    assert stacks.decode_window_rows(cfg, positions[:5]) == 256 * (1 + 2 + 4 + 4 + 4)


def test_decode_through_the_kernels_agrees_with_the_reference(seeded, interpreted, monkeypatch):
    """Both decode kernels interpreted, the rings in two blocks of 4 rows."""
    monkeypatch.setattr(attn, "_DECODE_BLOCK_S", 4)
    got = numbers(seeded, placement="one bucket", programs=_programs())
    assert got["decode"] <= TOL and got["tenant"] <= TOL, got


# -- through the engine ---------------------------------------------------------


def test_the_engine_serves_short_and_long_prompts_in_one_queue_over_rings():
    """`InferenceEngine` on the normal path, both placement routes live in
    one queue: prompts that fit a bucket (`prefill_insert`; one of 5 tokens
    leaves its ring partly filled) beside prompts longer than the largest
    (through `extend` in pieces, the last padded), 40 decode steps each, and
    a second round into the same slots. Every served token is the largest
    logit of the module's own uncached forward over the tokens before it, to
    within the two paths' rounding."""
    ecfg = EngineConfig(num_slots=3, max_seq=256, prefill_buckets=(16, 32), max_sessions=0,
                        decode_chunk=4, dtype="float32")
    engine = InferenceEngine(CFG, ecfg, seed=3)
    assert engine.model_module is llama and len(engine._cache) == 4
    assert engine.kv_bytes_per_token() == 1 * 2 * 16 * 4 * 2    # the full layer's rows alone
    engine.warmup(sessions=True)
    engine.start()
    rng = np.random.default_rng(0)
    forward = jax.jit(lambda p, t: llama.forward_train(p, CFG, t))
    lengths = (45, 5, 70, 13)
    try:
        for _ in range(2):
            prompts = [[int(t) for t in rng.integers(0, 256, size=n)] for n in lengths]
            handles = [engine.submit(p, SamplingParams(max_tokens=40, temperature=0.0,
                                                       stop_token_ids=())) for p in prompts]
            for prompt, handle in zip(prompts, handles):
                out = [ev.token_id for ev in handle.events()
                       if getattr(ev, "token_id", None) is not None]
                assert len(out) == 40
                logits = np.asarray(forward(engine.params, jnp.asarray([prompt + out]))[0])
                rows = logits[len(prompt) - 1:len(prompt) + 39]
                assert np.all(rows.max(-1) - rows[np.arange(40), out] <= 1e-4)
    finally:
        engine.stop()
    m = engine.metrics
    assert m["prefill_tokens"] == 2 * sum(lengths)
    assert m["extend_tokens"] == 2 * (45 + 70)      # those placed in pieces
    assert m["extend_steps"] == 2 * (2 + 3) and m["decode_steps"] > 0
    assert 0 < m["moe_experts_hit"] <= m["decode_steps"] * 4 * CFG.experts_held
    # every expert is held: every assignment of every slot's row lands, a layer a step
    assert m["moe_assignments_held"] == m["decode_steps"] * 4 * 3 * CFG.num_experts_per_tok
    assert m["decode_window_rows"] == 8 * m["decode_slot_steps"]


# -- what is refused ------------------------------------------------------------


@pytest.mark.parametrize("feature,asked", [("max_sessions", {"max_sessions": 4}),
                                           ("prefix_cache_slots", {"prefix_cache_slots": 2}),
                                           ("spec_decode", {"spec_decode": 4})])
def test_what_rings_cannot_do_is_still_refused_by_name(feature, asked):
    ecfg = EngineConfig(**{"num_slots": 2, "max_seq": 256, "prefill_buckets": (64,),
                           "max_sessions": 0, **asked})
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}=.*not ported to a "
                                                  r"model of several kinds of layers.*ring"):
        refuse_unported(CFG, ecfg)
