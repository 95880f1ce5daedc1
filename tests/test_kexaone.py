"""The pair family with layers of several kinds (models/llama.py's stacks: a
period of window and full attention layers, a 128-row-style ring beside
whole contexts, NoPE full layers, QK-norm, a leading dense layer and then the
dropless expert share) against the plain reference of the model it was
written for, `benchmark/reference/kexaone_ref.py`, at `test-tiny-window`'s
size: a window of 8 rows, so a ring of 8, which sequences of 72 tokens wrap
nine times.

Logits are compared and never tokens. Everything is float32 on the CPU, so
the program and the reference differ by the order of their sums alone: TOL
is 1e-5 of the reference's logit range (readings here are 1e-7 to 5e-7), and
every planted fault has to move the number named for it by a hundred times
that."""
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
from omnia_tpu.engine.programs import build_programs
from omnia_tpu.engine.types import EngineConfig, SamplingParams
from omnia_tpu.models import get_config, llama, model_module, stacks
from omnia_tpu.ops import attention as attn
from omnia_tpu.ops import moe

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


ref = _reference("kexaone_ref")
CFG = get_config("test-tiny-window")
PREFILL, DECODE = 40, 32
TOL = 1e-5
# How a prompt of PREFILL tokens is placed: (real rows, bucket) a piece.
PLACEMENTS = {
    "one bucket": [(PREFILL, PREFILL)],
    # (the second starts at position 12: row 4 of a ring of 8)
    "pieces, the last padded": [(12, 16), (20, 32), (8, 16)],
    "pieces longer than the ring's spare rows, padded": [(32, 32), (8, 32)],
}


def file_of(cfg) -> dict:
    """The keys of a configuration file that the reference reads, for `cfg`."""
    sparse = cfg.num_layers - cfg.num_dense_layers
    return {
        "num_hidden_layers": cfg.num_layers, "layer_types": list(cfg.layer_types),
        "mlp_layer_types": ["dense"] * cfg.num_dense_layers + ["sparse"] * sparse,
        "sliding_window": cfg.sliding_window, "expert_rank": cfg.expert_rank,
        "rope_parameters": {"rope_theta": cfg.rope_theta, "rope_type": "default"},
        "scoring_func": cfg.router_scoring, "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": cfg.routed_scaling_factor,
        "assumed": {"qk_norm": cfg.qk_norm, "rope_on_full_layers": cfg.rope_on_full_layers},
    }


def _programs():
    """`step`, `piece`, `train` and `fresh` under `jax.jit`, the configuration
    a static argument: new functions a call, so traced anew. SOUND is the set
    every case on the sound path shares (a configuration, a placement's
    shapes and `pad_is_real` each compile once a module); a case that patches
    a function of the model, or routes the kernels, makes its own, because
    the shared set would hand it the trace of the sound path."""
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


def served_logits(params, cfg, tokens, placement, rows: int = 128, pad_is_real=False,
                  programs=SOUND):
    """The prompt placed into a fresh cache piece by piece (a padded piece
    names its last real row, as engine/programs.py::extend does), then one
    token a step through the cache: float32 [T, V]. Each piece gives the
    logits of its real rows."""
    cache = llama.init_kv_cache(cfg, 1, rows, dtype=params["embed"].dtype)
    step, piece = programs["step"], programs["piece"]
    out, at = [], 0
    for take, bucket in placement:
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :take] = tokens[at:at + take]
        logits, *cache = piece(params, cache, jnp.asarray(toks), jnp.int32(at),
                               jnp.int32(take - 1), cfg=cfg, pad_is_real=pad_is_real)
        out.append(np.asarray(logits[0, :take], np.float32))
        at += take
    for t in range(at, len(tokens)):
        logits, *cache = step(params, cache, jnp.asarray(tokens[None, t:t + 1]), jnp.int32(t),
                              cfg=cfg)
        out.append(np.asarray(logits[0], np.float32))
    return np.concatenate(out)


@functools.partial(jax.jit, static_argnames="cfg")
def seeded_params(key, *, cfg=CFG):
    return llama.init_params(cfg, key, dtype=jnp.float32)


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


def numbers(seeded, cfg=CFG, params=None, placement="pieces, the last padded",
            pad_is_real=False, programs=SOUND) -> dict:
    """The three numbers a fault is caught by, each a largest |logit
    difference| as a share of the reference's logit range: the uncached
    forward (`train`), and the prompt's positions and the decode positions
    through the cache."""
    own, tokens, _, want = seeded
    params = own if params is None else params
    got = served_logits(params, cfg, tokens, PLACEMENTS[placement], pad_is_real=pad_is_real,
                        programs=programs)
    train = np.asarray(programs["train"](params, jnp.asarray(tokens[None]), cfg=cfg)[0])
    return {"train": over_range(train, want),
            "prefill": over_range(got[:PREFILL], want[:PREFILL]),
            "decode": over_range(got[PREFILL:], want[PREFILL:])}


# -- (a) the program against the reference ------------------------------------


def test_the_preset_is_the_shape_the_issue_names():
    assert model_module(CFG) is llama and llama.is_stacked(CFG)
    assert llama.stack_kinds(CFG) == ("dense_window", "sparse_window", "sparse_full")
    assert llama.layer_order(CFG) == ((0, 0), (1, 0), (2, 0))
    assert llama.ring_rows(CFG) == CFG.sliding_window == 8
    assert (CFG.num_experts, CFG.experts_held, CFG.num_dense_layers) == (8, 4, 1)
    assert llama.decode_counters(CFG) == ("moe_assignments_held", "moe_experts_hit")
    assert not llama.is_stacked(get_config("test-tiny")) and not llama.decode_counters(
        get_config("test-tiny"))
    k, v, rk, rv = llama.init_kv_cache(CFG, 3, 64)
    assert k.shape == v.shape == (1, 3, 64, 2, 16)     # the full layer: whole contexts
    assert rk.shape == rv.shape == (2, 3, 8, 2, 16)    # the window layers: rings


def test_the_seeded_selection_bias_is_one_set_of_values_in_a_seeded_order():
    """The midpoints of N(0, 0.05)'s equal shares, a rank's count of them, in
    an order the seed draws and the same on every rank: non-zero, so that it
    changes which experts are kept, and neither a rank's load nor the count
    of experts a step's tokens hit, and no cell's rate with them, follows
    the seed."""
    ranks, held = CFG.num_experts // CFG.experts_held, CFG.experts_held
    orders = []
    for seed in (0, 2965719344 & 0x7FFFFFFF):
        params = seeded_params(jax.random.key(seed))
        for stack in params["layers"]:
            if "bias" not in stack["mlp"]:
                continue
            bias = np.asarray(stack["mlp"]["bias"])
            assert bias.dtype == np.float32 and bias.shape[1] == CFG.num_experts
            shares = bias.reshape(bias.shape[0], ranks, held)
            assert (shares == shares[:, :1]).all()
            np.testing.assert_allclose(
                np.sort(shares[:, 0], axis=-1),
                [[-0.05751746, -0.01593197, 0.01593197, 0.05751746]] * len(shares), atol=1e-7)
            orders += [tuple(np.argsort(row)) for row in shares[:, 0]]
    assert len(set(orders)) > 1


def test_the_uncached_forward_agrees_with_the_reference(seeded):
    assert numbers(seeded)["train"] <= TOL


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_prefill_then_decode_through_the_rings_agrees_with_the_reference(seeded, placement):
    """72 positions through rings of 8 rows: nine wraps, the prompt in one
    bucket or in pieces whose last is padded (one of them with pieces of 32
    rows, four times the ring)."""
    got = numbers(seeded, placement=placement)
    assert got["prefill"] <= TOL and got["decode"] <= TOL, got


def test_a_fresh_prefill_returns_the_rings_it_would_have_written(seeded):
    """`forward_prefill`'s chunks are `prefill_insert`'s operands: the window
    layers' come in the ring's shape, and decode from them agrees."""
    params, tokens, _, want = seeded
    n, bucket = 21, 32
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = tokens[:n]
    last, k, v, rk, rv = SOUND["fresh"](params, jnp.asarray(toks), jnp.int32(n - 1), cfg=CFG)
    assert over_range(np.asarray(last[0]), want[n - 1]) <= TOL
    assert k.shape == (1, 1, bucket, 2, 16) and rk.shape == (2, 1, 8, 2, 16)
    cache = [jax.lax.dynamic_update_slice(c, chunk, (0,) * 5)
             for c, chunk in zip(llama.init_kv_cache(CFG, 1, 128, dtype=jnp.float32),
                                 (k, v, rk, rv))]
    for t in range(n, n + 12):
        logits, *cache = SOUND["step"](params, cache, jnp.asarray(tokens[None, t:t + 1]),
                                       jnp.int32(t), cfg=CFG)
        assert over_range(np.asarray(logits[0, 0]), want[t]) <= TOL, t


def test_a_model_cut_out_of_the_period_keeps_its_stacks(seeded):
    """`with_layer_order`, as harness/correct.py cuts one- and two-layer
    models: the stacks stay, some with none, and the cache has an array of
    no layers where a kind is absent."""
    cut = llama.with_layer_order(CFG, ((1, 0),))
    assert llama.stack_kinds(cut) == llama.stack_kinds(CFG) and cut.num_dense_layers == 0
    assert llama.layer_order(cut) == ((1, 0),) and cut.has_window_layers
    assert [c.shape[0] for c in llama.init_kv_cache(cut, 1, 16)] == [0, 0, 1, 1]
    pair = llama.with_layer_order(CFG, ((1, 0), (2, 0)))
    assert llama.layer_order(pair) == ((1, 0), (2, 0))
    with pytest.raises(ValueError, match="dense layers first"):
        llama.with_layer_order(CFG, ((1, 0), (0, 0)))
    params, tokens, sizes, _ = seeded
    sub = {**params, "layers": [jax.tree_util.tree_map(lambda a: a[:0], params["layers"][0]),
                                params["layers"][1], params["layers"][2]]}
    want = np.asarray(ref.forward(sub, {**sizes, "layer_order": ((1, 0), (2, 0))},
                                  jnp.asarray(tokens)))
    got = served_logits(sub, pair, tokens, PLACEMENTS["pieces, the last padded"])
    assert over_range(got, want) <= TOL


# -- (b) planted faults --------------------------------------------------------


def _s_plus_b_as_weight(logits, k, scoring="softmax", bias=None):
    scores = jax.nn.sigmoid(logits) + bias.astype(logits.dtype)
    top_w, top_i = jax.lax.top_k(scores, k)
    return top_w / top_w.sum(axis=-1, keepdims=True), top_i


def _rows_by_index(ring, start, window, layer):
    return jax.lax.dynamic_index_in_dim(ring, layer, 0, keepdims=False)[:, :window]


def _without_shared(params):
    layers = [{**stack, "mlp": {k: v for k, v in stack["mlp"].items() if k != "shared"}}
              for stack in params["layers"]]
    return {**params, "layers": layers}


# name -> (the number it must show in, what to replace in CFG, (module, attribute,
# replacement) to patch, the parameters' change, whether pad rows count as real)
FAULTS = {
    "window off by one (i - j <= 8)": ("train", {"sliding_window": 9}, None, None, False),
    "window layers attend everything": ("train", {"sliding_window": 128}, None, None, False),
    "rotary on the full layer": ("train", {"rope_on_full_layers": True}, None, None, False),
    "no rotary on a window layer": (
        "train", {}, (stacks, "apply_rope", lambda x, cos, sin: x), None, False),
    "QK-norm dropped": ("train", {"qk_norm": False}, None, None, False),
    "a padded piece wraps onto live ring rows": ("decode", {}, None, None, True),
    "the ring read by row index, not by held position": (
        "prefill", {}, (stacks, "_ring_rows_before", _rows_by_index), None, False),
    "s + b used as a weight": (
        "train", {}, (moe, "top_k_weights", _s_plus_b_as_weight), None, False),
    "the shared expert left out": ("train", {}, None, _without_shared, False),
    "the layer kinds in another order": (
        "train", {"layer_types": ("sliding_attention", "full_attention", "sliding_attention")},
        None, None, False),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_by_a_hundred_tolerances(seeded, fault, monkeypatch):
    number, replace, patch, change, pad_is_real = FAULTS[fault]
    if patch:
        monkeypatch.setattr(*patch)
    params = change(seeded[0]) if change else None
    got = numbers(seeded, dataclasses.replace(CFG, **replace), params, pad_is_real=pad_is_real,
                  programs=_programs() if patch else SOUND)
    assert got[number] >= 100 * TOL, (fault, got)


def test_the_sound_run_passes_where_each_fault_is_looked_for(seeded):
    got = numbers(seeded)
    assert all(got[FAULTS[f][0]] <= TOL for f in FAULTS), got


def test_a_dead_slots_decode_step_leaves_its_ring_alone(seeded):
    """A slot that is not live is between tenants or mid-placement: a decode
    step writes its whole-context row (past the frontier, as ever) and not
    its ring, whose every row is still inside the window."""
    params, tokens, _, _ = seeded
    cache = tuple(c + 1.0 for c in llama.init_kv_cache(CFG, 2, 32, dtype=jnp.float32))
    live = jnp.asarray([True, False])
    _, k, v, rk, rv = llama.forward(
        params, CFG, jnp.asarray(tokens[:2, None]), jnp.full((2, 1), 11, jnp.int32), *cache,
        jnp.full((2,), 11, jnp.int32), live=live)
    assert np.all(np.asarray(rk[:, 1]) == 1.0) and np.all(np.asarray(rv[:, 1]) == 1.0)
    assert np.any(np.asarray(rk[:, 0, 11 % 8]) != 1.0)
    assert np.all(np.asarray(rk[:, 0, [r for r in range(8) if r != 11 % 8]]) == 1.0)


# -- the decode kernel over a ring --------------------------------------------


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "interpret")
    attn._pallas_decode_mode.cache_clear()
    yield
    attn._pallas_decode_mode.cache_clear()


def test_the_window_kernel_equals_the_einsum_and_reads_no_other_tenants_row(interpreted,
                                                                            monkeypatch):
    """`decode_window_attention` (interpreted) against the same mask by
    einsum, at positions before the ring has filled, as it wraps and far
    past it, a ring of two blocks with the window inside it; rows that the
    slot's positions have not reached are poisoned, a dead slot reads
    nothing and returns zeros."""
    B, H, Hkv, D, R, W = 4, 4, 2, 16, 16, 12
    monkeypatch.setattr(attn, "_DECODE_BLOCK_S", 8)
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (B, 1, H, D))
    rk = jax.random.normal(keys[1], (2, B, R, Hkv, D))
    rv = jax.random.normal(keys[2], (2, B, R, Hkv, D))
    pos = jnp.asarray([[3], [15], [16], [1000]], jnp.int32)
    rk = rk.at[:, 0, 4:].set(1e9)  # slot 0 has written rows 0..3 only
    rv = rv.at[:, 0, 4:].set(1e9)
    live = jnp.asarray([1, 1, 0, 1])
    got = attn.ring_decode_attention(q, rk, rv, pos, 1, live, W)
    attn._pallas_decode_mode.cache_clear()
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "0")
    want = attn.ring_decode_attention(q, rk, rv, pos, 1, live, W)
    assert np.all(np.asarray(got[2]) == 0)
    np.testing.assert_allclose(np.asarray(got)[[0, 1, 3]], np.asarray(want)[[0, 1, 3]],
                               atol=2e-6, rtol=1e-5)
    # by hand, slot 3: position 1000 sees positions 989..1000, at rows p % 16
    p = np.arange(1000 - W + 1, 1001)
    k, v = np.asarray(rk[1, 3])[p % R], np.asarray(rv[1, 3])[p % R]     # [W, Hkv, D]
    qh = np.asarray(q[3, 0]).reshape(Hkv, H // Hkv, D)
    s = np.einsum("hgd,shd->hgs", qh, k) / np.sqrt(D)
    w = np.exp(s - s.max(-1, keepdims=True))
    by_hand = np.einsum("hgs,shd->hgd", w / w.sum(-1, keepdims=True), v).reshape(H, D)
    np.testing.assert_allclose(np.asarray(got[3, 0]), by_hand, atol=2e-6, rtol=1e-5)


def test_decode_through_the_kernels_agrees_with_the_reference(seeded, interpreted):
    """Both decode kernels interpreted (the full layer's
    `decode_gqa_attention`, the window layers' `decode_window_attention`)."""
    got = numbers(seeded, placement="one bucket", programs=_programs())
    assert got["decode"] <= TOL, got


# -- (c) the share -------------------------------------------------------------


@pytest.mark.parametrize("ranks", [1, 2, 8])
def test_the_shares_routed_parts_and_the_shared_expert_once_equal_the_uncut_layer(seeded, ranks):
    """`model-configs` section 4 through the pair family's own layer
    (`expert_ffn` on a sparse stack of `llama.init_params`): what each of
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


# -- (d) through the engine ---------------------------------------------------


def test_the_engine_serves_it_through_pieces_rings_and_reused_slots():
    """`InferenceEngine` on the normal path: prompts longer than the largest
    bucket (placed through `extend` in pieces whose last is padded), one that
    fits a bucket (`prefill_insert`), 40 decode steps each (five wraps of the
    ring), and a second round of requests into the same two slots: a ring
    must not leak the previous tenant's rows. Every served token is the
    largest logit of the module's own uncached forward over the tokens
    before it, to within the two paths' rounding."""
    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(16, 32), max_sessions=0,
                        decode_chunk=4, dtype="float32")
    engine = InferenceEngine(CFG, ecfg, seed=3)
    assert engine.model_module is llama and len(engine._cache) == 4
    assert engine.kv_bytes_per_token() == 1 * 2 * 16 * 4 * 2    # the full layer's rows alone
    engine.warmup()
    engine.start()
    rng = np.random.default_rng(0)
    forward = jax.jit(lambda p, t: llama.forward_train(p, CFG, t))
    try:
        for _ in range(2):
            prompts = [[int(t) for t in rng.integers(0, 256, size=n)] for n in (45, 70, 13)]
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
    assert m["extend_steps"] > 0 and m["decode_steps"] > 0
    assert 0 < m["moe_experts_hit"] <= m["decode_steps"] * 2 * CFG.experts_held
    # every live slot spans its ring's one block of 8 rows, a window layer a step
    assert m["decode_window_rows"] == 8 * m["decode_slot_steps"]
    assert m["decode_kv_blocks"] == m["decode_slot_steps"]  # 256 rows: one block a slot


# -- (e) what is refused -------------------------------------------------------

REFUSED = {"kv_quant": {"kv_quant": "int8"}, "kv_pages": {"kv_pages": 8},
           "max_sessions": {"max_sessions": 4}, "prefix_cache_slots": {"prefix_cache_slots": 2},
           "spec_decode": {"spec_decode": 4}, "prefill_chunk_tokens": {"prefill_chunk_tokens": 64},
           "quant": {"quant": "int8"}, "sp": {"sp": 2}, "tp": {"tp": 2}, "dp": {"dp": 2}}


@pytest.mark.parametrize("feature", list(REFUSED))
def test_a_model_of_several_kinds_refuses_what_assumes_rows_by_position(feature):
    ecfg = EngineConfig(**{"num_slots": 2, "max_seq": 256, "prefill_buckets": (64,),
                           "max_sessions": 0, **REFUSED[feature]})
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}=.*not ported to a "
                                                  r"model of several kinds of layers.*: \w"):
        refuse_unported(CFG, ecfg)
    if feature not in ("sp", "tp", "dp"):  # those need devices before they are refused
        with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}="):
            InferenceEngine(CFG, ecfg)
    refuse_unported(get_config("test-tiny"), ecfg)  # one tree, a K and a V: nothing refused


# -- (f) a model whose layers are all alike is what it was --------------------

# Each program's lowered text (StableHLO) by its number of lines, of operations,
# and a digest of how many there are of each operation, as the parent commit
# (0e9f43d) gave them for these shapes; `python tests/test_kexaone.py` prints
# them anew.
ALIKE = EngineConfig(num_slots=4, max_seq=256, prefill_buckets=(32,), max_sessions=0,
                     decode_chunk=4)
def _lowered(name: str, preset: str):
    cfg = get_config(preset)
    programs = build_programs(cfg, ALIKE, None)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: llama.init_kv_cache(cfg, ALIKE.num_slots, ALIKE.max_seq))
    B = ALIKE.num_slots

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((B, *tail), dtype)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    if name == "decode_chunk":
        from omnia_tpu.engine.types import MAX_DEVICE_STOP_IDS

        lowered = programs.decode_fns[4].lower(
            params, *cache, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), vec(jnp.int32),
            vec(jnp.int32, MAX_DEVICE_STOP_IDS), vec(jnp.uint32, 2), vec(jnp.float32),
            vec(jnp.float32), vec(jnp.int32))
    else:
        lowered = programs.prefill_insert.lower(
            params, *cache, arg(jnp.int32, 1, 32), arg(jnp.int32, 1, 32), arg(jnp.int32),
            arg(jnp.int32), arg(jnp.uint32, 2), arg(jnp.float32), arg(jnp.float32),
            arg(jnp.int32))
    return lowered.as_text()


def _census(text: str) -> list:
    import collections
    import hashlib
    import json
    import re

    ops = collections.Counter(re.findall(r"= \"?((?:stablehlo|func|chlo)\.[\w.]+)", text))
    digest = hashlib.sha256(json.dumps(dict(ops), sort_keys=True).encode()).hexdigest()[:16]
    return [len(text.splitlines()), sum(ops.values()), digest]


PARENT_PROGRAMS = {
    "test-tiny.decode_chunk": [
        960,
        843,
        "ca72a6ca8671853c"
    ],
    "test-tiny.prefill_insert": [
        784,
        677,
        "20248b4449462ce0"
    ],
    "test-tiny-moe.decode_chunk": [
        1009,
        888,
        "d95e808a6b6c7565"
    ],
    "test-tiny-moe.prefill_insert": [
        833,
        722,
        "c6775c6b917cc729"
    ]
}


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-moe"])
@pytest.mark.parametrize("program", ["decode_chunk", "prefill_insert"])
def test_a_model_whose_layers_are_all_alike_compiles_to_the_parents_program(program, preset):
    assert _census(_lowered(program, preset)) == PARENT_PROGRAMS[f"{preset}.{program}"]


if __name__ == "__main__":
    import json

    print(json.dumps({f"{preset}.{program}": _census(_lowered(program, preset))
                      for preset in ("test-tiny", "test-tiny-moe")
                      for program in ("decode_chunk", "prefill_insert")}, indent=1))
