"""The latent family with two stacks of unlike layers, a residual of four
copies and a sigmoid router with a selection bias (models/mla.py as it serves
Xing4.0-29B-A4B) against the benchmark's plain reference
(benchmark/reference/xing4_ref.py), at tiny widths on the CPU with seeded
weights, and seven planted faults that must each fail by a named number."""
import contextlib
import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.models import get_config, mla, model_module, quant
from omnia_tpu.ops import hyper_connections as hc
from omnia_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

_spec = importlib.util.spec_from_file_location(
    "xing4_ref", os.path.join(BENCH, "reference", "xing4_ref.py"))
ref = importlib.util.module_from_spec(_spec)  # the benchmark's plain reference
_spec.loader.exec_module(ref)

CFG = get_config("test-tiny-hc")   # 1 dense + 2 sparse layers, 4 copies, 8 experts, 2 a token
PREFILL, DECODE = 64, 8
# Float32 on both sides: the served logits sit 1.4e-7 of the reference's logit
# range from it (rounding in another order of summation), the least of the
# planted faults 1.1e-3. The limit leaves the sound reading 70 times of room
# and is a hundredth of the least fault.
TOL = 1e-5


def reference_sizes(cfg) -> dict:
    """What `harness/manifest.py::reference_sizes` hands the reference,
    built from a ModelConfig instead of a configuration file."""
    factor, original, fast, slow, mscale, mscale_all = cfg.rope_yarn
    return {
        "num_heads": cfg.num_heads, "rms_norm_eps": cfg.rms_norm_eps,
        "num_experts": cfg.num_experts, "num_experts_per_tok": cfg.num_experts_per_tok,
        "tie_embeddings": False,
        "config": {
            "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
            "first_k_dense_replace": cfg.num_dense_layers,
            "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_rank,
            "rope_theta": cfg.rope_theta,
            "rope_scaling": {"factor": factor, "original_max_position_embeddings": original,
                             "beta_fast": fast, "beta_slow": slow, "mscale": mscale,
                             "mscale_all_dim": mscale_all, "type": "yarn"},
            "assumed": {"rope_interleave": cfg.rope_interleave},
            "scoring_func": cfg.router_scoring, "topk_method": cfg.router_topk_method,
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "hc_mult": cfg.residual_copies, "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
            "hc_eps": cfg.hc_eps, "mhc_h_res_clamp_min": cfg.hc_res_clamp_min,
            "mhc_h_res_clamp_max": cfg.hc_res_clamp_max,
        },
    }


def _step():
    """One call of `mla.forward` through the cache under `jax.jit`, the
    configuration a static argument: a new function a call, so traced anew.
    SOUND is the one every case on the sound path shares (a configuration
    compiles once a module); a case that replaces a function of the model
    makes its own, because SOUND would hand it the trace of the sound path."""
    def step(p, c, toks, start, *, cfg):
        return mla.forward(p, cfg, toks, start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None],
                           *c, jnp.reshape(start, (1,)))
    return jax.jit(step, static_argnames="cfg")


SOUND = _step()


def served_logits(params, cfg, tokens, prefill: int, rows: int = 128, step=SOUND):
    """Prefill of `prefill` tokens into a fresh cache, then one token a
    step through it: float32 [T, V]."""
    cache = mla.init_kv_cache(cfg, 1, rows, dtype=params["embed"].dtype)
    out = []
    for lo, hi in [(0, prefill)] + [(t, t + 1) for t in range(prefill, len(tokens))]:
        logits, *cache = step(params, cache, jnp.asarray(tokens[None, lo:hi]), jnp.int32(lo),
                              cfg=cfg)
        out.append(np.asarray(logits[0], np.float32))
    return np.concatenate(out)


@jax.jit
def seeded_params(key):
    return mla.init_params(CFG, key, dtype=jnp.float32)


@pytest.fixture(scope="module")
def seeded():
    params = seeded_params(jax.random.key(0))
    tokens = np.random.default_rng(0).integers(0, CFG.vocab_size, PREFILL + DECODE)
    tokens = tokens.astype(np.int32)
    sizes = reference_sizes(CFG)
    want, margin, sigma, residual = ref.forward_routed(params, sizes, jnp.asarray(tokens))
    return params, tokens, sizes, np.asarray(want), residual, (margin, sigma)


def over_range(got, want):
    return float(np.abs(got - want).max() / (want.max() - want.min()))


def numbers(seeded, cfg=CFG, layers: bool = True, step=SOUND) -> dict:
    """The three numbers a fault is caught by, each a largest |logit
    difference| as a share of the reference's logit range: the whole model's
    prefill positions, its decode positions through the cache, and
    (`layers`) every layer alone on the stream the reference saw enter it
    (the sub-model `benchmark/harness/correct.py` cuts, its table n * D wide)."""
    from harness import correct

    params, tokens, sizes, want, residual, _ = seeded
    got = served_logits(params, cfg, tokens, PREFILL, step=step)
    out = {"prefill": over_range(got[:PREFILL], want[:PREFILL]),
           "decode": over_range(got[PREFILL:], want[PREFILL:])}
    if not layers:
        return out
    out["layers"] = 0.0
    positions = np.arange(len(tokens), dtype=np.int32)
    order = mla.layer_order(cfg)
    for layer in range(cfg.num_layers):
        first, count, cut = correct.cut_layers(order, layer, 1)
        sub = correct._sub_model(params, residual[layer], first, count, jnp.float32)
        alone = np.asarray(ref.forward(sub, {**sizes, "layer_order": cut}, jnp.asarray(positions)))
        one = served_logits(sub, mla.with_layer_order(cfg, cut), positions, PREFILL, step=step)
        out["layers"] = max(out["layers"], over_range(one, alone))
    return out


def test_the_config_is_served_by_mla_as_two_stacks():
    assert model_module(CFG) is mla and CFG.residual_copies == 4
    assert CFG.router_bias and get_config("test-tiny-mla").router_bias is False
    assert mla.layer_order(CFG) == ((0, 0), (1, 0), (1, 1))
    tree = jax.eval_shape(lambda: mla.init_params(CFG, jax.random.key(0)))
    dense, sparse = tree["layers"]
    assert dense["ln1"].shape == (1, 64) and sparse["ln1"].shape == (2, 64)
    assert dense["mlp"]["wg"].shape == (1, 64, CFG.ffn_hidden_size) and "router" not in dense["mlp"]
    assert sparse["mlp"]["wg"].shape == (2, 8, 64, CFG.moe_ffn_hidden_size)
    assert sparse["mlp"]["bias"].shape == (2, 8) and sparse["mlp"]["bias"].dtype == jnp.float32
    for stack in (dense, sparse):   # both sublayers of every layer have maps of their own
        assert stack["hc"]["attn"]["phi"].shape[1:] == stack["hc"]["mlp"]["phi"].shape[1:] == (256, 24)
        assert stack["hc"]["attn"]["bias"].dtype == stack["hc"]["mlp"]["alpha"].dtype == jnp.float32
    assert tree["embed"].shape == (256, 64)            # the table keeps the model's width
    (cache,) = mla.init_kv_cache(CFG, 2, 64)
    assert cache.shape == (3, 2, 64, 128)              # one row a layer of BOTH stacks
    specs = mla.param_specs(CFG)
    assert jax.tree.structure(specs) == jax.tree.structure(tree)


def test_a_cut_model_may_leave_a_stack_with_no_layer():
    """`with_layer_order`: the harness's one- and two-layer models. The
    leaves of an emptied stack have a leading axis of 0 and its scan is
    skipped, in `forward` and `init_kv_cache` alike."""
    one_sparse = mla.with_layer_order(CFG, ((1, 0),))
    assert (one_sparse.num_layers, one_sparse.num_dense_layers) == (1, 0)
    one_dense = mla.with_layer_order(CFG, ((0, 0),))
    assert (one_dense.num_layers, one_dense.num_dense_layers) == (1, 1)
    pair = mla.with_layer_order(CFG, ((0, 0), (1, 0)))
    assert mla.layer_order(pair) == ((0, 0), (1, 0))
    with pytest.raises(ValueError, match="dense layers first"):
        mla.with_layer_order(CFG, ((1, 0), (0, 0)))
    whole = seeded_params(jax.random.key(1))
    for cut, kept in ((one_sparse, (0, 1)), (one_dense, (1, 0))):
        stacks = [jax.tree.map(lambda a, n=n: a[:n], stack)
                  for stack, n in zip(whole["layers"], kept)]
        params = {**whole, "layers": stacks}
        assert stacks[kept.index(0)]["ln1"].shape == (0, 64)
        (cache,) = mla.init_kv_cache(cut, 1, 32, dtype=jnp.float32)
        tokens = jnp.arange(8, dtype=jnp.int32)[None]
        logits, cache, counts = jax.jit(lambda p, c: mla.forward(
            p, cut, tokens, tokens, c, jnp.zeros((1,), jnp.int32), counters=True))(params, cache)
        assert logits.shape == (1, 8, 256) and cache.shape[0] == 1
        assert (int(counts[0]) > 0) == (cut is one_sparse)   # only a sparse layer counts


def test_prefill_then_decode_equals_the_reference_float32(seeded):
    """Prefill, then 8 decode steps through the cache (absorbed attention,
    the sparse stack's rows behind the dense stack's), equal the reference's
    whole forward; so does each layer alone on a stream of 4 x 64, and the
    fresh-sequence prefill program."""
    params, tokens, sizes, want, residual, (margin, sigma) = seeded
    sound = numbers(seeded)
    assert max(sound.values()) < TOL, sound
    fresh, chunk = jax.jit(lambda p, t: mla.forward_prefill(
        p, CFG, t, jnp.arange(PREFILL, dtype=jnp.int32)[None]))(
            params, jnp.asarray(tokens[None, :PREFILL]))
    assert over_range(np.asarray(fresh[0]), want[:PREFILL]) < TOL
    assert chunk.shape == (CFG.num_layers, 1, PREFILL, 128)
    assert residual.shape == (4, len(tokens), 4 * CFG.hidden_size)
    assert np.isinf(np.asarray(margin[0])).all() and float(sigma[0]) == 1.0  # dense: decided
    assert np.isfinite(np.asarray(margin[1:])).all() and (np.asarray(margin[1:]) >= 0).all()
    copies = np.asarray(residual[1]).reshape(len(tokens), 4, -1)
    assert np.abs(copies[:, 0] - copies[:, 1]).max() > 1e-3   # the copies part ways at once


@contextlib.contextmanager
def replaced(obj, name, make):
    real = getattr(obj, name)
    setattr(obj, name, make(real))
    try:
        yield
    finally:
        setattr(obj, name, real)


def _weights_from_the_biased_scores(real):
    def top_k_weights(logits, k, scoring="softmax", bias=None):
        top_w, top_i = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, k)
        return top_w / top_w.sum(axis=-1, keepdims=True), top_i
    return top_k_weights


def _folded_to_one_copy(real):
    def expand(x, n, width):
        if x.shape[-1] == n * width:    # the stream as a table: the mean of its copies, copied
            x = hc.fold(x, n) / n
        return real(x, n, width)
    return expand


# fault: (where it is planted, what takes its place, the number it is caught by)
FAULTS = {
    "sinkhorn-left-out": ((hc, "sinkhorn", lambda real: lambda m, iters, eps: m), "prefill"),
    "h-post-without-its-factor-2": (
        (hc, "maps", lambda real: lambda *a, **k: (lambda r: (r[0], r[1] / 2, r[2]))(real(*a, **k))),
        "prefill"),
    "the-selection-bias-ignored": (
        (moe, "top_k_weights", lambda real: lambda lg, k, sc="softmax", bias=None: real(lg, k, sc)),
        "prefill"),
    "softmax-in-place-of-sigmoid": (
        dataclasses.replace(CFG, router_scoring="softmax"), "prefill"),
    "weights-taken-from-the-biased-scores": (
        (moe, "top_k_weights", _weights_from_the_biased_scores), "prefill"),
    "the-sparse-stacks-cache-index-not-offset": (
        (mla, "_scans", lambda real: lambda p, c: [(s, l, e, 0) for s, l, e, _ in real(p, c)]),
        "decode"),
    "the-stream-folded-to-one-copy": ((hc, "expand", _folded_to_one_copy), "layers"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_planted_fault_fails_by_a_named_number(fault, seeded):
    """Planted in the served side alone. Each is at least 100 times over the
    limit in the number named (readings: 0.20, 0.10, 0.032, 0.025, 0.0017,
    0.029, 0.0018 of the range). The cache index shows only in decode (a
    prefill reads back the rows it has just written, wherever it wrote
    them), the folded stream only where the stream is handed in as the
    table, which is how `harness/correct.py` judges a layer."""
    plant, caught_by = FAULTS[fault]
    layers = caught_by == "layers"
    if isinstance(plant, tuple):
        with replaced(*plant):
            got = numbers(seeded, layers=layers, step=_step())
    else:
        got = numbers(seeded, plant, layers=layers)
    assert got[caught_by] > 100 * TOL, (fault, got)
    if fault == "the-sparse-stacks-cache-index-not-offset":
        assert got["prefill"] < TOL
    if fault == "the-stream-folded-to-one-copy":
        assert got["prefill"] < TOL and got["decode"] < TOL


def test_the_counters_count_the_sparse_layers_only(seeded):
    params = seeded[0]
    (cache,) = mla.init_kv_cache(CFG, 3, 64, dtype=jnp.float32)
    tokens = jnp.asarray([[5], [9], [200]], jnp.int32)
    pos = jnp.zeros((3, 1), jnp.int32)
    _, _, counts = jax.jit(lambda p, c: mla.forward(
        p, CFG, tokens, pos, c, pos[:, 0], counters=True))(params, cache)
    held, hit = (int(c) for c in counts)
    sparse = CFG.num_layers - CFG.num_dense_layers
    assert held == sparse * 3 * CFG.num_experts_per_tok     # every expert is held here
    assert 0 < hit <= min(held, sparse * CFG.num_experts)


def test_the_new_ops_carry_their_scopes_and_the_old_model_none_of_them():
    def text(cfg, T):
        params = jax.eval_shape(lambda: mla.init_params(cfg, jax.random.key(0)))
        cache = jax.eval_shape(lambda: mla.init_kv_cache(cfg, 2, 64))
        toks = jax.ShapeDtypeStruct((2, T), jnp.int32)
        return jax.jit(lambda p, t, c: mla.forward(
            p, cfg, t, t, *c, jnp.zeros((2,), jnp.int32))).lower(params, toks, cache).as_text(
                debug_info=True)

    new, old = text(CFG, 1), text(get_config("test-tiny-mla"), 1)
    for scope in ("stack.dense", "stack.sparse", "hc.mix", "hc.maps", "hc.sinkhorn"):
        assert scope in new and scope not in old, scope
    assert "/layers/" in old and "/layers/" not in new


def test_an_engine_built_from_a_two_stack_tree_starts_and_follows_the_reference(seeded):
    """`quant.params_quantized` read `params["layers"]` as one dict and the
    engine refused the tree before it built a program (PR 34's CPU run).
    Greedy tokens through `InferenceEngine` (prefill_insert, then the decode
    chunks) are the reference's argmax at every step."""
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig, SamplingParams

    params, tokens, sizes = seeded[0], seeded[1], seeded[2]
    assert quant.params_quantized(params) is False and quant.detect_mode(params) is None
    ecfg = EngineConfig(num_slots=2, max_seq=128, prefill_buckets=(32,), max_sessions=0,
                        dtype="float32", decode_chunk=4)
    engine = InferenceEngine(CFG, ecfg, params=params)
    assert engine._cache[0].shape == (CFG.num_layers, 2, 128, 128)
    assert engine.kv_bytes_per_token() == CFG.num_layers * 128 * 4
    engine.start()
    try:
        prompt = [int(t) for t in tokens[:24]]
        handle = engine.submit(prompt, SamplingParams(temperature=0, max_tokens=10,
                                                      stop_token_ids=()))
        out = [ev.token_id for ev in handle.events() if getattr(ev, "token_id", None) is not None]
    finally:
        engine.stop()
    assert len(out) == 10
    logits = np.asarray(ref.forward(params, sizes, jnp.asarray(prompt + out, jnp.int32)))
    assert out == [int(t) for t in logits[len(prompt) - 1:-1].argmax(-1)]
    assert engine.metrics["moe_assignments_held"] > 0
