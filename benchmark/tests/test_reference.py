"""The plain reference against `models/llama.py::forward` on the CPU at tiny
widths: prefill, then decode through the cache.

Tolerance: both sides run in float32 here (parameters made in float32), so
they differ only in the order of summation: 1e-4 of the logit range, about
a thousand float32 ulps over a few thousand-term dot products. A wrong
mask, RoPE convention, GQA grouping or expert weighting is off by tenths
of the range. (On the chip the served side is bfloat16 and the bound is
the one written in harness/correct.py.) At `test-tiny-moe` the prefill of
72 tokens takes `moe_dispatch`, whose capacity is ceil(N x 2 x 2 / 4) = N,
so no assignment can be dropped there; the reference drops nothing ever.

That is why this comparison never saw `moe_dispatch` drop: with 4 experts
top-2 at capacity factor 2 every expert has room for every token. With 8
experts capacity is N / 2, and seeded routers are uneven enough to pass it.
`test_sparse_check_bites` below is the test that shows a drop: a bf16 model
of 8 experts top-2, 8 layers, hidden 256, through `harness/correct.py`'s
own sparse check (each layer alone on the reference's input to it, then the
first two layers together), where the dropless evaluation passes and the
served `moe_dispatch`, un-renormalised weights, top-1, weights through int8,
the first two layers in the wrong order and too few decided pairs each fail.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import correct
from harness.manifest import Cell, load_reference, reference_sizes
from omnia_tpu.models import llama
from omnia_tpu.models.config import ModelConfig, get_config
from omnia_tpu.ops import moe

llama_ref = load_reference()

PREFILL, DECODE = 72, 6


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-gqa8", "test-tiny-moe"])
def test_reference_agrees_with_served_forward(preset):
    cfg = get_config(preset)
    params = llama.init_params(cfg, jax.random.key(5), dtype=jnp.float32)
    tokens = np.random.default_rng(11).integers(0, cfg.vocab_size, PREFILL + DECODE)
    tokens = tokens.astype(np.int32)
    ck, cv = llama.init_kv_cache(cfg, 1, 128, dtype=jnp.float32)

    def step(ck, cv, toks, start):
        pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
        return llama.forward(params, cfg, toks, pos, ck, cv, jnp.array([start]))

    logits, ck, cv = step(ck, cv, jnp.asarray(tokens[None, :PREFILL]), 0)
    served = [np.asarray(logits[0])]
    for i in range(PREFILL, PREFILL + DECODE):
        logits, ck, cv = step(ck, cv, jnp.asarray(tokens[None, i:i + 1]), i)
        served.append(np.asarray(logits[0]))
    served = np.concatenate(served)
    ref = np.asarray(llama_ref.forward(params, reference_sizes(cfg), jnp.asarray(tokens)))
    span = ref.max() - ref.min()
    assert np.abs(served - ref).max() / span < 1e-4


def test_reference_is_not_fooled_by_a_wrong_cache_row():
    """The comparison has teeth: shift one decode position and it fails."""
    cfg = get_config("test-tiny")
    params = llama.init_params(cfg, jax.random.key(5), dtype=jnp.float32)
    tokens = jnp.asarray(np.arange(40, dtype=np.int32) % cfg.vocab_size)
    ref = np.asarray(llama_ref.forward(params, reference_sizes(cfg), tokens))
    ck, cv = llama.init_kv_cache(cfg, 1, 128, dtype=jnp.float32)
    pos = jnp.arange(40, dtype=jnp.int32)[None, :] + 1  # off by one position
    logits, _, _ = llama.forward(params, cfg, tokens[None], pos, ck, cv, jnp.array([1]))
    span = ref.max() - ref.min()
    assert np.abs(np.asarray(logits[0]) - ref).max() / span > 1e-3


def test_a_configuration_names_its_reference():
    assert Cell("mistral-7b.chat-steady").reference == "llama_ref"  # the default: no key
    with pytest.raises(FileNotFoundError, match=r"reference.nowhere_ref\.py does not exist"):
        load_reference("nowhere_ref")


def _parent_check(engine, model_cfg, sizes, seed):
    """`harness/correct.py::check` as it stood before PR 27 (commit 72c37e8),
    verbatim but for the reference's import: what a dense configuration's
    dict has to equal, key for key and value for value."""
    PREFILL, DECODE, CACHE_ROWS = 128, 8, 256
    mesh = engine._mesh
    dtype = engine.params["embed"].dtype
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC0FFEE])
    tokens = rng.integers(0, model_cfg.vocab_size, size=PREFILL + DECODE).astype(np.int32)
    ck, cv = llama.init_kv_cache(model_cfg, 1, CACHE_ROWS, dtype=dtype)

    @jax.jit
    def step(params, ck, cv, toks, start):
        pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
        return llama.forward(params, model_cfg, toks, pos, ck, cv,
                             jnp.reshape(start, (1,)), mesh=mesh)

    logits, ck, cv = step(engine.params, ck, cv, jnp.asarray(tokens[None, :PREFILL]),
                          jnp.int32(0))
    served = [np.asarray(logits[0], np.float32)]
    for i in range(PREFILL, PREFILL + DECODE):
        logits, ck, cv = step(engine.params, ck, cv,
                              jnp.asarray(tokens[None, i:i + 1]), jnp.int32(i))
        served.append(np.asarray(logits[0], np.float32))
    served = np.concatenate(served, axis=0)
    ref = jax.jit(lambda params, toks: llama_ref.forward(params, sizes, toks))(
        engine.params, jnp.asarray(tokens))
    ref = np.asarray(ref, np.float32)
    rng_ = float(ref.max() - ref.min())
    diff = np.abs(served - ref)
    out = {"logit_range": rng_}
    for name, sl in (("prefill", slice(0, PREFILL)), ("decode", slice(PREFILL, None))):
        out[f"{name}_max_over_range"] = float(diff[sl].max() / rng_)
        out[f"{name}_mean_over_range"] = float(diff[sl].mean() / rng_)
    out["ok"] = bool(
        np.isfinite(served).all()
        and max(out["prefill_max_over_range"], out["decode_max_over_range"]) <= 5e-2
        and max(out["prefill_mean_over_range"], out["decode_mean_over_range"]) <= 1e-2)
    return out


def test_a_dense_preset_returns_the_parents_dict():
    cfg = get_config("test-tiny-gqa8")
    seed = 4294967311  # more than 32 bits, as the driver's are
    params = llama.init_params(cfg, jax.random.key(seed & 0x7FFFFFFF), dtype=jnp.bfloat16)
    engine = types.SimpleNamespace(params=params, _mesh=None)
    sizes = reference_sizes(cfg)
    mine = correct.check(engine, cfg, sizes, seed)
    assert mine == _parent_check(engine, cfg, sizes, seed)
    assert list(mine) == ["logit_range", "prefill_max_over_range", "prefill_mean_over_range",
                          "decode_max_over_range", "decode_mean_over_range", "ok"]
    # What the parent's own tree (its `correct.py` and its `llama_ref.py`)
    # printed for this preset and seed on the CPU, PR 27.
    assert mine == pytest.approx({
        "logit_range": 1.2456064224243164, "prefill_max_over_range": 0.002374411793425679,
        "prefill_mean_over_range": 0.00041323763434775174,
        "decode_max_over_range": 0.0018862982979044318,
        "decode_mean_over_range": 0.000396439601900056, "ok": True}, rel=1e-5)


def _sparse(k: int = 2) -> ModelConfig:
    return ModelConfig(
        name="sparse-8x", vocab_size=512, hidden_size=256, num_layers=8, num_heads=4,
        num_kv_heads=2, head_dim=64, ffn_hidden_size=512, rope_theta=1e6,
        rms_norm_eps=1e-5, tie_embeddings=False, num_experts=8, num_experts_per_tok=k,
        max_seq_len=4096)


@pytest.fixture(scope="module")
def sparse_engine():
    init = jax.jit(lambda key: llama.init_params(_sparse(), key, dtype=jnp.bfloat16))
    return types.SimpleNamespace(params=init(jax.random.key(3)), _mesh=None)


def _route_without_renormalising(h, router_w, k):
    logits = jnp.dot(h, router_w).astype(jnp.float32)
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)


def _through_int8(params):
    """Every matrix of the layers through int8, one scale an output column:
    the contract's control for a bf16 configuration."""
    def q(a):
        if a.ndim < 3:
            return a
        f = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=-2, keepdims=True) / 127.0
        return (jnp.round(f / scale).clip(-127, 127) * scale).astype(a.dtype)
    return {**params, "layers": jax.tree_util.tree_map(q, params["layers"])}


# case: (what is changed on the served side, ok, the number that must be over its limit)
SPARSE_CASES = {
    "dropless": ({"dispatch_min": 1 << 30}, True, None),
    "as-served-capacity-2": ({}, False, "layers_prefill_max_over_range"),
    "weights-not-renormalised": (
        {"dispatch_min": 1 << 30, "route": _route_without_renormalising},
        False, "layers_prefill_max_over_range"),
    "top-1": ({"dispatch_min": 1 << 30, "k": 1}, False, "layers_decode_max_over_range"),
    "weights-through-int8": ({"dispatch_min": 1 << 30, "int8": True}, False,
                             "layers_noise_ratio_max"),
    "first-two-layers-in-the-wrong-order": (
        {"dispatch_min": 1 << 30, "swap_pair": True}, False,
        "pair_prefill_median_worst_over_range"),
    "too-few-decided": ({"dispatch_min": 1 << 30, "min_decided": 10_000}, False, None),
}


@pytest.mark.parametrize("case", list(SPARSE_CASES))
def test_sparse_check_bites(case, sparse_engine, monkeypatch):
    """bf16, 8 experts top-2, 8 layers, hidden 256, seed 3: flips and drops
    both occur. Only the dropless evaluation of the right mathematics in the
    served precision passes."""
    change, ok, over = SPARSE_CASES[case]
    if "dispatch_min" in change:   # every token count takes `moe_dense`: nothing drops
        monkeypatch.setattr(moe, "DISPATCH_MIN_TOKENS", change["dispatch_min"])
    if "route" in change:
        monkeypatch.setattr(moe, "route_sparse", change["route"])
    if "min_decided" in change:
        monkeypatch.setattr(correct, "MIN_DECIDED", change["min_decided"])
    if "int8" in change:
        # The reference reads the weights as made; the served side, and only
        # it, reads them through int8.
        served = types.SimpleNamespace(params=_through_int8(sparse_engine.params), _mesh=None)
        real = correct._served_logits
        monkeypatch.setattr(correct, "_served_logits",
                            lambda engine, *a, **k: real(served, *a, **k))
    if "swap_pair" in change:
        # Each layer alone is as it should be; the two-layer model runs layer
        # 1 before layer 0 on the served side.
        swapped = types.SimpleNamespace(_mesh=None, params={
            **sparse_engine.params, "layers": jax.tree_util.tree_map(
                lambda a: a.at[:2].set(a[1::-1]), sparse_engine.params["layers"])})
        real = correct._served_logits
        monkeypatch.setattr(
            correct, "_served_logits", lambda engine, *a, depth=1, **k: real(
                swapped if depth == correct.PAIR else engine, *a, depth=depth, **k))
    sizes = reference_sizes(_sparse())  # the reference always keeps top-2
    out = correct.check(sparse_engine, _sparse(change.get("k", 2)), sizes, seed=3)
    limits = out["limits"]
    assert out["ok"] is ok, out
    assert out["tau"] == correct.TAU_SIGMA and 0.5 < out["decided_share"] < 0.95
    if ok:
        assert out["decided_positions"] >= correct.MIN_DECIDED
        assert out["layers_prefill_max_over_range"] < correct.MAX_TOL / 3
        assert out["layers_noise_ratio_max"] < 1.3
        assert out["undecided_over_tol_share"] < 0.1
        assert out["pair_prefill_median_worst_over_range"] < correct.PAIR_TOL / 5
        assert out["pair_decode_median_worst_over_range"] < correct.PAIR_TOL / 5
    elif over:
        assert out[over] > limits[over.replace("prefill_", "").replace("decode_", "")], out
    else:
        # Everything it could judge was sound; there was too little of it.
        assert out["decided_positions"] < correct.MIN_DECIDED
        assert out["layers_prefill_max_over_range"] < correct.MAX_TOL
        assert out["layers_noise_ratio_max"] < correct.NOISE_FACTOR
