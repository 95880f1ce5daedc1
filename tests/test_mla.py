"""The latent-attention family (models/mla.py) against the benchmark's
plain reference (benchmark/reference/mla_moe_ref.py), at tiny widths on
the CPU with seeded weights: the first test under tests/ that takes its
oracle from the benchmark, so the program and the yardstick are held to
one statement of the model."""
import dataclasses
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.models import get_config, mla, model_module
from omnia_tpu.ops import rope
from omnia_tpu.ops.decode_mla_attention import decode_mla_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "mla_moe_ref", os.path.join(BENCH, "reference", "mla_moe_ref.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()
CFG = get_config("test-tiny-mla")


def reference_sizes(cfg) -> dict:
    """What `harness/manifest.py::reference_sizes` hands the reference,
    built from a ModelConfig instead of a configuration file."""
    factor, original, fast, slow, mscale, mscale_all = cfg.rope_yarn
    return {
        "num_heads": cfg.num_heads, "rms_norm_eps": cfg.rms_norm_eps,
        "num_experts": cfg.num_experts, "num_experts_per_tok": cfg.num_experts_per_tok,
        "tie_embeddings": False,
        "config": {
            "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_rank,
            "rope_interleave": cfg.rope_interleave, "expert_rank": cfg.expert_rank,
            "norm_topk_prob": True, "routed_scaling_factor": cfg.routed_scaling_factor,
            "rope_parameters": {
                "rope_theta": cfg.rope_theta, "factor": factor,
                "original_max_position_embeddings": original, "beta_fast": fast,
                "beta_slow": slow, "mscale": mscale, "mscale_all_dim": mscale_all,
                "llama_4_scaling_beta": cfg.q_scaling_beta},
        },
    }


def served_logits(params, cfg, tokens, prefill: int, rows: int = 256):
    """Prefill of `prefill` tokens into a fresh cache, then one token a
    step through it: float32 [T, V]."""
    dtype = params["embed"].dtype
    cache = mla.init_kv_cache(cfg, 1, rows, dtype=dtype)
    step = jax.jit(lambda p, c, toks, start: mla.forward(
        p, cfg, toks, start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None], *c,
        jnp.reshape(start, (1,))))
    out = []
    for lo, hi in [(0, prefill)] + [(t, t + 1) for t in range(prefill, len(tokens))]:
        logits, *cache = step(params, cache, jnp.asarray(tokens[None, lo:hi]), jnp.int32(lo))
        out.append(np.asarray(logits[0], np.float32))
    return np.concatenate(out)


def seeded(cfg, dtype, seed=0, n=128):
    params = mla.init_params(cfg, jax.random.key(seed), dtype=dtype)
    tokens = np.random.default_rng(seed).integers(0, cfg.vocab_size, n).astype(np.int32)
    return params, tokens


def test_the_latent_config_is_served_by_mla():
    assert model_module(CFG) is mla
    assert model_module(get_config("test-tiny")).__name__ == "omnia_tpu.models.llama"
    (cache,) = mla.init_kv_cache(CFG, 3, 64, dtype=jnp.float32)
    assert cache.shape == (CFG.num_layers, 3, 64, 128) and mla.row_width(CFG) == 128
    assert mla.row_width(dataclasses.replace(CFG, kv_rank=256, qk_rope_head_dim=64)) == 384


def test_prefill_then_decode_equals_the_reference_float32():
    """Prefill then 8 decode steps through the cache (absorbed attention,
    rows read back from the one array) equal the reference's whole forward
    (expanded attention, no cache). Positions past the original context
    (64 here) exercise the query scale and YaRN's slowed pairs."""
    params, tokens = seeded(CFG, jnp.float32)
    want = np.asarray(ref.forward(params, reference_sizes(CFG), jnp.asarray(tokens)))
    got = served_logits(params, CFG, tokens, prefill=len(tokens) - 8)
    assert np.abs(got - want).max() < 1e-4
    fresh, chunk = mla.forward_prefill(
        params, CFG, jnp.asarray(tokens[None, :64]), jnp.arange(64, dtype=jnp.int32)[None])
    assert np.abs(np.asarray(fresh[0]) - want[:64]).max() < 1e-4
    assert chunk.shape == (CFG.num_layers, 1, 64, 128)


def test_bfloat16_is_within_the_dense_tolerance_where_routing_is_decided():
    """bfloat16 weights and cache, teacher-forced a layer at a time as
    `harness/correct.py` judges a model with a router: each layer alone on
    the stream the reference saw enter it, held to correct.py's dense
    tolerance on the (layer, position) pairs whose routing is decided."""
    from harness import correct

    params, tokens = seeded(CFG, jnp.bfloat16, seed=1, n=72)
    sizes = reference_sizes(CFG)
    _, margin, sigma, residual = ref.forward_routed(params, sizes, jnp.asarray(tokens))
    decided = correct.decided_pairs(margin, sigma)
    assert decided.sum() >= correct.MIN_DECIDED
    one = dataclasses.replace(CFG, num_layers=1)
    positions = np.arange(len(tokens), dtype=np.int32)
    for layer in range(CFG.num_layers):
        sub = correct._sub_model(params, residual[layer], layer, 1, jnp.bfloat16)
        want = np.asarray(ref.forward(sub, sizes, jnp.asarray(positions)))
        got = served_logits(sub, one, positions, prefill=len(tokens) - 8)
        diff = np.abs(got - want)[decided[layer]] / (want.max() - want.min())
        assert diff.max() <= correct.MAX_TOL and diff.mean() <= correct.MEAN_TOL


def _rows_and_queries(seed, B, S, H, R, dr, W, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    cache = jax.random.normal(ks[0], (2, B, S, W), dtype=jnp.float32)
    cache = cache.at[..., R + dr:].set(0).astype(dtype)   # the pad lanes are zeros
    q = jax.random.normal(ks[1], (B, H, W), dtype=jnp.float32).astype(dtype)
    return cache, q


def _einsum_decode(q, rows, positions, R, scale):
    scores = jnp.einsum("bhw,bsw->bhs", q, rows, preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(rows.shape[1])[None, :] <= positions[:, None]
    probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
    return jnp.einsum("bhs,bsr->bhr", probs, rows[..., :R].astype(jnp.float32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("positions,live", [
    ((5, 300, 511), (1, 1, 1)),      # one, two and two blocks
    ((5, 300, 511), (1, 0, 1)),      # a dead slot in the middle
    ((0, 255, 256), (0, 1, 1)),      # a dead first slot; a block's last row, the next's first
])
def test_the_kernel_under_the_interpreter_equals_the_einsum_path(dtype, tol, positions, live):
    B, S, H, R, dr, W = 3, 512, 4, 32, 8, 128
    cache, q = _rows_and_queries(3, B, S, H, R, dr, W, dtype)
    pos = jnp.asarray(positions, jnp.int32)
    got = decode_mla_attention(q, cache, pos, jnp.int32(1), jnp.asarray(live, jnp.int32),
                               rank=R, scale=0.3, block_s=256, interpret=True)
    want = _einsum_decode(q, cache[1], pos, R, 0.3)
    for b in range(B):
        if live[b]:
            np.testing.assert_allclose(np.asarray(got[b], np.float32), np.asarray(want[b]),
                                       atol=tol, rtol=tol)
        else:
            assert not np.asarray(got[b], np.float32).any()  # a dead slot's row is zeros


def test_absorbed_attention_equals_expanded_attention():
    """One query a slot over a filled cache: Wkvb absorbed into the query
    and the output (decode) gives what expanding the rows gives (prefill)."""
    cfg = CFG
    B, S, H = 2, 64, cfg.num_heads
    R, dn, dr, dv = cfg.kv_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(jax.random.key(7), 4)
    cache = jax.random.normal(ks[0], (1, B, S, mla.row_width(cfg))).at[..., R + dr:].set(0)
    q_nope = jax.random.normal(ks[1], (B, 1, H, dn))
    q_rope = jax.random.normal(ks[2], (B, 1, H, dr))
    wkvb = jax.random.normal(ks[3], (R, H * (dn + dv))) * 0.1
    positions = jnp.asarray([[17], [63]], jnp.int32)
    absorbed = mla._absorbed_attention(q_nope, q_rope, cache, wkvb, cfg, positions, 0, None)
    expanded = mla._expanded_attention(q_nope, q_rope, cache[0], wkvb, cfg, positions)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), atol=2e-5, rtol=1e-4)


# -- rotary embeddings -------------------------------------------------------

PUBLISHED_YARN = dict(dim=64, theta=1e4, factor=128.0, original=8192, fast=32.0, slow=1.0)


def _yarn_by_the_formula(dim, theta, factor, original, fast, slow):
    """YaRN's frequencies written out pair by pair in float64."""
    def pair(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))
    low, high = max(math.floor(pair(fast)), 0), min(math.ceil(pair(slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        freq = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(freq / factor * ramp + freq * (1 - ramp))
    return np.asarray(out), low, high


@pytest.mark.parametrize("yarn", [PUBLISHED_YARN,
                                  dict(dim=8, theta=1e4, factor=16.0, original=64, fast=32.0, slow=1.0)])
def test_yarn_frequencies_follow_the_formula(yarn):
    want, low, high = _yarn_by_the_formula(**yarn)
    got = np.asarray(rope.yarn_inv_freq(yarn["dim"], yarn["theta"], yarn["factor"],
                                        yarn["original"], yarn["fast"], yarn["slow"]))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # fast pairs keep their frequency, slow ones are divided by the factor
    assert got[0] == pytest.approx(1.0) and low < high
    last = yarn["theta"] ** (-(yarn["dim"] - 2) / yarn["dim"])
    assert got[-1] == pytest.approx(last / yarn["factor"], rel=1e-6)


def test_yarn_scales():
    yarn = (128.0, 8192, 32.0, 1.0, 1.0, 1.0)
    m = 0.1 * math.log(128.0) + 1.0
    assert rope.yarn_softmax_scale(128, yarn) == pytest.approx(128 ** -0.5 * m * m)
    assert rope.yarn_softmax_scale(128, None) == pytest.approx(128 ** -0.5)
    cos, sin = rope.yarn_cos_sin(jnp.arange(4), 64, 1e4, yarn)
    assert float(cos[0, 0]) == 1.0 and float(sin[0, 0]) == 0.0  # mscale's ratio is 1
    half = (128.0, 8192, 32.0, 1.0, 1.0, 0.5)  # unequal: cos and sin carry the ratio
    cos2, _ = rope.yarn_cos_sin(jnp.arange(4), 64, 1e4, half)
    assert float(cos2[0, 0]) == pytest.approx(m / (0.05 * math.log(128.0) + 1.0))


def test_interleaved_pairs_rotate_as_complex_numbers():
    x = np.random.default_rng(0).standard_normal((2, 5, 3, 8)).astype(np.float32)
    positions = jnp.arange(10).reshape(2, 5)
    cos, sin = rope.rope_cos_sin(positions, 8, 1e4)
    got = np.asarray(rope.apply_rope_interleaved(jnp.asarray(x), cos, sin))
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.asarray(cos + 1j * sin)[:, :, None, :]
    want = np.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=1e-6)


def _parent_rope_cos_sin(positions, head_dim, theta, scaling=None):
    """ops/rope.py::rope_cos_sin and its Llama-3 remap as the parent
    commit (72dfb23) had them, line for line."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is not None:
        factor, low_freq_factor, high_freq_factor, original_max_position = scaling
        wavelen = 2.0 * jnp.pi / inv_freq
        low_wavelen = original_max_position / low_freq_factor
        high_wavelen = original_max_position / high_freq_factor
        smooth = (original_max_position / wavelen - low_freq_factor) / (
            high_freq_factor - low_freq_factor
        )
        smooth = jnp.clip(smooth, 0.0, 1.0)
        blended = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
        inv_freq = jnp.where(
            wavelen > low_wavelen,
            inv_freq / factor,
            jnp.where(wavelen < high_wavelen, inv_freq, blended),
        )
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def _parent_apply_rope(x, cos, sin):
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("scaling", [None, (8.0, 1.0, 4.0, 8192)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_llama_rope_is_bit_equal_to_the_parents(scaling, dtype):
    positions = jnp.asarray([[0, 1, 77, 4095, 100000]], jnp.int32)
    x = jax.random.normal(jax.random.key(0), (1, 5, 4, 128)).astype(dtype)
    cos, sin = rope.rope_cos_sin(positions, 128, 500000.0, scaling)
    want_cos, want_sin = _parent_rope_cos_sin(positions, 128, 500000.0, scaling)
    assert np.array_equal(np.asarray(cos), np.asarray(want_cos))
    assert np.array_equal(np.asarray(sin), np.asarray(want_sin))
    got = rope.apply_rope(x, cos, sin)
    want = _parent_apply_rope(x, want_cos, want_sin)
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
