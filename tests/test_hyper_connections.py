"""Manifold-constrained hyper-connections (ops/hyper_connections.py): the
mixing matrix is doubly stochastic, one copy with unit maps is the plain
residual, and the op equals the few lines of the benchmark's plain
reference (benchmark/reference/xing4_ref.py)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.ops import hyper_connections as hc

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmark", "reference", "xing4_ref.py")
_spec = importlib.util.spec_from_file_location("xing4_ref", _REF)
ref = importlib.util.module_from_spec(_spec)  # the benchmark's plain reference
_spec.loader.exec_module(ref)

CONSTANTS = {"iters": 20, "eps": 1e-6, "clamp": (-30.0, 30.0), "norm_eps": 1e-6}
FILE = {"hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30}


def maps_of(n, d, seed=0, dtype=jnp.float32):
    """One sublayer's maps, seeded as models/mla.py::init_params seeds them."""
    ks = jax.random.split(jax.random.key(seed), 3)
    b_res = 1.5 * jnp.eye(n).reshape(n * n) + 0.3 * jax.random.normal(ks[2], (n * n,))
    return {"phi": (jax.random.normal(ks[0], (n * d, 2 * n + n * n)) * (n * d) ** -0.5).astype(dtype),
            "bias": jnp.concatenate([0.5 * jax.random.normal(ks[1], (2 * n,)), b_res]),
            "alpha": jnp.asarray([0.5, 0.5, 0.3])}


@pytest.mark.parametrize("spread", [1.0, 12.0], ids=["mild", "clamped"])
@pytest.mark.parametrize("n", [2, 4, 8])
def test_sinkhorn_leaves_rows_that_sum_to_one_and_columns_nearly(n, spread):
    """Of any positive matrix the rows sum to 1 (they are divided last); the
    columns are as near as 20 iterations bring them: 2e-2 for logits of unit
    spread, and not judged for ones that reach the clamp."""
    a = spread * jax.random.normal(jax.random.key(n), (n, n, 5, 7))
    h = np.asarray(hc.sinkhorn(jnp.exp(jnp.clip(a, -30.0, 30.0)), 20, 1e-6))
    assert (h >= 0).all() and np.isfinite(h).all()
    np.testing.assert_allclose(h.sum(axis=1), 1.0, atol=1e-4)
    if spread == 1.0:
        np.testing.assert_allclose(h.sum(axis=0), 1.0, atol=2e-2)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_seeded_mixing_matrix_is_doubly_stochastic_and_no_constant(seed):
    """As models/mla.py::init_params seeds the maps, at the 4 copies the
    benchmark's model has: after 20 iterations every row and column of H_res
    sums to 1 within 1e-4, and it is neither the identity nor uniform."""
    n, d = 4, 16
    x = jax.random.normal(jax.random.key(seed), (64, 32, n * d))
    _, _, h_res = hc.maps(x, maps_of(n, d, seed), n, **CONSTANTS)
    np.testing.assert_allclose(np.asarray(h_res.sum(axis=0)), 1.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_res.sum(axis=1)), 1.0, atol=1e-4)
    eye = np.eye(n)[:, :, None, None]
    assert np.abs(np.asarray(h_res) - eye).mean() > 0.05         # not the identity
    assert np.abs(np.asarray(h_res) - 1.0 / n).mean() > 0.05     # nor uniform
    assert np.asarray(h_res).std(axis=(2, 3)).min() > 1e-2       # and the token decides it


def test_one_copy_with_unit_maps_is_the_plain_residual():
    """n = 1: H_res is 1 whatever its logit; with h_pre = h_post = 1 (a
    saturated b_pre, b_post = 0, no token term) the sublayer sees x and the
    stream becomes x + f(x)."""
    d = 32
    x = jax.random.normal(jax.random.key(0), (2, 5, d))
    y = jax.random.normal(jax.random.key(1), (2, 5, d))
    unit = {"phi": jnp.zeros((d, 3)), "bias": jnp.asarray([30.0, 0.0, 0.7]),
            "alpha": jnp.zeros((3,))}
    u, mixes = hc.pre(x, unit, 1, **CONSTANTS)
    np.testing.assert_allclose(np.asarray(u), np.asarray(x), atol=1e-6)
    np.testing.assert_allclose(np.asarray(hc.post(x, y, mixes)), np.asarray(x + y), atol=1e-5)
    assert hc.expand(x, 1, d) is x
    np.testing.assert_allclose(np.asarray(hc.fold(x, 1)), np.asarray(x))


@pytest.mark.parametrize("n", [2, 4])
def test_the_op_equals_the_references_lines(n):
    d, T = 48, 9
    p = maps_of(n, d, seed=3)
    x = jax.random.normal(jax.random.key(4), (T, n * d))
    y = jax.random.normal(jax.random.key(5), (T, d))
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = ref._maps(x.reshape(T, n, d), p, FILE, 1e-6)
        want, want_u = ref._sublayer(x.reshape(T, n, d), p, FILE, 1e-6, lambda u: (y, u))
        got_pre, got_post, got_res = hc.maps(x, p, n, **CONSTANTS)
        u, mixes = hc.pre(x, p, n, **CONSTANTS)
        got = hc.post(x, y, mixes)
    np.testing.assert_allclose(np.asarray(got_pre).T, np.asarray(h_pre), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_post).T, np.asarray(h_post), atol=1e-6)
    np.testing.assert_allclose(np.moveaxis(np.asarray(got_res), -1, 0), np.asarray(h_res),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(u), np.asarray(want_u), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want).reshape(T, n * d), atol=1e-5)
    # the maps differ by copy and by token: nothing here is a constant in disguise
    assert np.asarray(h_pre).std(axis=0).min() > 1e-2 and np.asarray(h_pre).std(axis=1).mean() > 1e-2
    assert np.asarray(h_post).std(axis=0).min() > 1e-2


def test_the_stream_in_bfloat16_keeps_its_maps_in_float32():
    n, d = 4, 64
    p = maps_of(n, d, seed=6, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(7), (3, 6, n * d)).astype(jnp.bfloat16)
    u, (h_post, h_res) = hc.pre(x, p, n, **CONSTANTS)
    out = hc.post(x, u, (h_post, h_res))
    assert u.dtype == out.dtype == jnp.bfloat16 and out.shape == x.shape
    assert h_post.dtype == h_res.dtype == jnp.float32 and h_res.shape == (n, n, 3, 6)
    exact = hc.pre(x.astype(jnp.float32), {**p, "phi": p["phi"].astype(jnp.float32)}, n,
                   **CONSTANTS)[1][1]
    np.testing.assert_allclose(np.asarray(h_res), np.asarray(exact), atol=1e-5)


def test_a_table_of_the_streams_width_is_the_stream_itself():
    x = jnp.arange(24.0).reshape(1, 2, 12)
    assert hc.expand(x, 4, 3) is x                     # 4 x 3 wide: the copies themselves
    wide = hc.expand(x, 2, 12)                         # the model's width: copied
    assert wide.shape == (1, 2, 24)
    np.testing.assert_array_equal(np.asarray(wide[..., :12]), np.asarray(wide[..., 12:]))
    np.testing.assert_array_equal(np.asarray(hc.fold(wide, 2)), 2 * np.asarray(x))
