"""Model-level tests: shapes, prefill/decode equivalence, sharded equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from omnia_tpu.models import get_config
from omnia_tpu.models import llama
from omnia_tpu.parallel import make_mesh, shard_pytree


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("test-tiny")
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    return cfg, params


def test_forward_train_shapes(tiny):
    cfg, params = tiny
    tokens = jnp.zeros((2, 7), dtype=jnp.int32)
    logits = llama.forward_train(params, cfg, tokens)
    assert logits.shape == (2, 7, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_forward_train_causal(tiny):
    """Changing a future token must not change past logits."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(1, 8))
    a = llama.forward_train(params, cfg, jnp.asarray(toks, dtype=jnp.int32))
    toks2 = toks.copy()
    toks2[0, 5] = (toks2[0, 5] + 1) % cfg.vocab_size
    b = llama.forward_train(params, cfg, jnp.asarray(toks2, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(a[0, :5]), np.asarray(b[0, :5]), rtol=2e-4, atol=2e-4)
    assert not np.allclose(np.asarray(a[0, 5]), np.asarray(b[0, 5]))


def test_prefill_matches_forward_train(tiny):
    """Serving prefill (cache path) must produce the same logits as the
    no-cache training forward."""
    cfg, params = tiny
    B, T, S = 2, 6, 16
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, T)), dtype=jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    ck, cv = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    logits_serve, _, _ = llama.forward(
        params, cfg, tokens, pos, ck, cv, jnp.zeros((B,), jnp.int32)
    )
    logits_train = llama.forward_train(params, cfg, tokens)
    np.testing.assert_allclose(
        np.asarray(logits_serve), np.asarray(logits_train), rtol=2e-4, atol=2e-4
    )


def test_decode_matches_prefill(tiny):
    """Incremental decode must reproduce full-prefill logits token by token.
    This is THE serving-correctness invariant."""
    cfg, params = tiny
    B, T, S = 1, 8, 16
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, T)), dtype=jnp.int32)

    # Full prefill at once.
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    ck, cv = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    full_logits, _, _ = llama.forward(
        params, cfg, tokens, pos, ck, cv, jnp.zeros((B,), jnp.int32)
    )

    # Token-by-token decode.
    ck, cv = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    step_logits = []
    for t in range(T):
        tok = tokens[:, t : t + 1]
        p = jnp.full((B, 1), t, dtype=jnp.int32)
        start = jnp.full((B,), t, dtype=jnp.int32)
        lg, ck, cv = llama.forward(params, cfg, tok, p, ck, cv, start)
        step_logits.append(np.asarray(lg[:, 0]))

    np.testing.assert_allclose(
        np.stack(step_logits, axis=1), np.asarray(full_logits), rtol=2e-4, atol=2e-4
    )


def test_chunked_prefill_matches_full(tiny):
    """Multi-turn incremental prefill (write_start > 0) is exact."""
    cfg, params = tiny
    B, T, S = 1, 8, 16
    split = 5
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, T)), dtype=jnp.int32)

    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    ck, cv = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    full_logits, _, _ = llama.forward(
        params, cfg, tokens, pos, ck, cv, jnp.zeros((B,), jnp.int32)
    )

    ck, cv = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    _, ck, cv = llama.forward(
        params, cfg, tokens[:, :split], pos[:, :split], ck, cv, jnp.zeros((B,), jnp.int32)
    )
    second, _, _ = llama.forward(
        params, cfg, tokens[:, split:], pos[:, split:], ck, cv,
        jnp.full((B,), split, dtype=jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(second), np.asarray(full_logits[:, split:]), rtol=2e-4, atol=2e-4
    )


def test_moe_forward(tiny):
    cfg = get_config("test-tiny-moe")
    params = llama.init_params(cfg, jax.random.key(1), dtype=jnp.float32)
    tokens = jnp.zeros((2, 5), dtype=jnp.int32)
    logits = llama.forward_train(params, cfg, tokens)
    assert logits.shape == (2, 5, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_param_count_estimate():
    cfg = get_config("llama3-8b")
    n = cfg.num_params()
    assert 7.5e9 < n < 8.5e9, n


def test_sharded_forward_matches_single_device(tiny, devices8):
    """TP+DP sharded execution must be numerically equivalent (f32) to
    single-device execution."""
    cfg, params = tiny
    mesh = make_mesh(dp=2, tp=2, devices=devices8)
    B, T, S = 2, 4, 8
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, T)), dtype=jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    start = jnp.zeros((B,), jnp.int32)

    ck, cv = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    ref_logits, ref_k, ref_v = llama.forward(params, cfg, tokens, pos, ck, cv, start)

    sh_params = shard_pytree(params, llama.param_specs(cfg), mesh)
    kspec, vspec = llama.kv_cache_specs()
    sh_ck = jax.device_put(ck, NamedSharding(mesh, kspec))
    sh_cv = jax.device_put(cv, NamedSharding(mesh, vspec))
    sh_tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))

    fwd = jax.jit(lambda p, t, q, k, v, s: llama.forward(p, cfg, t, q, k, v, s))
    out_logits, out_k, out_v = fwd(sh_params, sh_tokens, pos, sh_ck, sh_cv, start)

    np.testing.assert_allclose(
        np.asarray(out_logits), np.asarray(ref_logits), rtol=1e-3, atol=1e-3
    )
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(ref_k), rtol=1e-3, atol=1e-3)


def test_sharded_moe_matches_single_device(devices8):
    """Expert-parallel MoE over tp axis is numerically equivalent."""
    cfg = get_config("test-tiny-moe")
    params = llama.init_params(cfg, jax.random.key(2), dtype=jnp.float32)
    mesh = make_mesh(dp=2, tp=4, devices=devices8)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, cfg.vocab_size, size=(2, 4)), dtype=jnp.int32
    )
    ref = llama.forward_train(params, cfg, tokens)
    sh_params = shard_pytree(params, llama.param_specs(cfg), mesh)
    got = jax.jit(lambda p, t: llama.forward_train(p, cfg, t))(sh_params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-3, atol=1e-3)


def test_train_step_runs_and_loss_decreases(devices8):
    import optax
    from omnia_tpu.parallel import make_mesh
    from omnia_tpu.train import make_train_step

    cfg = get_config("test-tiny")
    mesh = make_mesh(dp=2, tp=2, devices=devices8)
    init_fn, train_step = make_train_step(cfg, optax.adamw(1e-2), mesh=mesh)
    state = init_fn(jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, size=(4, 12)),
        dtype=jnp.int32,
    )
    state, loss0 = train_step(state, tokens)
    for _ in range(5):
        state, loss = train_step(state, tokens)
    assert float(loss) < float(loss0)
    assert int(state.step) == 6


# ---------------------------------------------------------------------------
# The cache's way through the layer scan: it is the scan's carry, written
# and read in place — against a plain Python loop over the layers
# ---------------------------------------------------------------------------

_S, _PAGE = 64, 16
_SHAPES = {  # name -> (write_start [B], T)
    "decode-ragged": ([5, 0, 17], 1),
    "verify-window": ([5, 0, 17], 4),   # B > 1, T > 1, per-slot offsets
    "extend-one-slot": ([9], 8),        # a [L, 1, S, H, D] slot view
}


def _random_cache(cfg, batch, layout, seed):
    """(ck, cv) holding random rows everywhere, so a row that moves shows."""
    from omnia_tpu.models import kv_quant as kvq
    from omnia_tpu.models.paged_kv import PagedKV

    rng = np.random.default_rng(seed)
    paged, int8 = layout.startswith("paged"), layout.endswith("int8")
    npg = _S // _PAGE
    lead = (batch * npg + 2, _PAGE) if paged else (batch, _S)
    table = jnp.asarray(
        (rng.permutation(batch * npg) + 2).reshape(batch, npg), jnp.int32
    )

    def one():
        rows = jnp.asarray(rng.standard_normal(
            (cfg.num_layers, *lead, cfg.num_kv_heads, cfg.head_dim)
        ), jnp.float32)
        rows = kvq.quantize_rows(rows) if int8 else rows
        return PagedKV(rows, table) if paged else rows

    return one(), one()


def _loop_forward(params, cfg, tokens, qpos, ck, cv, start):
    """forward as a plain Python loop: each layer gets ITS slice of the
    cache as a one-layer cache and the slices are stacked back — the
    route the scan used to take, with the same ``_layer`` arithmetic."""
    from omnia_tpu.models.kv_quant import kv_map
    from omnia_tpu.models.paged_kv import PagedKV, is_paged

    def pool(c):
        return c.pool if is_paged(c) else c

    def one_layer(c, l):
        rows = kv_map(lambda a: a[l:l + 1], pool(c))
        return PagedKV(rows, c.table) if is_paged(c) else rows

    x, (cos, sin) = llama._embed(params, cfg, tokens, qpos)
    ks, vs = [], []
    for l in range(cfg.num_layers):
        p = jax.tree.map(lambda a: a[l], params["layers"])
        x, k_l, v_l = llama._layer(
            x, p, cfg, cos, sin, qpos, one_layer(ck, l), one_layer(cv, l),
            start, layer=0,
        )
        ks.append(pool(k_l))
        vs.append(pool(v_l))

    def stack(parts, like):
        rows = kv_map(lambda *a: jnp.concatenate(a, axis=0), *parts)
        return PagedKV(rows, like.table) if is_paged(like) else rows

    return llama._logits(params, cfg, x), stack(ks, ck), stack(vs, cv)


def _row_leaves(cache):
    """The cache's arrays as [L, B, S, ...] numpy (a paged pool read
    through its table), so row (l, b, s) means the same in every layout."""
    from omnia_tpu.models.paged_kv import is_paged

    out = []
    for leaf in jax.tree.leaves(cache.pool if is_paged(cache) else cache):
        a = np.asarray(leaf)
        if is_paged(cache):
            t = np.asarray(cache.table)
            a = a[:, t].reshape(a.shape[0], t.shape[0], -1, *a.shape[3:])
        out.append(a)
    return out


@pytest.mark.parametrize("route", ["0", "interpret"])
@pytest.mark.parametrize("layout", ["plain", "int8", "paged", "paged-int8"])
@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_forward_keeps_cache_in_place_like_a_plain_layer_loop(
        monkeypatch, shape, layout, route):
    """The scan that carries the whole cache gives the logits and the
    cache of a Python loop over per-layer slices, on the einsum route and
    through the (interpreted) decode kernel, and touches only rows
    ``[layer, b, start[b] : start[b] + T]``."""
    from omnia_tpu.ops import attention as attn

    monkeypatch.setenv("OMNIA_PALLAS_DECODE", route)
    attn._pallas_decode_mode.cache_clear()
    try:
        cfg = get_config("test-tiny", num_layers=3)
        params = llama.init_params(cfg, jax.random.key(1), dtype=jnp.float32)
        starts, T = _SHAPES[shape]
        B = len(starts)
        start = jnp.asarray(starts, jnp.int32)
        qpos = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        tokens = jnp.asarray(
            np.random.default_rng(2).integers(0, cfg.vocab_size, (B, T)),
            jnp.int32,
        )
        ck, cv = _random_cache(cfg, B, layout, seed=3)

        logits, nk, nv = jax.jit(
            lambda *a: llama.forward(params, cfg, *a)
        )(tokens, qpos, ck, cv, start)
        ref_logits, rk, rv = _loop_forward(
            params, cfg, tokens, qpos, ck, cv, start
        )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits), rtol=1e-5, atol=1e-5
        )
        written = np.zeros((cfg.num_layers, B, _S), bool)
        for b, s in enumerate(starts):
            written[:, b, s:s + T] = True
        for old, new, ref in ((ck, nk, rk), (cv, nv, rv)):
            for o, n, r in zip(*map(_row_leaves, (old, new, ref))):
                if n.dtype == np.int8:  # a half-way case may round either way
                    assert np.abs(n.astype(int) - r.astype(int)).max() <= 1
                else:
                    np.testing.assert_allclose(n, r, rtol=1e-5, atol=1e-6)
                np.testing.assert_array_equal(n[~written], o[~written])
                assert (n[written] != o[written]).any(axis=-1).all()
    finally:
        attn._pallas_decode_mode.cache_clear()


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["plain", "int8"])
@pytest.mark.parametrize("T", [1, 4])
def test_write_kv_sharded_form_writes_the_same_rows(T, kv_quant):
    """The scatter a "dp"-sharded cache is written with and the per-slot
    updates of an unsharded one are the same write, a window that would
    run past the cache end included (both shift it back to fit)."""
    cfg = get_config("test-tiny", num_layers=3)
    B, S = 3, 16
    ck, _ = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32, kv_quant=kv_quant)
    new = jax.random.normal(
        jax.random.key(0), (B, T, cfg.num_kv_heads, cfg.head_dim), jnp.float32
    )
    start = jnp.asarray([0, 7, S - 1], jnp.int32)  # the last one overruns for T > 1
    a = llama._write_kv(ck, new, start, 1)
    b = llama._write_kv(ck, new, start, 1, slots_sharded=True)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        x = np.asarray(x)
        assert not x[0].any() and not x[2].any()       # other layers untouched
        assert x[1, 2, S - T:].any() and not x[1, 2, :S - T].any()
