"""The blocked prefill attention (ops/prefill_attention.py) under the Pallas
interpreter against the einsum path it replaces, the routing that picks
between them, and the two families' forward passes with the route on."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.engine import EngineConfig, InferenceEngine, SamplingParams
from omnia_tpu.engine.family import prefill_blocked
from omnia_tpu.models import get_config, llama, mla
from omnia_tpu.models.kv_quant import quantize_rows
from omnia_tpu.models.paged_kv import PagedKV
from omnia_tpu.ops import attention as attn
from omnia_tpu.ops.prefill_attention import prefill_attention, tiles

HERE = os.path.dirname(os.path.abspath(__file__))


def _cases_of(name):
    """Another test file's helpers (tests/ is no package)."""
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def route(monkeypatch):
    """set_route("interpret" | "0"): the switch the decode kernels have."""
    def set_route(mode):
        monkeypatch.setenv("OMNIA_PALLAS_DECODE", mode)
        attn._pallas_decode_mode.cache_clear()

    yield set_route
    attn._pallas_decode_mode.cache_clear()


@pytest.fixture
def no_band_is_small(monkeypatch):
    """The window route as a model of served size meets it: a test model's
    band scores are a megabyte, and the route keeps the einsums under 96 MiB."""
    monkeypatch.setattr(attn, "_BAND_SCORES_MOST", 0)


def _operands(B, T, S, H, Hkv, D, dtype=jnp.float32, seed=0, layers=None):
    keys = jax.random.split(jax.random.key(seed), 3)
    lead = () if layers is None else (layers,)
    return (jax.random.normal(keys[0], (B, T, H, D), dtype),
            jax.random.normal(keys[1], (*lead, B, S, Hkv, D), dtype),
            jax.random.normal(keys[2], (*lead, B, S, Hkv, D), dtype))


def _blocked(q, k, v, pos, layer=None, tiling=None):
    B, T, H, D = q.shape
    Hkv = k.shape[-2]
    out = prefill_attention(
        q.reshape(B, T, H * D), k.reshape(*k.shape[:-2], Hkv * D),
        v.reshape(*v.shape[:-2], Hkv * D), pos, layer, kv_heads=Hkv, scale=D ** -0.5,
        tiling=tiling, interpret=True)
    return np.asarray(out.reshape(B, T, H, D), np.float32)


def _contiguous(first, T):
    return jnp.asarray(first, jnp.int32)[:, None] + jnp.arange(T, dtype=jnp.int32)[None]


# -- the kernel against the einsums ---------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("G", [1, 4, 8])
def test_a_fresh_chunk_equals_the_einsum_path(G, dtype, tol):
    """Keys from the chunk itself, two query tiles and two key blocks, so
    that the second tile's running softmax spans both and the first skips
    the block past it."""
    q, k, v = _operands(1, 256, 256, 2 * G, 2, 128, dtype)
    pos = _contiguous([0], 256)
    want = np.asarray(attn.einsum_attention(q, k, v, pos), np.float32)
    got = _blocked(q, k, v, pos, tiling=(128, 128))
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def _latent_cfg(dn, dr, dv=128):
    return dataclasses.replace(get_config("test-tiny-mla"), qk_nope_head_dim=dn,
                               qk_rope_head_dim=dr, v_head_dim=dv)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("dn,dr", [(128, 64), (64, 64)])
def test_the_latent_familys_expanded_form_equals_its_einsums(route, dn, dr, dtype, tol):
    """A key 192 wide (padded to 256 lanes) and 128 wide, values 128 wide,
    YaRN's softmax scale: `_expanded_attention` with the route on against
    itself with the route off, a fresh chunk and a piece over longer rows."""
    cfg = _latent_cfg(dn, dr)
    B, T, H, R, W = 1, 128, cfg.num_heads, cfg.kv_rank, mla.row_width(cfg)
    keys = jax.random.split(jax.random.key(1), 4)
    q_nope = jax.random.normal(keys[0], (B, T, H, dn), dtype)
    q_rope = jax.random.normal(keys[1], (B, T, H, dr), dtype)
    wkvb = (jax.random.normal(keys[3], (R, H * (dn + 128)), jnp.float32) * R ** -0.5).astype(dtype)
    for S, first in ((128, 0), (384, 200)):
        rows = jax.random.normal(keys[2], (B, S, W), dtype).at[..., R + dr:].set(0)
        pos = _contiguous([first], T)
        out = {}
        for mode in ("0", "interpret"):
            route(mode)
            assert attn.prefill_kernel_on(T, S, cfg.attn_value_width) == (mode == "interpret")
            out[mode] = np.asarray(mla._expanded_attention(q_nope, q_rope, rows, wkvb, cfg, pos),
                                   np.float32)
        np.testing.assert_allclose(out["interpret"], out["0"], atol=tol, rtol=tol)


@pytest.mark.parametrize("first", [0, 200, 384], ids=["at 0", "inside a block", "the last block"])
def test_a_piece_reads_no_row_past_its_own(first):
    """A piece of 128 rows at offset `first` over layer 1 of a cache of 512
    rows in blocks of 128: every row past `first + 128`, and every other
    layer, holds NaN. A block wholly past the piece is never fetched; the
    one it ends in is visited and its rows past the piece masked, in the
    scores and in the values."""
    T, S = 128, 512
    q, k, v = _operands(1, T, S, 8, 2, 128, layers=2)
    pos = _contiguous([first], T)
    want = np.asarray(attn.einsum_attention(q, k, v, pos, 1))
    written = jnp.arange(S)[None, None, :, None, None] < first + T
    mine = (jnp.arange(2) == 1)[:, None, None, None, None]
    kp, vp = (jnp.where(written & mine, x, jnp.nan) for x in (k, v))
    got = _blocked(q, kp, vp, pos, jnp.int32(1), tiling=(128, 128))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_two_slots_with_different_offsets_and_a_short_last_tile_and_block():
    """Slot 0 at 0 and slot 1 at 300 of 640 rows, 384 queries in tiles of
    256 (the second short) over blocks of 256 (the third short): what lies
    behind the arrays is masked like any row past the queries'."""
    T, S = 384, 640
    q, k, v = _operands(2, T, S, 4, 2, 128)
    pos = _contiguous([0, 256], T)
    want = np.asarray(attn.einsum_attention(q, k, v, pos))
    got = _blocked(q, k, v, pos, tiling=(256, 256))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert tiles(1152, 1152, 1) == (640, 1024) and tiles(1024, 8960, 8) == (512, 1024)
    assert tiles(128, 128, 4) == (128, 128) and tiles(2048, 2048, 1) == (1024, 1024)


def test_rows_in_no_whole_128s_are_a_short_last_block():
    """S = 192: the block is 256 rows, its last 64 behind the array and masked
    (a row's sum adds a block's columns 128 at a time, so a block of 192 would
    drop its last 64 from the sum and keep them in the product: refused)."""
    T, S = 128, 192
    q, k, v = _operands(1, T, S, 4, 2, 128, seed=5)
    pos = _contiguous([64], T)
    want = np.asarray(attn.einsum_attention(q, k, v, pos))
    assert tiles(T, S, 2) == (128, 256)
    np.testing.assert_allclose(_blocked(q, k, v, pos), want, atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="key block 192"):
        _blocked(q, k, v, pos, tiling=(128, 192))


def test_the_mask_is_the_positions_own():
    """Positions in no order, some repeated, one tile's all small: the mask
    follows each query's own position, the skipping only its tile's
    largest."""
    T, S = 256, 512
    q, k, v = _operands(1, T, S, 4, 4, 128, seed=3)
    rng = np.random.default_rng(0)
    pos = np.concatenate([rng.integers(0, 100, 128), rng.integers(0, S, 128)])[None]
    pos = jnp.asarray(pos, jnp.int32)
    want = np.asarray(attn.einsum_attention(q, k, v, pos))
    got = _blocked(q, k, v, pos, tiling=(128, 128))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# -- a window: the kernel against the einsum band ----------------------------------


def _window_operands(B, T, W, H, Hkv, D=128, seed=0):
    """q and the chunk's own k, v [B, T, ·], and the W rows before it."""
    keys = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(keys[0], (B, T, H, D)),
            *(jax.random.normal(key, (B, T, Hkv, D)) for key in keys[1:3]),
            *(jax.random.normal(key, (B, W, Hkv, D)) for key in keys[3:]))


def _banded(q, k, v, prev_k, prev_v, first, W, tiling, poison=True):
    """The kernel as `window_attention` calls it: a fresh chunk's own rows
    (`first` None), or `[prev | chunk]` with the queries at rows W + t and the
    slot's lowest row W - first; with `poison` every row before position 0
    (what a ring's unreached rows hold) is NaN in keys and values."""
    B, T, H, D = q.shape
    Hkv, lowest, at = k.shape[2], None, 0
    if first is not None:
        first = jnp.asarray(first, jnp.int32)
        lowest, at = jnp.maximum(W - first, 0), W
        k, v = jnp.concatenate([prev_k, k], axis=1), jnp.concatenate([prev_v, v], axis=1)
        if poison:
            dead = (jnp.arange(W + T)[None, :] < lowest[:, None])[:, :, None, None]
            k, v = jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v)
    pos = jnp.broadcast_to(at + jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    out = prefill_attention(
        q.reshape(B, T, H * D), k.reshape(B, -1, Hkv * D), v.reshape(B, -1, Hkv * D), pos,
        None, lowest, kv_heads=Hkv, scale=D ** -0.5, window=W, tiling=tiling, interpret=True)
    return np.asarray(out.reshape(B, T, H, D))


@pytest.mark.parametrize("T", [128, 256, 512], ids=["under the window", "at it", "twice it"])
def test_a_fresh_chunk_under_a_window_equals_the_band(T):
    """W = 256, the chunk its own keys: under and at the window the bound
    never binds, at twice it the second tile's first block is not block 0."""
    W = 256
    q, k, v, _, _ = _window_operands(1, T, W, 4, 2, seed=T)
    want = np.asarray(attn.band_attention(q, k, v, None, None, None, W))
    got = _banded(q, k, v, None, None, None, W, (128, 128))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("G", [1, 8])
@pytest.mark.parametrize("first", [50, 512, 333],
                         ids=["under the window", "a multiple of it", "off every edge"])
def test_a_piece_behind_its_window_equals_the_band(first, G):
    """A piece of 256 rows behind the 256 before it, tiles and blocks of 128:
    under the window the slot's lowest row binds (rows before position 0, and
    with them a ring's unreached rows, hold NaN); past it each tile starts at
    the block of its smallest bound and the one block every query sees whole
    takes the unmasked body."""
    T = W = 256
    q, k, v, pk, pv = _window_operands(1, T, W, 2 * G, 2, seed=first + G)
    want = np.asarray(attn.band_attention(q, k, v, pk, pv, jnp.asarray([first]), W))
    got = _banded(q, k, v, pk, pv, [first], W, (128, 128))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("T,W,firsts,tiling", [
    (256, 128, [0, 300], (128, 128)),       # an empty slot beside one past the window
    (384, 128, [5, 4000], (256, 256)),      # a short last tile, a short last block
    (512, 256, [3, 256], (256, 512)),       # one block as wide as tile and window together
    (256, 128, [64, 640], (256, 128)),      # a tile over three blocks of four
], ids=["an empty slot", "a short last tile", "a wide block", "narrow blocks"])
def test_two_slots_with_different_firsts_under_a_window(T, W, firsts, tiling):
    q, k, v, pk, pv = _window_operands(2, T, W, 8, 2, seed=T + W)
    want = np.asarray(attn.band_attention(q, k, v, pk, pv, jnp.asarray(firsts), W))
    got = _banded(q, k, v, pk, pv, firsts, W, tiling)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_a_tile_under_a_window_visits_a_static_number_of_blocks():
    """Keys of 4,096 + 512 rows (a layer of a cache), a window of 256, tiles
    of 256 over blocks of 128: the grid's last axis is ⌈(256 + 255) / 128⌉ + 1
    = 5 whatever the keys' length, not their 36 blocks, and every row that no
    query sees, before the band as well as past it, holds NaN."""
    T, W, before = 512, 256, 4096
    S = before + T + 128
    q, k, v = _operands(1, T, S, 4, 2, 128, seed=9)
    pos = _contiguous([before], T)
    col = jnp.arange(S)
    scores = jnp.einsum("bthgd,bshd->bhgts", q.reshape(1, T, 2, 2, 128), k) * 128 ** -0.5
    seen = (col[None, None] <= pos[:, :, None]) & (col[None, None] > pos[:, :, None] - W)
    probs = jax.nn.softmax(jnp.where(seen[:, None, None], scores, -1e30), axis=-1)
    want = np.asarray(jnp.einsum("bhgts,bshd->bthgd", probs, v).reshape(1, T, 4, 128))
    reached = ((col > before - W) & (col < before + T))[None, :, None, None]
    flat = (q.reshape(1, T, -1), jnp.where(reached, k, jnp.nan).reshape(1, S, -1),
            jnp.where(reached, v, jnp.nan).reshape(1, S, -1), pos)
    call = lambda *a: prefill_attention(*a, kv_heads=2, scale=128 ** -0.5, window=W,  # noqa: E731
                                        tiling=(256, 128), interpret=True)
    assert "grid=(1, 2, 2, 5)" in str(jax.make_jaxpr(call)(*flat))
    got = np.asarray(call(*flat).reshape(1, T, 4, 128))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_window_0_is_the_call_without_a_window_bit_for_bit():
    """`window=0` (whatever `lowest` it is handed) gives the result of the call
    that names no window bit for bit (tests/chipless/test_code_mixed.py holds the
    lowered programs of the cells without window layers to the parent's text);
    and so does a window that never binds, through its own program."""
    T, S = 256, 512
    q, k, v = _operands(2, T, S, 8, 2, 128, seed=7)
    flat = (q.reshape(2, T, -1), k.reshape(2, S, -1), v.reshape(2, S, -1),
            _contiguous([100, 256], T))
    kw = dict(kv_heads=2, scale=128 ** -0.5, tiling=(128, 128), interpret=True)
    want = np.asarray(prefill_attention(*flat, **kw))
    zero = prefill_attention(*flat, None, jnp.full((2,), 40, jnp.int32), window=0, **kw)
    np.testing.assert_array_equal(np.asarray(zero), want)
    np.testing.assert_array_equal(np.asarray(prefill_attention(*flat, window=S, **kw)), want)


# -- routing ----------------------------------------------------------------------


def test_the_route_takes_whole_tiles_over_plain_unsharded_rows(route, devices8):
    from omnia_tpu.parallel import make_mesh

    route("interpret")
    assert attn.prefill_kernel_on(128, 128, 128)
    assert attn.prefill_kernel_on(1152, 2304, 128)
    assert not attn.prefill_kernel_on(64, 128, 128)          # below a tile
    assert not attn.prefill_kernel_on(1, 128, 128)           # a decode step
    assert not attn.prefill_kernel_on(128, 192, 128)         # rows in no whole tiles
    assert not attn.prefill_kernel_on(128, 128, 64)          # heads of 64 lanes
    assert not attn.prefill_kernel_on(128, 128, 128, plain=False)
    assert not attn.prefill_kernel_on(128, 128, 128, mesh=make_mesh(dp=2, tp=2))
    route("0")
    assert not attn.prefill_kernel_on(128, 128, 128)


@pytest.mark.parametrize("T,window,heads,fresh,kernel", [
    (1024, 1024, 32, False, True),    # mellum2's piece: 256 MiB of band scores
    (512, 1024, 32, False, False),    # its shorter pieces: 96 and 40 MiB
    (256, 1024, 32, False, False),
    (1024, 1024, 32, True, True),     # its fresh chunks: the window never binds
    (256, 1024, 32, True, True),
    (1024, 128, 64, False, False),    # k-exaone's piece and fresh chunk: 64 MiB
    (1024, 128, 64, True, False),
    (128, 128, 64, True, True),       # a fresh chunk no longer than its window
    (1024, 256, 64, False, True),     # 128 MiB
    (1024, 512, 32, True, True),      # a fresh chunk longer than its window: 128 MiB
    (1024, 1000, 32, False, False),   # [1,000 rows | the piece] are no whole tiles
    (192, 128, 64, True, False),      # nor is the chunk
])
def test_a_window_layers_chunk_takes_the_kernel_where_its_band_is_large(
        route, devices8, T, window, heads, fresh, kernel):
    """The window route from shapes alone: what `prefill_kernel_on` asks of the
    rows of keys, and then a fresh chunk inside its window or band scores
    `[H, T, Q + window]` of more than 96 MiB in float32."""
    from omnia_tpu.parallel import make_mesh

    route("interpret")
    assert attn.window_kernel_on(T, window, heads, 128, fresh) == kernel
    assert not attn.window_kernel_on(T, window, heads, 64, fresh)
    assert not attn.window_kernel_on(T, window, heads, 128, fresh, make_mesh(dp=2, tp=2))
    route("0")
    assert not attn.window_kernel_on(T, window, heads, 128, fresh)


def test_the_window_route_hands_the_band_what_the_kernel_does_not_serve(route, monkeypatch):
    """`window_attention` with the route on: a small band keeps the einsums
    (the kernel is not called), a large one equals them through the kernel."""
    import omnia_tpu.ops.prefill_attention as pa

    route("interpret")
    q, k, v, pk, pv = _window_operands(2, 256, 128, 8, 2)
    first = jnp.asarray([70, 900], jnp.int32)
    want = np.asarray(attn.band_attention(q, k, v, pk, pv, first, 128))
    kernel, calls = pa.prefill_attention, []

    def recorded(*a, **kw):
        calls.append(kw["window"])
        return kernel(*a, **kw)

    monkeypatch.setattr(pa, "prefill_attention", recorded)
    np.testing.assert_array_equal(
        np.asarray(attn.window_attention(q, k, v, pk, pv, first, 128)), want)
    assert calls == []
    monkeypatch.setattr(attn, "_BAND_SCORES_MOST", 0)
    np.testing.assert_allclose(np.asarray(attn.window_attention(q, k, v, pk, pv, first, 128)),
                               want, atol=1e-5, rtol=1e-5)
    fresh = np.asarray(attn.band_attention(q, k, v, None, None, None, 128))
    np.testing.assert_allclose(np.asarray(attn.window_attention(q, k, v, None, None, None, 128)),
                               fresh, atol=1e-5, rtol=1e-5)
    inside = np.asarray(attn.band_attention(q, k, v, None, None, None, 256))
    np.testing.assert_allclose(np.asarray(attn.window_attention(q, k, v, None, None, None, 256)),
                               inside, atol=1e-5, rtol=1e-5)
    assert calls == [128, 128, 0]


@pytest.mark.parametrize("case", ["below a tile", "QuantKV", "paged", "mesh"])
def test_what_the_kernel_does_not_serve_takes_the_einsums(route, monkeypatch, devices8, case):
    """With the route on, `gqa_attention` must not reach the kernel for a
    chunk below a tile, an int8 cache, a paged pool or a mesh."""
    import omnia_tpu.ops.prefill_attention as pa

    def refuse(*a, **k):
        raise AssertionError("the kernel was called")

    route("interpret")
    monkeypatch.setattr(pa, "prefill_attention", refuse)
    T = 64 if case == "below a tile" else 128
    q, k, v = _operands(2, T, 128, 4, 2, 128)
    pos = _contiguous([0, 0], T)
    want = np.asarray(attn.einsum_attention(q, k, v, pos))
    mesh = None
    if case == "QuantKV":
        k, v = quantize_rows(k), quantize_rows(v)
        want = np.asarray(attn.einsum_attention(q, k, v, pos))
    elif case == "paged":
        table = jnp.arange(2 * 4, dtype=jnp.int32).reshape(2, 4) + 1
        pool = lambda x: jnp.concatenate(  # noqa: E731  (page 0 is the trash page)
            [jnp.zeros((1, 32, 2, 128)), x.reshape(8, 32, 2, 128)])
        k, v = PagedKV(pool(k), table), PagedKV(pool(v), table)
    elif case == "mesh":
        from omnia_tpu.parallel import make_mesh

        mesh = make_mesh(dp=2, tp=2)
    got = np.asarray(attn.gqa_attention(q, k, v, pos, mesh=mesh))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_with_the_route_off_gqa_attention_lowers_to_the_einsums_text(route):
    route("0")
    q, k, v = _operands(1, 128, 256, 8, 2, 128, layers=3)
    pos = _contiguous([100], 128)
    routed = jax.jit(lambda *a: attn.gqa_attention(*a, layer=2)).lower(q, k, v, pos).as_text()
    plain = jax.jit(lambda *a: attn.einsum_attention(*a, 2)).lower(q, k, v, pos).as_text()
    assert routed == plain and "custom_call" not in routed


def test_gqa_attention_hands_a_chunk_and_a_layer_of_the_cache_to_the_kernel(route):
    q, k, v = _operands(1, 128, 256, 8, 2, 128, layers=3)
    pos = _contiguous([100], 128)
    route("0")
    want_layer = np.asarray(attn.gqa_attention(q, k, v, pos, layer=2))
    want_chunk = np.asarray(attn.gqa_attention(q, k[0], v[0], pos))
    route("interpret")
    np.testing.assert_allclose(np.asarray(attn.gqa_attention(q, k, v, pos, layer=2)),
                               want_layer, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(attn.gqa_attention(q, k[0], v[0], pos)),
                               want_chunk, atol=1e-5, rtol=1e-5)


# -- the families' forward passes with the route on --------------------------------


def test_the_latent_familys_prefill_and_decode_still_equal_the_reference(route):
    """tests/test_mla.py's case at heads the kernel serves (keys 128 + 64,
    values 128): a prefill of 128 tokens (one tile) through the blocked
    kernel, then 8 decode steps through the latent kernel, both interpreted,
    against the plain reference's whole forward; and the fresh prefill."""
    cases = _cases_of("test_mla")
    cfg = _latent_cfg(128, 64)
    params, tokens = cases.seeded(cfg, jnp.float32, n=136)
    want = np.asarray(cases.ref.forward(params, cases.reference_sizes(cfg), jnp.asarray(tokens)))
    route("interpret")
    assert attn.prefill_kernel_on(128, 256, cfg.attn_value_width)
    got = cases.served_logits(params, cfg, tokens, prefill=128)
    assert np.abs(got - want).max() < 1e-4
    fresh, _ = mla.forward_prefill(params, cfg, jnp.asarray(tokens[None, :128]),
                                   jnp.arange(128, dtype=jnp.int32)[None])
    assert np.abs(np.asarray(fresh[0]) - want[:128]).max() < 1e-4


@pytest.mark.parametrize("placement", [[(128, 128)], [(128, 128), (100, 128)],
                                       [(256, 256), (200, 256)]],
                         ids=["one bucket", "pieces, the last padded", "pieces of two tiles"])
@pytest.mark.parametrize("window", [8, 128])
def test_the_pair_familys_stacks_still_equal_the_reference(route, no_band_is_small, window,
                                                           placement):
    """tests/test_kexaone.py's case at heads of 128 lanes: the full layers'
    prefill through the blocked kernel (a fresh bucket's worth by `extend`'s
    seam, and a padded second piece at offset 128 over the slot's rows), the
    decode steps through both decode kernels, against the plain reference.
    At a window of 8 the window layers' pieces keep the band (8 + 128 rows are
    no whole tiles); at 128 they go through the kernel with its lower
    bound, the first behind a ring that holds nothing (the slot's lowest row
    binds), the second behind the first's last 128 rows, and a fresh chunk
    placed whole (`forward_prefill`) makes the causal call at 128 rows and the
    window call at 256."""
    import omnia_tpu.ops.prefill_attention as pa

    cases = _cases_of("test_kexaone")
    cfg = dataclasses.replace(cases.CFG, head_dim=128, sliding_window=window)
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    n = sum(take for take, _ in placement) + 8
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, n).astype(np.int32)
    sizes = cases.reference_sizes(cfg, cases.file_of(cfg))
    want = np.asarray(cases.ref.forward(params, sizes, jnp.asarray(tokens)))
    route("interpret")
    bucket = placement[0][1]
    assert attn.prefill_kernel_on(bucket, 512, cfg.attn_value_width)
    assert attn.window_kernel_on(bucket, window, cfg.num_heads, 128, False) == (window == 128)
    kernel, windows = pa.prefill_attention, []

    def recorded(*a, **k):
        windows.append(k.get("window", 0))
        return kernel(*a, **k)

    pa.prefill_attention, before = recorded, pa.prefill_attention
    try:
        got = cases.served_logits(params, cfg, tokens, placement, rows=512)
        pieces, windows[:] = set(windows), []
        toks = jnp.asarray(tokens[None, :bucket])
        fresh = cases.SOUND["fresh"](params, toks, jnp.int32(bucket - 1), cfg=cfg)[0]
    finally:
        pa.prefill_attention = before
    assert cases.over_range(got, want) <= cases.TOL
    assert cases.over_range(np.asarray(fresh[0]), want[bucket - 1]) <= cases.TOL
    # What the traced programs handed the kernel: a piece's window layers their
    # window where [the window's rows | the piece] are whole tiles, a fresh
    # chunk's (its own rows alone) wherever it is longer than the window.
    assert pieces == ({0, 128} if window == 128 else {0})
    assert set(windows) == ({0, window} if window < bucket else {0})


def test_forward_train_differentiates_with_the_route_on(route):
    """`forward_train` names the einsums as its attention (a Pallas call has
    no VJP): at a chunk the route would take, its gradient is the route-off
    gradient."""
    cfg = dataclasses.replace(get_config("test-tiny"), head_dim=128)
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (1, 128), 0, cfg.vocab_size)

    def loss(p):
        return jnp.mean(llama.forward_train(p, cfg, tokens) ** 2)

    route("0")
    want = jax.grad(loss)(params)
    route("interpret")
    assert attn.prefill_kernel_on(128, 128, cfg.attn_value_width)
    got = jax.grad(loss)(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("window,T", [(8, 128), (128, 256)],
                         ids=["the full layers", "the window layers too"])
def test_forward_train_of_the_stacks_keeps_the_einsums_too(route, monkeypatch, no_band_is_small,
                                                           window, T):
    """The stacks' full layers take `forward_train`'s `attn_fn` as well, and
    where there is one the window layers take the einsum band (at a window of
    128 and 256 tokens their fresh chunk would take the kernel with its lower
    bound). Their experts' grouped matmul is a Pallas call from one row tile
    up, so a gradient at 128 rows with the route on stops there: the forward
    alone."""
    import omnia_tpu.ops.prefill_attention as pa

    def refuse(*a, **k):
        raise AssertionError("the kernel was called")

    cfg = dataclasses.replace(get_config("test-tiny-window"), head_dim=128, sliding_window=window)
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (1, T), 0, cfg.vocab_size)
    route("0")
    want = np.asarray(llama.forward_train(params, cfg, tokens))
    route("interpret")
    monkeypatch.setattr(pa, "prefill_attention", refuse)
    assert attn.prefill_kernel_on(T, T, cfg.attn_value_width)
    assert attn.window_kernel_on(T, window, cfg.num_heads, cfg.attn_value_width, True)
    np.testing.assert_allclose(np.asarray(llama.forward_train(params, cfg, tokens)), want,
                               atol=1e-5, rtol=1e-5)


# -- the engine's counter -----------------------------------------------------------


def _ringed(window):
    """test-tiny-window (two window layers, one full) at heads of 128 lanes."""
    return dataclasses.replace(get_config("test-tiny-window"), head_dim=128, max_seq_len=256,
                               sliding_window=window)


@pytest.mark.parametrize("model", ["pair", "rings of 128", "rings of 8"])
def test_the_engine_counts_the_tokens_whose_program_took_the_kernel(route, caplog,
                                                                    no_band_is_small, model):
    """Buckets of 64 and 128 over 256 rows: a prompt of 100 goes through the
    128-row bucket (the kernel), one of 40 through the 64-row one (the
    einsums), one of 200 through `extend` in pieces of 128 (the kernel). A
    model with rings counts a program only if its window layers take the
    kernel too: at a window of 128 they do; at 8 a piece's `[8 rows | 128]`
    are no whole tiles, so its extend programs hold the kernel in the full
    layer and the band in the window layers, and do not count."""
    import logging

    route("interpret")
    cfg = {"pair": dataclasses.replace(get_config("test-tiny"), head_dim=128, num_layers=1,
                                       max_seq_len=256),
           "rings of 128": _ringed(128), "rings of 8": _ringed(8)}[model]
    extend = model != "rings of 8"
    with caplog.at_level(logging.INFO, logger="omnia_tpu.engine.engine"):
        eng = InferenceEngine(cfg, EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64, 128),
                                                dtype="float32", max_sessions=0), seed=0)
    assert ("blocked_buckets={'prefill': [128], 'extend': %s}" % ([128] if extend else [])
            in caplog.text)
    assert eng._blocked(128, True) and eng._blocked(128, False) == extend
    assert not eng._blocked(64, True)

    def turn(n):
        eng.submit(list(range(1, n + 1)), SamplingParams(temperature=0.0, max_tokens=2))
        while eng.metrics["requests_finished"] < eng.metrics["requests_submitted"]:
            eng.step()

    turn(100)
    assert (eng.metrics["prefill_tokens"], eng.metrics["prefill_tokens_blocked"]) == (100, 100)
    turn(40)
    assert (eng.metrics["prefill_tokens"], eng.metrics["prefill_tokens_blocked"]) == (140, 100)
    turn(200)
    assert (eng.metrics["prefill_tokens"], eng.metrics["prefill_tokens_blocked"]) == (
        340, 300 if extend else 100)


@pytest.mark.parametrize("case", ["plain", "kv_quant", "kv_pages", "mesh", "rings"])
def test_the_counter_says_what_the_engines_programs_hold(route, monkeypatch, devices8,
                                                         no_band_is_small, case):
    """A prompt of 100 through ``prefill_insert`` and one of 200 through
    ``extend`` in pieces of 128, on an engine of each kind: whether the traced
    program called the kernel is what ``prefill_blocked`` says of it, and the
    counter follows. A fresh chunk is plain rows whatever the cache, so only
    a mesh keeps it on the einsums (``build_programs`` hands the model its
    mesh for the fresh prefill too: XLA cannot partition a Mosaic call); a
    slot's view is int8 under ``kv_quant`` and gathered plain rows under
    ``kv_pages``. A model with rings (a window of 128: a run of dense window
    layers, one of sparse ones, one of full) holds the kernel once a run in
    each traced program, the window runs' with their window in a piece and
    without it in a fresh chunk no longer than the window."""
    import omnia_tpu.ops.prefill_attention as pa

    route("interpret")
    kernel, seen = pa.prefill_attention, []

    def recorded(q, *a, **k):
        seen.append((q.shape[1], k.get("window", 0)))
        return kernel(q, *a, **k)

    monkeypatch.setattr(pa, "prefill_attention", recorded)
    cfg = _ringed(128) if case == "rings" else dataclasses.replace(
        get_config("test-tiny"), head_dim=128, num_layers=1, max_seq_len=256)
    kind = {"plain": {}, "kv_quant": {"kv_quant": "int8"},
            "kv_pages": {"kv_pages": 16, "kv_page_tokens": 64}, "mesh": {"tp": 2},
            "rings": {}}[case]
    eng = InferenceEngine(cfg, EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64, 128),
                                            dtype="float32", max_sessions=0, **kind), seed=0)
    want = {"plain": (True, True), "kv_quant": (True, False), "kv_pages": (True, True),
            "mesh": (False, False), "rings": (True, True)}[case]
    assert (eng._blocked(128, True), eng._blocked(128, False)) == want
    assert want == tuple(prefill_blocked(cfg, eng.cfg, eng._mesh, 128, f) for f in (True, False))

    def turn(n):
        before = len(seen), eng.metrics["prefill_tokens_blocked"]
        eng.submit(list(range(1, n + 1)), SamplingParams(temperature=0.0, max_tokens=2))
        while eng.metrics["requests_finished"] < eng.metrics["requests_submitted"]:
            eng.step()
        return len(seen) > before[0], eng.metrics["prefill_tokens_blocked"] - before[1]

    assert turn(100) == (want[0], 100 * want[0])            # prefill_insert
    fresh = list(seen)
    assert turn(200) == (want[1], 200 * want[1])            # extend, two pieces
    if case == "rings":
        assert fresh == [(128, 0)] * 3                      # dense window, sparse window, full
        # `extend_nosample` for the first piece, `extend` for the last
        assert seen[3:] == [(128, 128), (128, 128), (128, 0)] * 2
