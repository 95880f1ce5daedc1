"""The Pallas grouped matmul of a prompt's and a decode step's row counts
(ops/grouped_matmul.py), interpreted on the CPU: held against
``jax.lax.ragged_dot`` and against a plain loop over the runs in float32,
and ``moe_dropless`` end to end through both routes of
``ops/moe.py::_grouped_matmul`` on the same inputs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_moe_share as share  # tests/ is on the path: its cases, at more rows

from omnia_tpu.ops import attention as attn
from omnia_tpu.ops import grouped_matmul as gmm
from omnia_tpu.ops import moe

TM = 16  # the row tile of these cases (ROW_TILE is 128)


def step_runs(held, runs, empty, seed=0):
    """``held`` rows over ``runs`` runs as a router leaves them: ``empty``
    runs drawn to get none, every other at least one and the rest by lot."""
    rng = np.random.default_rng(seed)
    full = np.sort(rng.permutation(runs)[empty:])
    sizes = np.zeros(runs, np.int64)
    sizes[full] = 1 + rng.multinomial(held - len(full), np.ones(len(full)) / len(full))
    return sizes.tolist()


# name: (rows, the runs' sizes)
RUNS = {
    "runs that end inside a row tile": (64, [5, 20, 3, 36]),
    "empty runs between full ones": (64, [16, 0, 0, 32, 0, 16]),
    "one run longer than several tiles": (80, [3, 70, 7]),
    "rows past the last held run": (64, [10, 9, 0, 5]),
    "a first run that is empty": (48, [0, 0, 17, 31]),
    "rows that are no multiple of the tile": (70, [10, 0, 30, 5, 0, 7]),
    "every run a tile exactly": (64, [16, 16, 16, 16]),
    "one row a run": (16, [1, 1, 1, 1, 1]),
    # The served decode steps' calls (slots × k rows; PERF.md section 6, PR 42's
    # table): a few rows a run, most runs sharing a row tile with others.
    "a decode step's 192 rows in 64 runs of which 7 are empty": (
        192, step_runs(192, 64, empty=7)),
    "a decode step's 256 rows of which 37 are held, in 16 runs of which 4 are empty": (
        256, step_runs(37, 16, empty=4)),
    "a decode step's 384 rows in 32 runs": (384, step_runs(384, 32, empty=0)),
}
# (K, N) in the cells' ratios of width to expert width: 3.5, 3 and 2 to 1
# (xing4-29b-a4b 3584 : 1024, k-exaone-236b-a23b 6144 : 2048,
# mistral-small-4 4096 : 2048), gate / up and down.
RATIOS = [(448, 128), (128, 448), (384, 128), (128, 384), (256, 128), (128, 256)]
TOLERANCE = {jnp.float32: dict(atol=1e-5, rtol=1e-5), jnp.bfloat16: dict(atol=2e-2, rtol=2e-2)}


def operands(rows, sizes, K, N, dtype, layers=None, seed=0):
    kx, kw = jax.random.split(jax.random.key(seed))
    lead = (len(sizes),) if layers is None else (layers, len(sizes))
    xs = jax.random.normal(kx, (rows, K), dtype)
    w = (jax.random.normal(kw, (*lead, K, N), jnp.float32) / np.sqrt(K)).astype(dtype)
    return xs, w, jnp.asarray(sizes, jnp.int32)


def loop_in_float32(xs, w, sizes):
    """Each run's rows against its own matrix, in float32, run by run."""
    xs, w = np.asarray(xs, np.float32), np.asarray(w, np.float32)
    out, start = np.zeros((xs.shape[0], w.shape[-1]), np.float32), 0
    for g, size in enumerate(np.asarray(sizes)):
        out[start:start + size] = xs[start:start + size] @ w[g]
        start += size
    return out[:start]


def held_rows_agree(got, xs, w, sizes, dtype):
    """Only the held rows are compared: a row past the last run is never
    written by the kernel and never read by its caller."""
    held = int(np.asarray(sizes).sum())
    got = np.asarray(got[:held], np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, loop_in_float32(xs, w, sizes), **TOLERANCE[dtype])
    ragged = np.asarray(jax.lax.ragged_dot(xs, w, sizes)[:held], np.float32)
    np.testing.assert_allclose(got, ragged, **TOLERANCE[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("tk", [256, 128], ids=["K-whole", "K-tiled"])
@pytest.mark.parametrize("case", list(RUNS))
def test_the_kernel_equals_ragged_dot_and_a_loop_over_the_runs(case, tk, dtype):
    rows, sizes = RUNS[case]
    xs, w, sizes = operands(rows, sizes, 256, 256, dtype)
    got = gmm.grouped_matmul(xs, w, sizes, tiling=(TM, tk, 128), interpret=True)
    assert got.shape == (rows, 256) and got.dtype == dtype
    held_rows_agree(got, xs, w, sizes, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layer", [0, 1, 3], ids=["first", "middle", "last"])
def test_a_layer_of_the_stack_is_met_where_it_lies(layer, dtype):
    rows, sizes = RUNS["empty runs between full ones"]
    xs, w, sizes = operands(rows, sizes, 128, 256, dtype, layers=4, seed=layer)
    got = jax.jit(lambda xs, w, sizes, layer: gmm.grouped_matmul(
        xs, w, sizes, layer, tiling=(TM, 128, 128), interpret=True))(
            xs, w, sizes, jnp.int32(layer))
    held_rows_agree(got, xs, w[layer], sizes, dtype)


@pytest.mark.parametrize("K,N", RATIOS, ids=[f"{k}x{n}" for k, n in RATIOS])
def test_the_cells_ratios_of_k_to_n_at_the_tiles_the_kernel_picks(K, N):
    """``tiling`` left out: ROW_TILE rows and ``tiles``' (tk, tn)."""
    sizes = [100, 0, 31, 150, 60]
    xs, w, sizes = operands(384, sizes, K, N, jnp.bfloat16, seed=K)
    got = gmm.grouped_matmul(xs, w, sizes, interpret=True)
    held_rows_agree(got, xs, w, sizes, jnp.bfloat16)


@pytest.mark.parametrize("case", [c for c in RUNS if c.startswith("a decode step")])
def test_a_decode_steps_runs_at_the_tiles_the_kernel_picks(case):
    """``tiling`` left out, as the decode programs call it: 128-row tiles,
    so dozens of runs share a tile and each visit stores its own rows only."""
    rows, sizes = RUNS[case]
    xs, w, sizes = operands(rows, sizes, 256, 128, jnp.bfloat16, layers=2, seed=rows)
    assert int((sizes > 0).sum()) > 2 * -(-rows // gmm.ROW_TILE)  # runs that share tiles
    got = gmm.grouped_matmul(xs, w, sizes, jnp.int32(1), interpret=True)
    held_rows_agree(got, xs, w[1], sizes, jnp.bfloat16)


def test_no_held_row_is_no_visit():
    xs, w, sizes = operands(32, [0, 0, 0], 128, 128, jnp.float32)
    assert int(gmm._visits(sizes, TM, 2)[3]) == 0
    out = gmm.grouped_matmul(xs, w, sizes, tiling=(TM, 128, 128), interpret=True)
    assert out.shape == (32, 128)


def test_a_k_tile_that_does_not_divide_k_is_refused():
    xs, w, sizes = operands(32, [32], 384, 128, jnp.float32)
    with pytest.raises(ValueError, match="does not divide"):
        gmm.grouped_matmul(xs, w, sizes, tiling=(TM, 256, 128), interpret=True)


@pytest.mark.parametrize("seed", range(6))
def test_the_visits_are_the_pairs_of_run_and_tile_that_hold_rows(seed):
    """The work list against a host count: every (run, tile) pair in which
    the run has a row, once, in run order; none for an empty run or past
    the last one; never more than tiles + runs − 1."""
    rng = np.random.default_rng(seed)
    G, tiles_m = 9, 8
    sizes = rng.integers(0, 2 * TM, G) * rng.integers(0, 2, G)
    sizes = (sizes * min(1.0, tiles_m * TM / max(sizes.sum(), 1))).astype(np.int32)
    group, tile, offsets, n = (np.asarray(a) for a in gmm._visits(
        jnp.asarray(sizes), TM, tiles_m))
    ends = np.cumsum(sizes)
    want = [(g, t) for g in range(G) for t in range(tiles_m)
            if max(ends[g] - sizes[g], t * TM) < min(ends[g], (t + 1) * TM)]
    assert int(n) == len(want) <= tiles_m + G - 1 == len(group)
    assert list(zip(group[:n], tile[:n])) == want
    np.testing.assert_array_equal(offsets, np.concatenate([[0], ends]))


@pytest.mark.parametrize("K,N,itemsize,want", [
    (3584, 1024, 2, (3584, 1024)), (1024, 3584, 2, (1024, 3584)),
    (6144, 2048, 2, (6144, 2048)), (2048, 6144, 2, (2048, 6144)),
    (4096, 2048, 2, (4096, 2048)), (6144, 2048, 4, (6144, 512)),
    (32, 48, 4, (32, 48)), (16384, 4096, 4, (16384, 256)), (65536, 4096, 4, (32768, 128)),
])
def test_the_tiles_take_the_whole_matrix_where_two_of_it_fit(K, N, itemsize, want):
    tk, tn = gmm.tiles(K, N, itemsize)
    assert (tk, tn) == want and K % tk == 0
    assert 2 * tk * tn * itemsize <= gmm._W_BLOCK_BYTES


# --- ops/moe.py: the route by the call's row count, and both routes alike ---


@pytest.fixture
def routes(monkeypatch):
    """``through(mode)``: run under that OMNIA_PALLAS_DECODE; ``calls``
    counts the grouped matmuls that took the kernel."""
    calls = []

    def counted(*args, **kwargs):
        calls.append(args[0].shape[0])
        return gmm.grouped_matmul(*args, **kwargs)

    monkeypatch.setattr(moe, "grouped_matmul", counted)

    def through(mode):
        monkeypatch.setenv("OMNIA_PALLAS_DECODE", mode)
        attn._pallas_decode_mode.cache_clear()

    yield through, calls
    monkeypatch.undo()
    attn._pallas_decode_mode.cache_clear()


def both_routes(routes, fn):
    through, calls = routes
    through("0")
    ragged = fn()
    assert not calls
    through("interpret")
    kernel = fn()
    assert len(calls) == 3 and min(calls) >= moe.GROUPED_MATMUL_MIN_ROWS
    del calls[:]
    return ragged, kernel


ROWS = 300  # tokens: 600 rows a call at k = 2, nearly five times the constant (one row tile)


@pytest.mark.parametrize("ranks", [1, 2, 4])
@pytest.mark.parametrize("scaling", [1.0, 2.5])
@pytest.mark.parametrize("router", list(share.ROUTERS))
def test_both_routes_give_the_uncut_layer(routes, router, ranks, scaling):
    """tests/test_moe_share.py's cut, at a prompt's row count: each route's
    ranks summed are the uncut layer, to the tolerance held there."""
    scoring, bias, _ = share.ROUTERS[router]
    p = share.layer_params(bias=bias)
    h = jax.random.normal(jax.random.key(9), (ROWS, share.D))
    want = share.uncut_reference(h, p, scaling=scaling, router=router)
    totals = [share.shared_expert(h, p), share.shared_expert(h, p)]
    for rank in range(ranks):
        mine, first = share.share_of(p, rank, ranks)
        outs = both_routes(routes, lambda: moe.moe_dropless(
            h, mine, share.K, first_expert=first, routed_scaling_factor=scaling,
            scoring=scoring))
        for i, (out, held, hit) in enumerate(outs):
            totals[i] = totals[i] + out
        assert [int(c) for c in outs[0][1:]] == [int(c) for c in outs[1][1:]]
    for total in totals:
        np.testing.assert_allclose(np.asarray(total), want, atol=2e-5, rtol=1e-4)


def test_both_routes_lose_none_when_every_token_picks_one_expert(routes):
    """One run of 300 rows, longer than two row tiles, beside short ones."""
    p = share.layer_params(seed=2, shared=False)
    h = jnp.abs(jax.random.normal(jax.random.key(4), (ROWS, share.D))) + 0.1
    p["router"] = p["router"].at[:, 3].set(4.0)
    ragged, kernel = both_routes(routes, lambda: moe.moe_dropless(h, p, share.K))
    assert int(kernel[1]) == ROWS * share.K
    np.testing.assert_allclose(np.asarray(kernel[0]), np.asarray(ragged[0]),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_both_routes_meet_a_layer_of_the_stacks_in_place(routes, layer):
    per_layer = [share.layer_params(seed=10 + i, shared=False) for i in range(3)]
    stacks = {k: jnp.stack([p[k] for p in per_layer]) for k in ("wg", "wu", "wd")}
    h = jax.random.normal(jax.random.key(11), (ROWS, share.D))
    mine = per_layer[layer]
    through, _ = routes
    through("0")
    want = moe.moe_dropless(h, mine, share.K)
    for out in both_routes(routes, lambda: moe.moe_dropless(
            h, {"router": mine["router"], **stacks}, share.K, layer=jnp.int32(layer))):
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want[0]), atol=2e-5, rtol=1e-4)
        assert (int(out[1]), int(out[2])) == (int(want[1]), int(want[2]))


@pytest.mark.parametrize("tokens,kernel", [(8, False), (63, False), (64, True), (300, True)])
def test_the_route_is_the_calls_row_count_against_one_constant(routes, tokens, kernel):
    """tokens × k against ``GROUPED_MATMUL_MIN_ROWS``, one row tile: a call
    of a tile or more (a served decode step's, a prompt's) takes the kernel
    with the kernels routed on, a shorter one keeps ``ragged_dot``, and with
    them off no call takes the kernel."""
    through, calls = routes
    assert moe.GROUPED_MATMUL_MIN_ROW_TILES == 1
    assert moe.GROUPED_MATMUL_MIN_ROWS == gmm.ROW_TILE == 128
    p = share.layer_params(shared=False)
    h = jax.random.normal(jax.random.key(1), (tokens, share.D))
    through("interpret")
    moe.moe_dropless(h, p, share.K)
    assert calls == ([tokens * share.K] * 3 if kernel else [])
    del calls[:]
    through("0")
    moe.moe_dropless(h, p, share.K)
    assert not calls
