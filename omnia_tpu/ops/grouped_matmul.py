"""Pallas grouped matmul for the experts' runs of rows, from one row tile up.

Rows of ``xs`` [M, K] lie in runs, run ``g`` of ``sizes[g]`` rows, and each
run meets its own matrix ``w[g]`` [K, N]: what ``jax.lax.ragged_dot``
computes, and what ops/moe.py's dropless expert layer asks three times a
sparse layer, in a prompt's programs and in a decode step's. At a prompt's
rows (thousands, 30–100 an expert) ``ragged_dot`` takes three times the
floor of the hit experts' bytes and this kernel 1.3–1.7 times it; at a
decode step's (192–512, a few an expert) 1.4–2 times against the kernel's
1.15–1.2 (PERF.md section 6, PR 42 and PR 44):

- the work is a list made on the device, as the decode kernels' is
  (ops/decode_attention.py): ``_visits`` turns ``sizes`` into the pairs
  (run, ROW_TILE-row tile) in which the run has rows, in run order,
  scalar-prefetched. The grid is (column tiles, pairs, K tiles), and a
  pair's index maps fetch that tile of ``xs`` and that run's ``[tk, tn]``
  tile of ``w``. An empty run has no pair; rows past the last run are in
  no pair's tile (or are masked in the last one), so they cost nothing
  and their output rows are whatever the buffer held.
- a tile of rows that two runs share is visited once for each, one after
  the other: the output block stays in VMEM between them and each visit
  stores only the rows of its own run (an iota mask against the run's
  first and last row).
- with ``tk == K`` (what ``tiles`` picks whenever the block fits) the
  pairs of one run follow each other with the same block of ``w``, which
  the pipeline then does not fetch again: each hit run's matrix is read
  once, whatever number of row tiles the run spans (with K tiled, as
  ``jax.experimental.pallas.ops.tpu.megablox`` does it, once a pair:
  a third slower at the cells' shapes), and ``xs`` once a column tile.
- the matrices are met in the stack where they lie: ``w`` may be
  ``[L, G, K, N]`` with ``layer`` an index that goes into the block index
  map, so no layer's experts are sliced out in front of the kernel.
- both operands go to the MXU as they lie, the accumulator is float32, the
  result has the operands' type: ``ragged_dot``'s arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Rows of ``xs`` in one tile: the MXU's side. A larger tile spans fewer
#: (run, tile) pairs but multiplies more rows of other runs in each.
ROW_TILE = 128

# What the two buffers of a block of ``w`` may take of VMEM (128 MiB on a
# v5e, of which the compiler's default scope is 16; the kernel asks for
# what its blocks need). 56 MiB holds a whole [K, N] matrix twice at every
# served width (50 MB at 6144 × 2048 in bfloat16).
_W_BLOCK_BYTES = 56 << 20
_COLUMN_TILES = (512, 256, 128)


def tiles(K: int, N: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn) for ``w[g]`` [K, N]: the whole matrix if two of it fit
    ``_W_BLOCK_BYTES`` (one contiguous fetch an expert, ``xs`` read once:
    2–16 % faster than 512 columns at the cells' widths, PERF.md section
    6, PR 42), else the widest column tile dividing N with all of K that
    does, else the narrowest with the largest multiple of 128 dividing K
    that does."""
    columns = [N] + [t for t in _COLUMN_TILES if t < N and N % t == 0]
    for tn in columns:
        if 2 * K * tn * itemsize <= _W_BLOCK_BYTES:
            return K, tn
    tn = columns[-1]
    fits = [t for t in range(K - K % 128, 0, -128)
            if K % t == 0 and 2 * t * tn * itemsize <= _W_BLOCK_BYTES]
    return (fits[0] if fits else K), tn


def _visits(sizes, tm: int, tiles_m: int):
    """(group int32 [V], tile int32 [V], offsets int32 [G + 1], n int32
    []): the (run, row tile) pairs in which a run has rows, in run order,
    the runs' first rows, and how many pairs there are. V = tiles_m + G − 1
    bounds them (every run after the first can share one tile with its
    predecessor); entries past ``n`` are never visited."""
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(spans, dtype=jnp.int32)
    v = jnp.arange(tiles_m + G - 1, dtype=jnp.int32)
    # Pair v belongs to the run after those whose pairs end at or before it.
    group = jnp.minimum(
        jnp.sum(v[:, None] >= visit_end[None, :], axis=1, dtype=jnp.int32), G - 1)
    tile = first[group] + v - (visit_end - spans)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # Entries past n stay inside the arrays: a block index is an address.
    return group, jnp.clip(tile, 0, tiles_m - 1), offsets, visit_end[-1]


def _gmm_kernel(layer_ref, group_ref, tile_ref, offsets_ref, x_ref, w_ref,
                out_ref, *acc, tm: int, tiles_k: int):
    """One grid step a (column tile, pair, K tile). x_ref [tm, tk]; w_ref
    [tk, tn]; out_ref [tm, tn]; ``acc`` is one float32 [tm, tn] scratch
    when K is tiled, else nothing."""
    del layer_ref
    v, k_i = pl.program_id(1), pl.program_id(2)
    part = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    def store(total):
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, total.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        out_ref[...] = jnp.where(mine, total.astype(out_ref.dtype), out_ref[...])

    if tiles_k == 1:
        store(part)
        return
    (acc_ref,) = acc

    @pl.when(k_i == 0)
    def _first():
        acc_ref[...] = part

    @pl.when(k_i > 0)
    def _rest():
        acc_ref[...] += part

    @pl.when(k_i == tiles_k - 1)
    def _last():
        store(acc_ref[...])


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def grouped_matmul(xs, w, sizes, layer=None, *, tiling=None, interpret: bool = False):
    """xs [M, K] in runs of ``sizes`` int32 [G] × ``w`` [G, K, N] (with
    ``layer`` an int32 index: [L, G, K, N], of which layer ``layer``) →
    [M, N] in the operands' type. Row ``r`` of run ``g`` is ``xs[r] @
    w[g]`` accumulated in float32; a row past the last run is unwritten.
    ``tiling`` (tm, tk, tn) overrides ``ROW_TILE`` and ``tiles``: tk must
    divide K, and on the chip tm is a multiple of 16 and tk, tn of 128."""
    M, K = xs.shape
    N = w.shape[-1]
    if w.ndim == 3:
        w, layer = w[None], 0
    tm, tk, tn = tiling or (ROW_TILE, *tiles(K, N, w.dtype.itemsize))
    if K % tk:
        raise ValueError(f"grouped_matmul: tile {tk} does not divide K = {K}")
    tiles_m, tiles_k = pl.cdiv(M, tm), K // tk
    group, tile, offsets, n_visits = _visits(sizes.astype(jnp.int32), tm, tiles_m)
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1), group, tile, offsets]

    def x_index(n_i, v, k_i, layer_ref, group_ref, tile_ref, offsets_ref):
        return (tile_ref[v], k_i)

    def w_index(n_i, v, k_i, layer_ref, group_ref, tile_ref, offsets_ref):
        return (layer_ref[0], group_ref[v], k_i, n_i)

    def out_index(n_i, v, k_i, layer_ref, group_ref, tile_ref, offsets_ref):
        return (tile_ref[v], n_i)

    itemsize = max(xs.dtype.itemsize, w.dtype.itemsize)
    vmem = 2 * (tm * tk + tk * tn + tm * tn) * itemsize + 3 * tm * tn * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(pl.cdiv(N, tn), n_visits, tiles_k),
        in_specs=[
            pl.BlockSpec((tm, tk), x_index),
            pl.BlockSpec((None, None, tk, tn), w_index),
        ],
        out_specs=pl.BlockSpec((tm, tn), out_index),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] if tiles_k > 1 else [],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((M, N), xs.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem + (8 << 20), 32 << 20),
        ),
        interpret=interpret,
        name="grouped_matmul",
    )(*prefetch, xs, w)
