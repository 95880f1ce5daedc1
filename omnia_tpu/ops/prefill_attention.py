"""Pallas blocked causal attention for a prompt's chunk (T > 1).

The einsum path (ops/attention.py::gqa_attention, models/mla.py::
_expanded_attention) writes the masked score tensor ``[H, T, S]`` to HBM in
float32 and reads it back for the maximum, the exponential, the sum and the
second product, over all S rows of the slot whatever the chunk's position.
This kernel keeps one tile of scores in VMEM and visits no key block that no
query of the tile can see (PERF.md section 6, PR 45):

- the grid is (slot, KV head, query tile, key block). A step multiplies the
  G heads of one KV head's group, ``tq`` query rows each, with one block of
  ``tk`` keys and values; the running maximum, sum and accumulator of every
  (head, row) live in float32 VMEM scratch across a tile's key blocks (a TPU
  grid runs in order on one core) and the tile's output is written at its
  last visited block. Both products take their operands as they lie (the
  cache's type) and accumulate in float32; the probabilities are cast to
  the values' type before the second, as the einsum path casts them.
- the mask is the positions' own: key index ``<= q_positions[b, t]``, a tile
  of positions (lane-broadcast in front, as a tile of segment ids would be)
  against an iota. So the result is the einsum path's for any positions.
- which blocks a tile visits comes from its largest position alone,
  ``max position // tk + 1`` of them (the maximum scalar-prefetched): a step
  past it computes nothing, and its index map names the tile's last visited
  block again, which the pipeline then does not fetch. A fresh chunk does
  the causal half; a piece at offset ``first`` reads ``first + T`` rows of
  the slot, not its capacity. Rows of a visited block past the tile's
  largest position are zeroed in the values (whatever an unwritten row
  holds, 0 × it is then 0).
- keys and values are met where they lie: ``[B, S, Hkv·D]`` (a chunk's own,
  any reshape of ``[B, S, Hkv, D]``) or the whole cache ``[L, B, S, Hkv·D]``
  with ``layer`` in the block index map, so no layer is sliced out in front.
  A KV head's columns are one block of the minor axis: the widths are
  multiples of 128 lanes, the key's and the value's may differ.
- with a ``window`` W (a window layer's chunk, PERF.md section 6, PR 48) a
  query at key row p sees rows ``max(p - W + 1, lowest[b]) … p``, and a tile
  has a *first* visited block beside its last: the block that holds its
  smallest lower bound (from the tile's smallest position and the slot's
  ``lowest``, both scalar-prefetched). The index map and the guard count
  from it, so a tile visits ``⌈(tq + W - 1) / tk⌉ + 1`` blocks at most
  whatever the keys' length, and the grid's last axis is that static number.
  A block wholly inside every query's band takes the unmasked body; rows of
  a visited block before the tile's smallest bound are zeroed in the values
  like those past its largest position. ``window`` 0 is the causal call:
  its program holds no operand, step or compare of the window's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: What a query tile's rows are a multiple of, and what the route
#: (ops/attention.py::prefill_kernel_on) asks a chunk's length and a cache's
#: rows to be a multiple of: every bucket of every served cell is.
QUERY_TILE = 128
_LANES = 128
_NEG_INF = -1e30

# The most rows of a query tile, the most a grid step multiplies with one key
# block over the heads of a group (G · tq), and the rows of a key block: a
# step's fixed costs (the accumulator's rescaling, the maximum's reduction
# across lanes) are a tile's whatever the block, and past 1,024 of either the
# tile of scores spills (PERF.md section 6, PR 45 has the sweeps).
_QUERY_TILE_MOST = 1024
_STEP_ROWS = 4096
_KEY_BLOCK = 1024
# Under a window a tile visits the blocks that hold its tq + window − 1 rows,
# and a narrower block wastes fewer rows at their two ends: 512 is 3–10 %
# faster than 1,024 at windows of 512 and 1,024 and level with it at 128 and
# 256; 256 is 20–70 % slower everywhere (PERF.md section 6, PR 48).
_WINDOW_KEY_BLOCK = 512


def tiles(T: int, S: int, G: int, window: int = 0) -> tuple[int, int]:
    """(tq, tk): query tiles of even size, as few as stay within
    ``_QUERY_TILE_MOST`` and ``_STEP_ROWS``, in whole 128s (the last one is
    short where T is no multiple: 1,152 rows are tiles of 640 and 512); key
    blocks of ``_KEY_BLOCK`` rows (``_WINDOW_KEY_BLOCK`` under a ``window``),
    the last one short likewise (S in whole 128s where that is less)."""
    most = min(_QUERY_TILE_MOST, _STEP_ROWS // G)
    tq = pl.cdiv(pl.cdiv(T, pl.cdiv(T, most)), QUERY_TILE) * QUERY_TILE
    return tq, min(_WINDOW_KEY_BLOCK if window else _KEY_BLOCK, pl.cdiv(S, _LANES) * _LANES)


def _visits(top, tk: int, blocks: int):
    """Key blocks a tile whose largest position is ``top`` visits: those
    that hold a row at or before it (one at least, the cache's at most)."""
    return jnp.clip(top // tk + 1, 1, blocks)


def _band(top, low, lowest, window: int, tk: int, blocks: int, most: int):
    """(bound, first, visits) of a tile under a window: its smallest lower
    bound (that of its smallest position ``low``, no lower than the slot's
    ``lowest``), the block that holds it, and the blocks from there to the
    one that holds its largest position ``top`` (one at least, the grid's
    ``most`` at most)."""
    bound = jnp.maximum(low - (window - 1), lowest)
    first = jnp.clip(bound // tk, 0, blocks - 1)
    return bound, first, jnp.clip(top // tk + 1 - first, 1, most)


def _each_head(G: int, head) -> None:
    """``head(g)`` for the G heads of a group: a loop on the device, its body
    traced and compiled once (unrolled, a group of 8 is 6–10 % faster alone,
    six times the compile and a third of a second of every program's lowering
    at each start, PERF.md section 6, PR 45); a group of one is the call itself."""
    if G == 1:
        head(0)
    else:
        jax.lax.fori_loop(0, G, head, None)


def _lanes_of(g, width: int):
    """Head ``g``'s ``width`` lanes of a block that holds a group's heads side by side."""
    return pl.ds(g * width if isinstance(g, int) else pl.multiple_of(g * width, _LANES), width)


def _kernel(layer_ref, top_ref, low_ref, *refs, G: int, dk: int, dv: int, tk: int,
            tiles_q: int, blocks: int, rows: int, scale: float, window: int, most: int):
    """One grid step a (slot, KV head, query tile, key block). ``refs``:
    with a window first lowest_ref [B] (scalar-prefetched like the three in
    front), then pos_ref [tq, 128] int32 (a row's position in every lane);
    q_ref [tq, G·dk]; k_ref [tk, dk]; v_ref [tk, dv]; out_ref [tq, G·dv];
    m_ref [G, tq, 1], l_ref [G, tq, 128] (a row's sum is its lanes' sum: a
    step adds lane to lane, and the reduction across them waits for the
    tile's last), acc_ref [G, tq, dv], float32 all three. ``rows`` = S: the
    last block may hold fewer, and what lies behind them is masked like any
    row past ``top``. ``most``: the grid's last axis under a window."""
    del layer_ref
    pos_ref, q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref = refs[-8:]
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    top = jnp.minimum(top_ref[b * tiles_q + i], rows - 1)
    if window:
        # The tile's smallest lower bound names its first block; a block is
        # ``whole`` (every query sees all of it) between the tile's largest
        # bound and its smallest position.
        low, lowest = low_ref[b * tiles_q + i], refs[0][b]
        bound, first, visits = _band(top, low, lowest, window, tk, blocks, most)
        at = first + j
        whole = ((at >= (jnp.maximum(top - (window - 1), lowest) + tk - 1) // tk)
                 & (at < (low + 1) // tk))
    else:
        visits = _visits(top, tk, blocks)
        # Blocks every query of the tile sees whole: no mask to compute.
        whole = jnp.clip((low_ref[b * tiles_q + i] + 1) // tk, 0, visits)
        at = j

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(masked: bool):
        k, v = k_ref[...], v_ref[...]
        if masked:
            pos = jnp.minimum(pos_ref[:, :1], rows - 1)               # [tq, 1]
            col = at * tk + jax.lax.broadcasted_iota(jnp.int32, (pos.shape[0], tk), 1)
            seen = col <= pos
            # Rows no query of the tile sees: out of the second product
            # whatever they hold (0 × NaN is NaN).
            row = at * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
            kept = row <= top
            if window:
                seen &= col >= jnp.maximum(pos - (window - 1), lowest)
                kept &= row >= bound
            v = jnp.where(kept, v, jnp.zeros_like(v))
        def head(g, carry=None):
            s = jax.lax.dot_general(
                q_ref[:, _lanes_of(g, dk)], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale          # [tq, tk]
            if masked:
                s = jnp.where(seen, s, _NEG_INF)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[g] = l_ref[g] * alpha + sum(
                p[:, c * _LANES:(c + 1) * _LANES] for c in range(tk // _LANES))
            acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new

        _each_head(G, head)

    if window:
        pl.when(whole & (j < visits))(lambda: block(False))
        pl.when(jnp.logical_not(whole) & (j < visits))(lambda: block(True))
    else:
        pl.when(j < whole)(lambda: block(False))
        pl.when((j >= whole) & (j < visits))(lambda: block(True))

    @pl.when(j == visits - 1)
    def _finish():
        def head(g, carry=None):
            total = l_ref[g].sum(axis=-1, keepdims=True)
            out_ref[:, _lanes_of(g, dv)] = (acc_ref[g] / total).astype(out_ref.dtype)

        _each_head(G, head)


@functools.partial(jax.jit, static_argnames=("kv_heads", "scale", "window", "tiling",
                                             "interpret"))
def prefill_attention(q, k, v, q_positions, layer=None, lowest=None, *, kv_heads: int,
                      scale: float, window: int = 0, tiling=None, interpret: bool = False):
    """q [B, T, H·dk] × keys k [B, S, Hkv·dk] and values v [B, S, Hkv·dv] at
    rows 0 … S − 1 (with ``layer`` an int32 index: [L, B, S, ·], of which
    layer ``layer``) → [B, T, H·dv] in q's type: softmax over the keys whose
    row is at or before ``q_positions`` [B, T] of ``scale`` × q·k, head h with
    KV head ``h // (H // Hkv)``. With ``window`` W > 0 a query at row p sees
    rows ``max(p − W + 1, lowest[b]) … p`` (``lowest`` int32 [B], None for
    0), and a tile's positions must lie within ``tq`` rows of each other (a
    chunk's rising ones do): the grid's last axis is sized from that. dk and
    dv are multiples of 128, the query tile of 8, the key block of 128 (a
    row's sum is kept lane by lane, a block's columns added 128 at a time);
    ``tiling`` (tq, tk) overrides ``tiles``. A last tile or block that T or
    S does not fill reads what lies behind the array: such keys are masked,
    such queries' rows not written."""
    B, T, _ = q.shape
    if k.ndim == 3:
        k, v, layer = k[None], v[None], 0
    S = k.shape[2]
    dk, dv = k.shape[3] // kv_heads, v.shape[3] // kv_heads
    G = q.shape[2] // (kv_heads * dk)
    tq, tk = tiling or tiles(T, S, G, window)
    if tq % 8 or tk % _LANES or dk % _LANES or dv % _LANES:
        raise ValueError(f"prefill_attention: query tile {tq}, key block {tk}, widths {dk}, "
                         f"{dv}: a multiple of 8, and the rest multiples of {_LANES}")
    tiles_q, blocks = pl.cdiv(T, tq), pl.cdiv(S, tk)
    # A short last tile's rows behind T stand at the last real position.
    positions = jnp.pad(q_positions.astype(jnp.int32), ((0, 0), (0, tiles_q * tq - T)),
                        mode="edge")
    by_tile = positions.reshape(B * tiles_q, tq)
    # A tile's largest and smallest position: what it visits, what it masks.
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1), by_tile.max(axis=-1),
                by_tile.min(axis=-1)]
    most = blocks
    if window:
        most = min(blocks, pl.cdiv(tq + window - 1, tk) + 1)
        prefetch.append(jnp.zeros((B,), jnp.int32) if lowest is None
                        else lowest.astype(jnp.int32))

    def q_index(b, h, i, j, *_):
        return (b, i, h)

    def kv_index(b, h, i, j, layer_ref, top_ref, low_ref, *lowest_ref):
        top = jnp.minimum(top_ref[b * tiles_q + i], S - 1)
        if not window:
            return (layer_ref[0], b, jnp.minimum(j, _visits(top, tk, blocks) - 1), h)
        _, first, visits = _band(top, low_ref[b * tiles_q + i], lowest_ref[0][b], window, tk,
                                 blocks, most)
        return (layer_ref[0], b, first + jnp.minimum(j, visits - 1), h)

    itemsize = max(q.dtype.itemsize, k.dtype.itemsize)
    vmem = (2 * (tq * _LANES * 4 + (tq * G + tk) * (dk + dv) * itemsize)  # the blocks, twice
            + G * tq * (2 * _LANES + dv) * 4                            # m, l, acc
            + 4 * tq * tk * 4)                                          # a tile of scores
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, kv_heads, tiles_q, most),
        in_specs=[
            pl.BlockSpec((None, tq, _LANES), lambda b, h, i, j, *_: (b, i, 0)),
            pl.BlockSpec((None, tq, G * dk), q_index),
            pl.BlockSpec((None, None, tk, dk), kv_index),
            pl.BlockSpec((None, None, tk, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((None, tq, G * dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((G, tq, 1), jnp.float32),
            pltpu.VMEM((G, tq, _LANES), jnp.float32),
            pltpu.VMEM((G, tq, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, G=G, dk=dk, dv=dv, tk=tk, tiles_q=tiles_q, blocks=blocks,
                          rows=S, scale=scale, window=window, most=most),
        out_shape=jax.ShapeDtypeStruct((B, T, kv_heads * G * dv), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem + (8 << 20), 32 << 20),
        ),
        interpret=interpret,
        name="prefill_attention",
    )(*prefetch, jnp.broadcast_to(positions[:, :, None], (B, tiles_q * tq, _LANES)), q, k, v)


def latent_prefill_attention(q_nope, q_rope, rows, wkvb, q_positions, *, scale: float,
                             interpret: bool = False):
    """The latent family's expanded attention (models/mla.py::_expanded_attention)
    through the kernel: q_nope [B, T, H, dn], q_rope [B, T, H, dr]; rows [B, S, W],
    a cached row ``[c | k_rope | 0]`` with c R wide; wkvb [R, H·(dn + dv)], a head's
    ``[k_nope | v]`` columns → [B, T, H·dv]. A head's key is ``[k_nope | k_rope | 0]``
    side by side, its width the next multiple of 128 lanes, and the query likewise,
    so that one product gives the score; the rows go through ``wkvb`` in front, all
    S of them."""
    B, T, H, dn = q_nope.shape
    S, dr, R = rows.shape[1], q_rope.shape[-1], wkvb.shape[0]
    pad = -(dn + dr) % _LANES
    wkvb = wkvb.reshape(R, H, -1)
    k_nope = jnp.einsum("bsr,rhd->bshd", rows[..., :R], wkvb[..., :dn])
    v = jnp.einsum("bsr,rhd->bshd", rows[..., :R], wkvb[..., dn:])
    k_rope = jnp.broadcast_to(rows[:, :, None, R:R + dr], (B, S, H, dr))
    k = jnp.concatenate([k_nope, k_rope, jnp.zeros((B, S, H, pad), rows.dtype)], axis=-1)
    q = jnp.concatenate([q_nope, q_rope, jnp.zeros((B, T, H, pad), q_nope.dtype)], axis=-1)
    return prefill_attention(q.reshape(B, T, -1), k.reshape(B, S, -1), v.reshape(B, S, -1),
                             q_positions, kv_heads=H, scale=scale, interpret=interpret)
