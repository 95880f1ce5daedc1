"""Pallas decode attention whose work follows the live (slot, block) pairs.

Decode attention is HBM-bandwidth-bound: each step streams the KV cache.
The XLA path (ops/attention.py gqa_attention) always reads all S rows —
a slot at position 500 in an 8192-row cache pays 16× the necessary HBM
traffic, and a slot nobody is decoding pays as much as a live one. This
kernel takes one grid step for each BLOCK_S-row block a LIVE slot's
context spans, and none for anything else:

- the kernel takes the WHOLE cache ``[L, B, S, Hkv, D]`` and the layer
  index as a scalar-prefetch operand: the kv BlockSpec squeezes the layer
  axis and its index map starts with ``layer``, so the layer scan in
  models/llama.py carries one buffer and never slices a layer out of it.
- the grid is a WORK LIST, ``grid = (n,)`` with ``n`` known only on the
  device: ``_work_list`` turns ``positions`` and the ``live`` mask into
  the pairs ``(slot, block)`` for ``block ≤ positions[slot] // BLOCK_S``
  of every live slot, in slot order, packed one int32 a pair and
  scalar-prefetched. Grid step ``w`` reads ``work[w]`` in its index maps,
  so the pipeline fetches exactly those blocks, the next pair's (the next
  live slot's first block included) while this one is computed. A block
  past a slot's position, and every block of a dead slot, costs no grid
  step, no DMA and no compute.
- a dead slot's output row is zeros: the output buffer starts as zeros
  (aliased in) and the grid writes only the rows of the slots it visits.
  Whoever marks a slot dead discards its sample (engine/programs.py).
- within-block causality is an iota mask; the running (m, l, acc)
  flash-attention state lives in VMEM scratch across a slot's steps
  (a TPU grid runs in order on one core), reset at the slot's block 0
  and written out at its last block.
- GQA without KV repeat: q reshapes to [Hkv, G, D] and both matmuls
  run a KV head over its G query heads (MXU), accumulating in f32.
- ONE body for every edition: the paged edition differs only in the
  index map (block ``s`` of slot ``b`` is pool page ``table[b, s]``), the
  int8 edition only in two extra blocks of row scales.
- the block of K and of V has one of TWO SHAPES, by ``flat_rows`` (shapes
  and the cache's type, nothing else). ``[BLOCK_S, Hkv, D]``, the cache's
  own axes: the minor two are a tile, so where the KV heads (those a
  device holds) are fewer than a tile's sublanes (16 bfloat16, 32 int8, 8
  float32) every cache row is one padded tile of K and one of V in VMEM,
  and the copy in, the ``swapaxes`` and the cast all work on 16 sublanes
  of which 4 or 8 hold anything: the kernel then costs 5.5 ns a row
  whatever the row holds (PERF.md section 6, PR 53). So there the kernel
  meets the cache as ``[L, B, S · Hkv, D]``, the heads among the rows:
  row s's head h is sublane ``s · Hkv + h``, every tile whole. That is a
  reshape of the operand in ``_attend`` and the SAME BYTES on the chip
  (it holds ``[.., S, 4, 128]`` in tiles of (4, 128) and ``[.., S · 4,
  128]`` in tiles of (8, 128), both row after row), a bitcast in the
  compiled program; ``[.., S, Hkv · D]``, a head's columns a lane block,
  is another order of bytes and cost a copy of the whole cache a call.
  The body takes a head's rows out of the block at a stride of ``Hkv``
  sublanes (``_heads_of_flat_rows``: the chip strides 32-bit words only,
  so two bfloat16 or four int8 heads come in a word and shifts part
  them, which for bfloat16 IS the cast to float32) and scores its G
  queries by a product that contracts D of both operands: no transpose.
  A short unrolled loop over the heads and not one batched product,
  because a batch axis in front wants the heads major, [Hkv, BLOCK_S,
  D], the very relayout the strided loads replace; the heads' [G,
  BLOCK_S] scores are stacked on a leading axis, which moves nothing, so
  the mask, ``m``, ``l``, ``acc`` and the int8 scales are one code for
  both shapes. Heads of 64 lanes, 16 or more bfloat16 heads, an odd
  number of them: the first shape.

Why a list and not a loop inside one grid step a slot (manual DMA out of
``pl.ANY`` into a two-deep VMEM buffer): Mosaic (libtpu 0.0.34) pads an
HBM operand's minor dims to its tile and then refuses a DMA slice that is
not a multiple of it — ``head_dim`` 64, the int8 edition's ``[S, Hkv]``
f32 scale rows and int8 rows of fewer than 4 KV heads (tests/
test_tpu_compile.py has all three shapes). Blocks that the pipeline
fetches through a BlockSpec have no such limit, so the list serves every
edition with one body.

Used for T==1 (decode) steps on TPU; a prompt's chunk (T > 1) has a kernel
of its own, ops/prefill_attention.py."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 256
_NEG_INF = -1e30


def _decode_kernel(
    layer_ref,      # SMEM [1] (scalar prefetch; the index maps consume it)
    positions_ref,  # SMEM [B] (scalar prefetch)
    work_ref,       # SMEM [B * NUM_S] (scalar prefetch): the pairs, see _pair
    *rest,          # [table_ref,] zeros_ref, q_ref, k_ref, v_ref,
                    # [ks_ref, vs_ref,] out_ref, m_ref, l_ref, acc_ref
    block_s: int,
    num_s: int,
    scale: float,
    quantized: bool = False,
    paged: bool = False,
    window: int = 0,
    flat: bool = False,
):
    """One grid step a live (slot, block) pair; see the module docstring.
    The editions share every line but the two products: ``paged`` only adds
    the table the index maps read, ``quantized`` the two [1, BLOCK_S, Hkv]
    f32 blocks of row scales, ``window`` the mask of a ring
    (``decode_window_attention``), ``flat`` the block's shape (``flat_rows``)."""
    del layer_ref
    rest = rest[2:] if paged else rest[1:]  # the table, the aliased zeros
    # q_ref [1, Hkv, G, D]; k_ref, v_ref [1, BLOCK_S, Hkv, D], or [1, 1,
    # BLOCK_S · Hkv, D] when flat (bf16, or int8 when quantized).
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = rest
    else:
        q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref = rest
    slot, s = _pair(work_ref, pl.program_id(0), num_s)
    pos = positions_ref[slot]

    @pl.when(s == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)           # [Hkv, G, D]
    # scores [Hkv, G, BLOCK_S]
    if flat:  # a head at a time: a product a head's [BLOCK_S, D], D against D
        scores = jnp.stack([
            jax.lax.dot_general(q[h], k_h, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for h, k_h in enumerate(_heads_of_flat_rows(k_ref, q.shape[0]))])
    else:  # batch over the KV-head axis
        k, v = k_ref[0], v_ref[0]              # [BLOCK_S, Hkv, D]
        scores = jax.lax.dot_general(
            q,
            jnp.swapaxes(k, 0, 1).astype(jnp.float32),  # [Hkv, BLOCK_S, D]
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
    scores = scores * scale
    if quantized:
        # int8-KV edition (EngineConfig.kv_quant): the HBM read streams
        # int8 rows (half the bf16 bytes — the whole point of the mode);
        # scales apply to the score/prob matrices, never as a cache
        # upcast. The per-(row, head) k scale factors out of the D
        # contraction.
        scores = scores * jnp.swapaxes(ks_ref[0], 0, 1)[:, None, :]

    key_idx = s * block_s + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, dimension=2
    )
    if window:
        # The cache is a ring of num_s * block_s rows (a power of two):
        # row r holds the newest position ≡ r at or before ``pos``, which
        # lies ``back`` rows behind the query; rows that position has not
        # reached yet hold another tenant's and lie "before position 0".
        back = (pos - key_idx) & (num_s * block_s - 1)
        scores = jnp.where((back < window) & (back <= pos), scores, _NEG_INF)
    else:
        scores = jnp.where(key_idx <= pos, scores, _NEG_INF)

    m_prev, l_prev = m_ref[:], l_ref[:]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)             # [Hkv, G]
    p = jnp.exp(scores - m_new[:, :, None])     # [Hkv, G, BLOCK_S]
    if quantized:
        # The v scale varies along the contracted S axis → fold it
        # into p before the pv matmul (p is already f32 in VMEM; the
        # softmax statistics l/m stay scale-free because p here is
        # only the pv operand — l sums the UNscaled p below).
        pv_p = p * jnp.swapaxes(vs_ref[0], 0, 1)[:, None, :]
    else:
        pv_p = p
    # pv [Hkv, G, D]
    if flat:
        pv = jnp.stack([
            jnp.dot(pv_p[h], v_h, preferred_element_type=jnp.float32)
            for h, v_h in enumerate(_heads_of_flat_rows(v_ref, q.shape[0]))])
    else:
        pv = jax.lax.dot_general(
            pv_p,
            jnp.swapaxes(v, 0, 1).astype(jnp.float32),  # [Hkv, BLOCK_S, D]
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
    acc_ref[:] = acc_ref[:] * alpha[:, :, None] + pv
    l_ref[:] = l_prev * alpha + p.sum(axis=-1)
    m_ref[:] = m_new

    @pl.when(s == jnp.minimum(pos // block_s, num_s - 1))  # _work_list's last
    def _finish():
        out_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)[:, :, None]
        ).astype(out_ref.dtype)


def flat_rows(kv_heads: int, head_dim: int, dtype, block_s: int) -> bool:
    """Whether a grid step's K and V rows lie in VMEM as ``[block_s · Hkv, D]``
    (row s's head h at sublane ``s · Hkv + h``: whole tiles) and not as
    ``[block_s, Hkv, D]``, from what the call can see: heads of whole 128-lane
    tiles; fewer KV heads (those a device holds) than the sublanes of a tile
    of the cache's type (8 float32, 16 bfloat16, 32 int8: a row's ``(Hkv, D)``
    is one tile, padded); one head, or whole 32-bit words of heads (two
    bfloat16, four int8: ``_heads_of_flat_rows`` takes a word's heads apart);
    and a block of whole tiles."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.float32, jnp.bfloat16, jnp.int8):
        return False
    sublanes, a_word = 32 // dtype.itemsize, 4 // dtype.itemsize
    return (head_dim % 128 == 0 and kv_heads < sublanes
            and (kv_heads == 1 or kv_heads % a_word == 0)
            and block_s * kv_heads % sublanes == 0)


def _heads_of_flat_rows(ref, kv_heads: int):
    """The block ``ref`` [1, 1, BLOCK_S · Hkv, D] a KV head at a time, in
    float32: Hkv arrays [BLOCK_S, D], head h the sublanes h, h + Hkv, …. The
    chip loads sublanes at a stride in 32-bit words only, and a word of a
    bfloat16 or int8 tile holds two or four consecutive sublanes of one
    lane, which here are consecutive heads of one row: the words of a group
    of heads come at the stride, and shifts take them apart. A bfloat16 is
    the upper half of its float32, so this is the cast, exactly."""
    dtype, a_word = ref.dtype, 4 // ref.dtype.itemsize
    if kv_heads == 1:
        return [ref[0, 0].astype(jnp.float32)]
    words = ref if a_word == 1 else ref.bitcast(jnp.int32)  # [1, 1, BLOCK_S · Hkv / a_word, D]
    groups, heads = kv_heads // a_word, []
    for g in range(groups):
        w = words[0, 0, pl.ds(g, ref.shape[2] // kv_heads, stride=groups), :]
        if dtype == jnp.float32:
            heads.append(w)
        elif dtype == jnp.bfloat16:            # the lower half is the even head
            heads += [jax.lax.bitcast_convert_type(w << 16, jnp.float32),
                      jax.lax.bitcast_convert_type(w & -65536, jnp.float32)]
        else:                                  # int8: byte i, its sign carried up
            heads += [((w << (24 - 8 * i)) >> 24).astype(jnp.float32) for i in range(4)]
    return heads


def _work_list(positions, live, block_s: int, num_s: int):
    """(work int32 [B * num_s], n int32 []): the live (slot, block)
    pairs in slot order, and how many there are. Slot ``b`` contributes
    blocks 0 … positions[b] // block_s, a dead slot none. Pair ``w`` is
    stored as ``slot * num_s + block - w`` (``_pair`` undoes it), which is
    one masked sum over the slots that end before it; entries past ``n``
    are never visited."""
    B = positions.shape[0]
    trips = jnp.minimum(jax.lax.div(positions, block_s) + 1, num_s)
    if live is not None:
        trips = jnp.where(live.astype(bool), trips, 0)
    slots = jnp.arange(B, dtype=jnp.int32)
    # end[b]: pairs of slots 0 … b. Masked sums, not cumsum and gather: B
    # and B * num_s are small and XLA fuses each into one pass.
    end = jnp.sum(jnp.where(slots[None, :] <= slots[:, None], trips[None, :], 0),
                  axis=1, dtype=jnp.int32)
    w = jnp.arange(B * num_s, dtype=jnp.int32)
    # Pair w belongs to the slot after those that end at or before it, as
    # many as `before` counts; its block is w less their pairs.
    before = w[:, None] >= end[None, :]
    work = jnp.sum(jnp.where(before, num_s - trips[None, :], 0), axis=1,
                   dtype=jnp.int32)
    return work, end[-1]


def _pair(work_ref, w, num_s: int):
    """(slot, block) of grid step ``w``."""
    item = work_ref[w] + w
    return item // num_s, item % num_s


def _attend(name, q, k, v, scales, table, positions, live, layer, block_s,
            num_s, interpret, window: int = 0):
    """The one ``pallas_call`` behind every entry point: ``table`` None
    is the contiguous cache [L, B, S, Hkv, D], else the pool
    [L, P, PAGE_S, Hkv, D] with ``block_s == PAGE_S``; ``window`` > 0 reads
    the contiguous cache as a ring. Where ``flat_rows`` holds the kernel
    meets either with the heads among the rows, [L, B, S · Hkv, D]: the same
    bytes in the chip's layout, so the reshape moves nothing (under a mesh it
    is of each device's own heads)."""
    B, H, D = q.shape
    Hkv = k.shape[3]
    G = H // Hkv
    flat = flat_rows(Hkv, D, k.dtype, block_s)
    if flat:
        # Row-major stated, or the compiler is free to lay a cache that no
        # kernel constrains any more slots-minor for its updates, and copies
        # it whole in front of every call (the chipless compile of
        # code-mixed's decode chunk: +1 GB of temporaries).
        # (ONE head: the chip's own layout of [.., S, 1, D] has the rows
        # minor to the head's axis of one, whole tiles of rows, which is
        # [.., S, D] byte for byte; rows-major as stated for more heads it
        # would pad every row to a tile of two and copy the cache to and fro.)
        order = (0, 1, 3, 2, 4) if Hkv == 1 else tuple(range(k.ndim))
        k, v = (with_layout_constraint(x, Layout(major_to_minor=order))
                .reshape(*x.shape[:2], -1, D) for x in (k, v))
    positions = positions.astype(jnp.int32)
    work, n_work = _work_list(positions, live, block_s, num_s)
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1), positions, work]
    if table is not None:
        prefetch.append(table.astype(jnp.int32))

    def slot_index(w, layer_ref, pos_ref, work_ref, *_):
        return (_pair(work_ref, w, num_s)[0], 0, 0, 0)

    def kv_index(w, layer_ref, pos_ref, work_ref, *tbl_ref):
        slot, s = _pair(work_ref, w, num_s)
        if tbl_ref:  # logical block s of the slot → its pool page
            return (layer_ref[0], tbl_ref[0][slot, s], 0, 0)
        return (layer_ref[0], slot, s, 0)

    slot_spec = pl.BlockSpec((1, Hkv, G, D), slot_index, memory_space=pltpu.VMEM)
    if flat:  # (the layer's axis kept: a block that squeezes one has no view in words)
        kv_spec = pl.BlockSpec((1, 1, block_s * Hkv, D), kv_index,
                               memory_space=pltpu.VMEM)
    else:
        kv_spec = pl.BlockSpec(
            (None, 1, block_s, Hkv, D), lambda *a: kv_index(*a) + (0,),
            memory_space=pltpu.VMEM,
        )
    scale_spec = pl.BlockSpec(
        (None, 1, block_s, Hkv), kv_index, memory_space=pltpu.VMEM,
    )
    n_pre = len(prefetch)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(n_work,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), slot_spec, kv_spec,
                  kv_spec] + [scale_spec] * len(scales),
        out_specs=slot_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, block_s=block_s, num_s=num_s, scale=D**-0.5,
            quantized=bool(scales), paged=table is not None,
            # (each named only where it is on: a call that keeps the first
            # shape lowers to the text it lowered to before there was a second)
            **({"window": window} if window else {}),
            **({"flat": True} if flat else {}),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        grid_spec=grid_spec,
        # The output starts as zeros and the grid writes the rows of the
        # slots it visits: a dead slot's row stays zero.
        input_output_aliases={n_pre: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=name,
    )(*prefetch, jnp.zeros((B, Hkv, G, D), q.dtype), q.reshape(B, Hkv, G, D),
      k, v, *scales)
    return out.reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_gqa_attention_paged(
    q: jnp.ndarray,          # [B, H, D] (rotary already applied)
    pool_k: jnp.ndarray,     # [L, P, PAGE_S, Hkv, D] (int8 when scales given)
    pool_v: jnp.ndarray,     # [L, P, PAGE_S, Hkv, D]
    table: jnp.ndarray,      # int32 [B, NP] — per-slot page table
    positions: jnp.ndarray,  # int32 [B] — current decode position per slot
    layer: jnp.ndarray,      # int32 [] — the layer of the pool to attend over
    k_scale: jnp.ndarray = None,  # f32 [L, P, PAGE_S, Hkv] (int8-KV mode)
    v_scale: jnp.ndarray = None,
    live: jnp.ndarray = None,     # int32/bool [B]; None = every slot live
    interpret: bool = False,
) -> jnp.ndarray:
    """→ [B, H, D]. Paged-attention decode over layer ``layer`` of the
    whole pool: one kernel block per KV page (``block_s == PAGE_S``),
    fetched from the pool through the scalar-prefetched page table
    and layer index. Only the pages up to a live slot's position are
    ever addressed, so HBM traffic stays proportional to actual context
    length — and free/dead pages, and every page of a dead slot, are
    simply never read (tests poison them to prove it)."""
    scales = () if k_scale is None else (k_scale, v_scale)
    return _attend("decode_gqa_attention_paged", q, pool_k, pool_v, scales,
                   table, positions, live, layer, pool_k.shape[2],
                   table.shape[1], interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_gqa_attention(
    q: jnp.ndarray,          # [B, H, D] (rotary already applied)
    k_cache: jnp.ndarray,    # [L, B, S, Hkv, D] (int8 when scales given)
    v_cache: jnp.ndarray,    # [L, B, S, Hkv, D]
    positions: jnp.ndarray,  # int32 [B] — current decode position per slot
    layer: jnp.ndarray,      # int32 [] — the layer of the cache to attend over
    k_scale: jnp.ndarray = None,  # f32 [L, B, S, Hkv] (int8-KV mode)
    v_scale: jnp.ndarray = None,
    live: jnp.ndarray = None,     # int32/bool [B]; None = every slot live
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jnp.ndarray:
    """→ [B, H, D], attention over layer ``layer`` of the whole cache: only
    that layer's blocks are ever addressed, and nothing is sliced out of
    the cache before the call. Requires S % block_s == 0 (engine sizes
    caches so). A slot whose ``live`` entry is 0 reads nothing and its
    output row is zeros.

    With k_scale/v_scale the caches are rowwise-int8 (models/kv_quant):
    the kernel streams half the KV bytes from HBM and applies the scales
    in VMEM on the score/prob matrices."""
    S = k_cache.shape[2]
    if S % block_s != 0:
        raise ValueError(f"cache length {S} not divisible by block {block_s}")
    scales = () if k_scale is None else (k_scale, v_scale)
    return _attend("decode_gqa_attention", q, k_cache, v_cache, scales, None,
                   positions, live, layer, block_s, S // block_s, interpret)


@functools.partial(jax.jit, static_argnames=("window", "block_s", "interpret"))
def decode_window_attention(
    q: jnp.ndarray,          # [B, H, D] (rotary already applied)
    k_ring: jnp.ndarray,     # [L, B, R, Hkv, D]
    v_ring: jnp.ndarray,     # [L, B, R, Hkv, D]
    positions: jnp.ndarray,  # int32 [B] — current decode position per slot
    layer: jnp.ndarray,      # int32 [] — the layer of the rings to attend over
    live: jnp.ndarray = None,     # int32/bool [B]; None = every slot live
    window: int = 0,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jnp.ndarray:
    """→ [B, H, D], a window layer's decode attention over layer ``layer``
    of its rings: row ``r`` of a slot holds the newest position ``p ≡ r (mod
    R)`` at or before the slot's ``positions`` entry (its own row included,
    already written), and the query sees the ``window`` positions up to its
    own. The body, the work list and the pipeline are
    ``decode_gqa_attention``'s; what differs is the mask, by the position a
    row holds and not by its index, and that a live slot's blocks are the
    ring's (``min(position // block_s + 1, R // block_s)``: at most R rows
    whatever the context). R is a power of two and a multiple of
    ``block_s``. Neither kernel knows of rotary position: q and the rows
    come rotated or not, as the layer's kind says."""
    R = k_ring.shape[2]
    if R % block_s or R & (R - 1) or not 0 < window <= R:
        raise ValueError(f"ring of {R} rows, block {block_s}, window {window}: R "
                         f"is a power of two, a multiple of the block, and holds the window")
    return _attend("decode_window_attention", q, k_ring, v_ring, (), None,
                   positions, live, layer, block_s, R // block_s, interpret,
                   window=window)
