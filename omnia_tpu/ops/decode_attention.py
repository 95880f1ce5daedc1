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
  batch over the KV-head axis (MXU), accumulating in f32.
- ONE body for every edition: the paged edition differs only in the
  index map (block ``s`` of slot ``b`` is pool page ``table[b, s]``), the
  int8 edition only in two extra blocks of row scales.

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
):
    """One grid step a live (slot, block) pair; see the module docstring.
    The editions share every line: ``paged`` only adds the table the
    index maps read, ``quantized`` the two [1, BLOCK_S, Hkv] f32 blocks
    of row scales, ``window`` the mask of a ring (``decode_window_attention``)."""
    del layer_ref
    rest = rest[2:] if paged else rest[1:]  # the table, the aliased zeros
    # q_ref [1, Hkv, G, D]; k_ref, v_ref [1, BLOCK_S, Hkv, D] (bf16, or
    # int8 when quantized).
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
    k = k_ref[0]                               # [BLOCK_S, Hkv, D]
    v = v_ref[0]
    # scores [Hkv, G, BLOCK_S] — batch over the KV-head axis.
    scores = jax.lax.dot_general(
        q,
        jnp.swapaxes(k, 0, 1).astype(jnp.float32),  # [Hkv, BLOCK_S, D]
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale
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
    the contiguous cache as a ring."""
    B, H, D = q.shape
    Hkv = k.shape[3]
    G = H // Hkv
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
            **({"window": window} if window else {}),
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
