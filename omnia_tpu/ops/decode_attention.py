"""Length-aware Pallas decode attention over a slot-contiguous KV cache.

Decode attention is HBM-bandwidth-bound: each step streams the KV cache.
The XLA path (ops/attention.py gqa_attention) always reads all S rows —
a slot at position 500 in an 8192-row cache pays 16× the necessary HBM
traffic. This kernel makes traffic proportional to the ACTUAL context:

- the kernel takes the WHOLE cache ``[L, B, S, Hkv, D]`` and the layer
  index as a scalar-prefetch operand: the kv BlockSpec squeezes the layer
  axis and its index map starts with ``layer``, so the layer scan in
  models/llama.py carries one buffer and never slices a layer out of it.
- grid = (B, S // BLOCK_S); the kv BlockSpec index_map CLAMPS the block
  index to the slot's last needed block (scalar-prefetched positions).
  Pallas skips the DMA when consecutive grid steps map to the same
  block, so rows past the position are never fetched from HBM.
- blocks past the position also skip all compute (`pl.when`).
- within-block causality is an iota mask; the running (m, l, acc)
  flash-attention state lives in VMEM scratch across the S-block loop
  (TPU grids iterate the last axis innermost, sequentially).
- GQA without KV repeat: q reshapes to [Hkv, G, D] and both matmuls
  batch over the KV-head axis (MXU), accumulating in f32.

Used for T==1 (decode) steps on TPU; prefill keeps the XLA path (it is
compute-bound and XLA fuses it well)."""

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
    q_ref,          # VMEM [1, Hkv, G, D]
    k_ref,          # VMEM [1, BLOCK_S, Hkv, D] (bf16, or int8 when quantized)
    v_ref,          # VMEM [1, BLOCK_S, Hkv, D]
    *rest,          # [ks_ref, vs_ref,] out_ref, m_ref, l_ref, acc_ref
    block_s: int,
    scale: float,
    quantized: bool = False,
):
    # int8-KV edition (EngineConfig.kv_quant): two extra VMEM blocks
    # carry the [1, BLOCK_S, Hkv] f32 row scales. The HBM read streams
    # int8 rows (half the bf16 bytes — the whole point of the mode);
    # scales apply to the score/prob matrices, never as a cache upcast.
    if quantized:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = rest
    else:
        out_ref, m_ref, l_ref, acc_ref = rest
    del layer_ref
    b = pl.program_id(0)
    s = pl.program_id(1)
    num_s = pl.num_programs(1)
    pos = positions_ref[b]
    last_needed = pos // block_s

    @pl.when(s == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(s <= last_needed)
    def _block():
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
            # Per-(row, head) k scale factors out of the D contraction.
            scores = scores * jnp.swapaxes(ks_ref[0], 0, 1)[:, None, :]

        key_idx = s * block_s + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, dimension=2
        )
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

    @pl.when(s == num_s - 1)
    def _finish():
        out_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)[:, :, None]
        ).astype(out_ref.dtype)


def _decode_kernel_paged(layer_ref, positions_ref, table_ref, *rest, block_s,
                         scale, quantized=False):
    """Paged edition (EngineConfig.kv_pages): identical online-softmax
    body — the page table acts entirely through the BlockSpec index
    maps, which resolve logical block ``s`` of slot ``b`` to pool page
    ``table[b, s]`` before the DMA. The kernel itself never sees page
    ids, so the math is the contiguous kernel's, block for block."""
    del table_ref  # consumed by the index maps only
    return _decode_kernel(
        layer_ref, positions_ref, *rest, block_s=block_s, scale=scale,
        quantized=quantized,
    )


def _layer_operand(layer) -> jnp.ndarray:
    """The layer index as the [1] int32 array scalar prefetch takes."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


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
    interpret: bool = False,
) -> jnp.ndarray:
    """→ [B, H, D]. Paged-attention decode over layer ``layer`` of the
    whole pool: one kernel block per KV page (``block_s == PAGE_S``),
    gathered from the pool through the scalar-prefetched page table
    and layer index. Blocks past a slot's position re-map
    to its last needed page (DMA dedup) and skip compute, so HBM
    traffic stays proportional to actual context length — and free/dead
    pages are simply never addressed (tests poison them to prove it)."""
    B, H, D = q.shape
    page_s, Hkv = pool_k.shape[2], pool_k.shape[3]
    G = H // Hkv
    num_s = table.shape[1]
    quantized = k_scale is not None
    positions = positions.astype(jnp.int32)
    table = table.astype(jnp.int32)

    def kv_index(b, s, layer_ref, pos_ref, tbl_ref):
        # Clamp to the last needed LOGICAL block, then translate through
        # the page table: repeated steps re-map to the same pool page,
        # which Pallas recognizes as resident and skips the DMA.
        page = tbl_ref[b, jnp.minimum(s, pos_ref[b] // page_s)]
        return (layer_ref[0], page, 0, 0)

    def q_index(b, s, *_):
        return (b, 0, 0, 0)

    kv_spec = pl.BlockSpec(
        (None, 1, page_s, Hkv, D), lambda *a: kv_index(*a) + (0,),
        memory_space=pltpu.VMEM,
    )
    q_spec = pl.BlockSpec((1, Hkv, G, D), q_index, memory_space=pltpu.VMEM)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [_layer_operand(layer), positions, table,
                q.reshape(B, Hkv, G, D), pool_k, pool_v]
    if quantized:
        scale_spec = pl.BlockSpec(
            (None, 1, page_s, Hkv), kv_index, memory_space=pltpu.VMEM,
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, num_s),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel_paged, block_s=page_s, scale=D**-0.5,
            quantized=quantized,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="decode_gqa_attention_paged",
    )(*operands)
    return out.reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_gqa_attention(
    q: jnp.ndarray,          # [B, H, D] (rotary already applied)
    k_cache: jnp.ndarray,    # [L, B, S, Hkv, D] (int8 when scales given)
    v_cache: jnp.ndarray,    # [L, B, S, Hkv, D]
    positions: jnp.ndarray,  # int32 [B] — current decode position per slot
    layer: jnp.ndarray,      # int32 [] — the layer of the cache to attend over
    k_scale: jnp.ndarray = None,  # f32 [L, B, S, Hkv] (int8-KV mode)
    v_scale: jnp.ndarray = None,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jnp.ndarray:
    """→ [B, H, D], attention over layer ``layer`` of the whole cache: only
    that layer's blocks are ever addressed, and nothing is sliced out of
    the cache before the call. Requires S % block_s == 0 (engine sizes
    caches so).

    With k_scale/v_scale the caches are rowwise-int8 (models/kv_quant):
    the kernel streams half the KV bytes from HBM and applies the scales
    in VMEM on the score/prob matrices."""
    B, H, D = q.shape
    S, Hkv = k_cache.shape[2], k_cache.shape[3]
    G = H // Hkv
    if S % block_s != 0:
        raise ValueError(f"cache length {S} not divisible by block {block_s}")
    quantized = k_scale is not None
    num_s = S // block_s
    positions = positions.astype(jnp.int32)

    def kv_index(b, s, layer_ref, pos_ref):
        # Clamp to the last needed block: steps past the position re-map
        # to the same block, which Pallas recognizes as "already resident"
        # and skips the HBM→VMEM DMA.
        return (layer_ref[0], b, jnp.minimum(s, pos_ref[b] // block_s), 0)

    def q_index(b, s, *_):
        return (b, 0, 0, 0)

    kv_spec = pl.BlockSpec(
        (None, 1, block_s, Hkv, D), lambda *a: kv_index(*a) + (0,),
        memory_space=pltpu.VMEM,
    )
    q_spec = pl.BlockSpec((1, Hkv, G, D), q_index, memory_space=pltpu.VMEM)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [_layer_operand(layer), positions, q.reshape(B, Hkv, G, D),
                k_cache, v_cache]
    if quantized:
        scale_spec = pl.BlockSpec(
            (None, 1, block_s, Hkv), kv_index, memory_space=pltpu.VMEM,
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, num_s),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, block_s=block_s, scale=D**-0.5,
            quantized=quantized,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="decode_gqa_attention",
    )(*operands)
    return out.reshape(B, H, D)
