"""Pallas decode attention over a latent (MLA) cache.

The cached row of a token is ``[c | k_rope | pad]``: the normalised latent
(``kv_lora_rank`` values), the one rotated key head every query head
shares, and zeros up to a multiple of the 128-lane tile. In the absorbed
form a decode step needs nothing else: head ``h`` scores a row with
``q_cat[h] · row`` where ``q_cat[h] = [q_nope[h]·Wkvb_k[h]ᵀ | q_rope[h] |
0]``, and its output in latent space is ``Σ p · c``, the leading
``kv_lora_rank`` lanes of the same rows. So one block of rows is fetched
ONCE and used for both products: all heads against one "KV head".

The work is ops/decode_attention.py's: one grid step for each live
(slot, BLOCK_S-row block) pair, from the same ``_work_list``, the whole
cache ``[L, B, S, W]`` with the layer in the block index map, nothing read
for a dead slot, whose output row stays zero. Both matmuls take the rows
as they lie (bf16) and accumulate in float32; the probabilities are
rounded to the rows' type for the second, as the einsum path does.

Why the row is padded (W = 384 for 256 + 64): Mosaic pads an HBM
operand's minor dimension to its 128-lane tile anyway and refuses a DMA
slice that is no multiple of it (ops/decode_attention.py), so a 320-wide
array would cost the same bytes and hide them. The pad is a sixth of what
the kernel reads; its roofline is counted against the 320 values a row
must hold (benchmark/decode_bytes/mla_moe_bytes.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from omnia_tpu.ops.decode_attention import _NEG_INF, _pair, _work_list

# Rows of a block, largest first. A row here is 768 B where a GQA row is
# 4 KB of K and V, so a 256-row block is a fifth of that kernel's and the
# grid step's fixed cost shows: 96 slots at ≈ 1,550 rows took 428 / 306 /
# 252 µs a call at 256 / 512 / 1024 rows (my chip run, PR 32), though the
# last block of a slot is half empty on average.
BLOCK_ROWS = (1024, 512, 256)


def block_rows(cache_len: int) -> int:
    """Cache rows in one block of the kernel: the largest of BLOCK_ROWS
    that divides the cache, else the whole (short) cache."""
    return next((b for b in BLOCK_ROWS if cache_len % b == 0), cache_len)


def _mla_kernel(layer_ref, positions_ref, work_ref, zeros_ref, q_ref, kv_ref,
                out_ref, m_ref, l_ref, acc_ref, *, block_s: int, num_s: int,
                rank: int, scale: float):
    """One grid step a live (slot, block) pair. q_ref [1, H, W]; kv_ref
    [1, BLOCK_S, W]; out_ref [1, H, rank]; m, l [H, 1] and acc [H, rank]
    are the slot's running softmax state in VMEM scratch."""
    del layer_ref, zeros_ref
    slot, s = _pair(work_ref, pl.program_id(0), num_s)
    pos = positions_ref[slot]

    @pl.when(s == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    rows = kv_ref[0]                                   # [BLOCK_S, W]
    scores = jax.lax.dot_general(                      # [H, BLOCK_S]
        q_ref[0], rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    key_idx = s * block_s + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(key_idx <= pos, scores, _NEG_INF)

    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                    # [H, 1]
    p = jnp.exp(scores - m_new)                        # [H, BLOCK_S]
    pv = jax.lax.dot_general(                          # [H, rank]
        p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[:] = acc_ref[:] * alpha + pv
    l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1, keepdims=True)
    m_ref[:] = m_new

    @pl.when(s == jnp.minimum(pos // block_s, num_s - 1))  # _work_list's last
    def _finish():
        out_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "block_s", "interpret"))
def decode_mla_attention(
    q: jnp.ndarray,          # [B, H, W]: [q_nope·Wkvb_kᵀ | q_rope | 0] a head
    cache: jnp.ndarray,      # [L, B, S, W]: [c | k_rope | 0] a row
    positions: jnp.ndarray,  # int32 [B] — current decode position per slot
    layer: jnp.ndarray,      # int32 [] — the layer of the cache to attend over
    live: jnp.ndarray = None,     # int32/bool [B]; None = every slot live
    *,
    rank: int,               # kv_lora_rank: the lanes that are the values
    scale: float,
    block_s: int = None,     # default: block_rows(S)
    interpret: bool = False,
) -> jnp.ndarray:
    """→ [B, H, rank], each head's attention output in latent space over
    layer ``layer`` of the whole cache. Requires S % block_s == 0 and, on
    the chip, ``rank`` a multiple of 128. A slot whose ``live`` entry is 0
    reads nothing and its output row is zeros."""
    B, H, W = q.shape
    S = cache.shape[2]
    block_s = block_s or block_rows(S)
    if S % block_s != 0:
        raise ValueError(f"cache length {S} not divisible by block {block_s}")
    num_s = S // block_s
    positions = positions.astype(jnp.int32)
    work, n_work = _work_list(positions, live, block_s, num_s)
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1), positions, work]

    def slot_index(w, layer_ref, pos_ref, work_ref):
        return (_pair(work_ref, w, num_s)[0], 0, 0)

    def kv_index(w, layer_ref, pos_ref, work_ref):
        slot, s = _pair(work_ref, w, num_s)
        return (layer_ref[0], slot, s, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_work,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, H, W), slot_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((None, 1, block_s, W), kv_index, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, H, rank), slot_index, memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, block_s=block_s, num_s=num_s, rank=rank,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        grid_spec=grid_spec,
        # The output starts as zeros and the grid writes the rows of the
        # slots it visits: a dead slot's row stays zero.
        input_output_aliases={len(prefetch): 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_mla_attention",
    )(*prefetch, jnp.zeros((B, H, rank), q.dtype), q, cache)
