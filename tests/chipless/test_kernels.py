"""The Pallas kernels alone, each at the widths and sizes the benchmark's
cells call it with, compiled for the described chip."""

import re

import jax
import jax.numpy as jnp
import pytest

from omnia_tpu.models import get_config, llama
from omnia_tpu.ops import decode_attention as dk

from .cells import (
    BLOCKED_CELLS, B, S, grouped_matmul_calls, model_operands, slot_vec,
)

PAGE_S = 64
# H, Hkv, D: heads of 64 keep the block [rows, Hkv, D]; at 128 lanes eight
# and four KV heads take the block [rows · Hkv, D] (``dk.flat_rows``), bfloat16
# and int8, contiguous and paged.
WIDTHS = {"llama3-1b": (32, 8, 64), "llama3-8b": (32, 8, 128),
          "mellum2-12b-a2p5b": (32, 4, 128)}


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("model", sorted(WIDTHS))
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_decode_kernel_compiles(one_chip, paged, model, kv_int8):
    H, Hkv, D = WIDTHS[model]
    kv_dtype = jnp.int8 if kv_int8 else jnp.bfloat16

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, pos = arr((B, H, D), jnp.bfloat16), arr((B,), jnp.int32)
    layer, layers = arr((), jnp.int32), 4  # the whole cache and which layer
    if paged:
        pages = B * S // PAGE_S
        kv = arr((layers, pages, PAGE_S, Hkv, D), kv_dtype)
        scale = arr((layers, pages, PAGE_S, Hkv), jnp.float32)
        args = (q, kv, kv, arr((B, S // PAGE_S), jnp.int32), pos, layer)
        fn = dk.decode_gqa_attention_paged
    else:
        kv = arr((layers, B, S, Hkv, D), kv_dtype)
        scale = arr((layers, B, S, Hkv), jnp.float32)
        args = (q, kv, kv, pos, layer)
        fn = dk.decode_gqa_attention
    if kv_int8:
        args += (scale, scale)
    assert dk.flat_rows(Hkv, D, kv_dtype, PAGE_S if paged else dk.DEFAULT_BLOCK_S) == (D == 128)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if D == 128:  # the heads among the rows are the cache's own bytes: nothing is copied
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("slots,rows,rank,lanes", [
    (96, 3072, 256, 384), (1, 256, 256, 384), (48, 2304, 512, 640), (1, 256, 512, 640),
], ids=["reason-batch", "the-check", "judge-batch", "the-check-at-640-lanes"])
def test_latent_decode_kernel_compiles(one_chip, slots, rows, rank, lanes):
    """`decode_mla_attention` at the published widths of the two latent
    configurations (32 heads against one latent head): Mistral-Small-4's rows
    of 256 + 64 values padded to 384 lanes, Xing4.0's of 512 + 64 padded to
    640; each cell's slots x rows (2304 rows divide by 256 alone, so that
    cell's blocks are the smallest), and the one slot x 256 rows that
    benchmark/harness/correct.py runs."""
    from omnia_tpu.ops.decode_mla_attention import decode_mla_attention

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, cache, pos, layer, live: decode_mla_attention(
            q, cache, pos, layer, live, rank=rank, scale=0.2)
    ).lower(
        arr((slots, 32, lanes), jnp.bfloat16), arr((5, slots, rows, lanes), jnp.bfloat16),
        arr((slots,), jnp.int32), arr((), jnp.int32), arr((slots,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("slots,dtype,heads,kv_heads,layers,ring,block,rows", [
    (32, jnp.bfloat16, 64, 8, 4, 128, 128, 8960), (1, jnp.bfloat16, 64, 8, 4, 128, 128, 8960),
    (1, jnp.float32, 64, 8, 4, 128, 128, 8960),
    (48, jnp.bfloat16, 32, 4, 6, 1024, 256, 5888), (1, jnp.float32, 32, 4, 6, 1024, 256, 5888),
], ids=["longdoc-batch", "the-check", "the-long-check-in-float32",
        "code-mixed", "code-mixed-the-long-check-in-float32"])
def test_window_decode_kernel_compiles(one_chip, slots, dtype, heads, kv_heads, layers, ring,
                                       block, rows):
    """`decode_window_attention` at K-EXAONE's published widths (64 query
    heads on 8 KV heads of 128, a window of 128 rows in a ring of 128: one
    block a live slot whatever its context), over the four window layers'
    rings at the cell's 32 slots and at the one slot the checks run, and at
    Mellum 2's (32 on 4 heads of 128, six rings of 1,024 rows in four blocks,
    48 slots); the full layers' `decode_gqa_attention` beside it at the
    cell's rows. Eight float32 heads fill a tile and keep the block [rows,
    Hkv, D]; every other case takes [rows · Hkv, D]."""
    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert dk.flat_rows(kv_heads, 128, dtype, block) == (
        (kv_heads, dtype) != (8, jnp.float32))
    q, pos = arr((slots, heads, 128), dtype), arr((slots,), jnp.int32)
    rings = arr((layers, slots, ring, kv_heads, 128), dtype)
    compiled = jax.jit(
        lambda q, k, v, pos, layer, live: dk.decode_window_attention(
            q, k, v, pos, layer, live=live, window=ring, block_s=block)
    ).lower(q, rings, rings, pos, arr((), jnp.int32), pos).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    whole = arr((1, slots, rows, kv_heads, 128), dtype)
    compiled = dk.decode_gqa_attention.lower(q, whole, whole, pos, arr((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_one_chip_decode_step_holds_the_mosaic_call(one_chip, kernel_route_on):
    cfg = get_config("llama3-1b")
    params, ck, cv = model_operands(cfg, lambda _spec: one_chip)
    toks = slot_vec(jnp.int32, one_chip)

    def step(params, ck, cv, toks, pos):
        logits, ck, cv = llama.forward(
            params, cfg, toks[:, None], pos[:, None], ck, cv, pos
        )
        return jnp.argmax(logits[:, 0], -1), ck, cv

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, ck, cv, toks, toks
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


# rows (tokens × k), layers of the cut stack, experts held, d, f: the three
# sparse cells' grouped matmuls at a prompt's rows (chip_grouped_matmul.py).
GROUPED_SHAPES = {
    "judge-batch": (6144, 4, 64, 3584, 1024),
    "longdoc-batch": (8192, 4, 16, 6144, 2048),
    "reason-batch": (4096, 5, 32, 4096, 2048),
}


@pytest.mark.parametrize("matmul", ["gate-up", "down"])
@pytest.mark.parametrize("cell", sorted(GROUPED_SHAPES))
def test_grouped_matmul_kernel_compiles(one_chip, cell, matmul):
    """The kernel alone at each cell's widths, the tiles it picks itself
    (a whole [K, N] matrix a block, twice in VMEM: 15–50 MB, past the
    compiler's default scope): Mosaic takes the blocks, the stack goes in
    whole, and what comes out has the rows' type."""
    from omnia_tpu.ops.grouped_matmul import grouped_matmul, tiles

    rows, L, Eh, d, f = GROUPED_SHAPES[cell]
    K, N = (d, f) if matmul == "gate-up" else (f, d)
    assert tiles(K, N, 2) == (K, N)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(grouped_matmul).lower(
        arg(jnp.bfloat16, rows, K), arg(jnp.bfloat16, L, Eh, K, N),
        arg(jnp.int32, Eh), arg(jnp.int32)).compile()
    text = compiled.as_text()
    assert len(grouped_matmul_calls(text)) == 1
    assert re.search(rf"ROOT \S+ = bf16\[{rows},{N}\]", text)
    assert not re.search(rf"bf16\[{Eh},{K},{N}\]\S* (copy|dynamic-slice|fusion)\(", text)


def test_kda_state_kernel_compiles_in_place(one_chip):
    """`decode_kda_state` at the published widths (32 heads of 128 x 128
    float32, 64 slots, six layers): Mosaic takes the 16-head blocks and the
    in-kernel transposes, the whole state goes in and comes out aliased, and
    nothing state-sized is copied around the call."""
    from omnia_tpu.ops import kda

    L, Bk, H, d = 6, 64, 32, 128

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(state, q, k, v, g, beta, layer, live):
        return kda.decode_kda_state(state, q, k, v, g, beta, layer, live, kernel=True)

    vec = arg(jnp.float32, Bk, H, d)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        arg(jnp.float32, L, Bk, H, d, d), vec, vec, vec, vec, arg(jnp.float32, Bk, H),
        arg(jnp.int32), arg(jnp.bool_, Bk)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%decode_kda_state[.\d]* = \(.*\) custom-call\(", text)) == 1
    memory = compiled.memory_analysis()
    state_bytes = L * Bk * H * d * d * 4
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 8


@pytest.mark.parametrize("cell", sorted(BLOCKED_CELLS))
def test_prefill_attention_kernel_compiles(one_chip, cell):
    """The kernel alone at each cell's shape and the tiles it picks itself: a
    fresh chunk of 2,048 × 32 heads × 192 (256 lanes) / 128, a piece of 1,024
    against layer ``layer`` of 8,960 rows × 64 / 8 heads (a last block of 768
    rows) and against 9,216 rows × 32 heads."""
    from omnia_tpu.ops.prefill_attention import prefill_attention

    _, program, T, S, H, Hkv, dk, dv = BLOCKED_CELLS[cell]

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lead = () if program == "prefill_insert" else (2,)
    layer = () if program == "prefill_insert" else (arg(jnp.int32),)
    text = jax.jit(
        lambda q, k, v, pos, *layer: prefill_attention(
            q, k, v, pos, *layer, kv_heads=Hkv, scale=0.07)
    ).lower(arg(jnp.bfloat16, 1, T, H * dk), arg(jnp.bfloat16, *lead, 1, S, Hkv * dk),
            arg(jnp.bfloat16, *lead, 1, S, Hkv * dv), arg(jnp.int32, 1, T), *layer
            ).compile().as_text()
    assert len(re.findall(r"%prefill_attention[.\d]* = \S+ custom-call\(", text)) == 1
