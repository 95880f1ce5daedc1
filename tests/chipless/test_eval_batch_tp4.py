"""The dense pair family's programs compiled for the described chip:
`mistral-7b.eval-batch`'s decode programs and its fresh prefill on one chip,
and a `tp=4` engine's decode chunk and fresh prefill on the 2x2 mesh."""

import dataclasses
import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from chip_smoke import collective_lines, kv_rows_moved, result_dims
from omnia_tpu.engine.family import prefill_blocked
from omnia_tpu.engine.programs import build_programs
from omnia_tpu.engine.types import EngineConfig
from omnia_tpu.models import get_config
from omnia_tpu.models.config import ModelConfig

from .cells import (
    B, S, computation_roots, grouped_matmul_calls, lower_program, model_operands,
    ragged_dot_calls, sorts_outside_conditionals,
)

# The eval-batch cell's shape (benchmark/cells/mistral-7b.eval-batch.json over
# benchmark/configs/mistral-7b.json): Mistral-7B widths, 14 layers, 32 × 2048.
CELL_SLOTS, CELL_SEQ = 32, 2048


def _cell_model():
    return ModelConfig(
        name="mistral-7b", vocab_size=32768, hidden_size=4096, num_layers=14,
        num_heads=32, num_kv_heads=8, head_dim=128, ffn_hidden_size=14336,
        rope_theta=1e6, rms_norm_eps=1e-5, tie_embeddings=False,
        max_seq_len=32768,
    )


@pytest.mark.parametrize("chunk", [1, 8])
def test_cell_decode_programs_keep_the_cache_in_place(one_chip, kernel_route_on,
                                                      chunk):
    """The engine's one-step and chunk-of-8 decode programs at the
    eval-batch cell's shape: the donated cache is the only copy of the
    cache. Until the cache rode the layer scan as its carry the one-step
    program held 4.3 GB of temporaries (a second whole cache) and every
    layer sliced 2 × 134 MB out of it and wrote them back."""
    cfg = _cell_model()
    ecfg = EngineConfig(
        num_slots=CELL_SLOTS, max_seq=CELL_SEQ, decode_chunk=8,
        decode_pipeline=2, max_sessions=0,
        prefill_buckets=(384, 512, 640, 768, 896, 1024),
    )
    params, *cache = model_operands(
        cfg, lambda _spec: one_chip, CELL_SLOTS, CELL_SEQ
    )
    compiled = lower_program(build_programs(cfg, ecfg, None), "decode", chunk, params,
                             cache, CELL_SLOTS, one_chip).compile()
    text = compiled.as_text()
    # The sampler's gates are conditionals on the chip too: a greedy
    # batch runs no sort over the vocabulary (PR 33).
    assert " conditional(" in text and re.search(r"\bsort\(", text)
    assert sorts_outside_conditionals(text) == []
    # One layer body, one Mosaic call in it; a dense model reaches neither
    # route of the experts' grouped matmul.
    assert text.count("tpu_custom_call") == 1
    # (by its instructions: the text ends in a table of every file a program
    # compiled earlier in this process was traced through, and a worker that
    # held a sparse cell's file before this one has ops/grouped_matmul.py there)
    assert ragged_dot_calls(text) == [] and grouped_matmul_calls(text) == []
    # Nothing produces a second cache, a layer of it, or a re-laid-out one:
    # the only instructions as large as a layer of K are the row writes,
    # fusions whose root updates the carried buffer in place.
    layer_elems = CELL_SLOTS * CELL_SEQ * cfg.num_kv_heads * cfg.head_dim
    roots = computation_roots(text)
    for ln in text.splitlines():
        m = re.search(r"= \w+\[[\d,]+\]\S* (copy|copy-start|dynamic-slice|"
                      r"transpose|fusion)\(", ln)
        dims = result_dims(ln) if m else []
        if not dims or int(np.prod(dims)) < layer_elems or dims[-1] != cfg.head_dim:
            continue
        assert m.group(1) == "fusion", ln.strip()[:200]
        called = re.search(r"calls=%([\w.\-]+)", ln).group(1)
        assert " dynamic-update-slice(" in roots[called], roots[called][:200]
    # What is left of the temporaries: wq / wk / wv re-laid out once a call
    # ahead of the step loop (chunk of 8 only; the parent did the same).
    relaid = sum(
        2 * int(np.prod(result_dims(ln))) for ln in text.splitlines()
        if re.search(r"= bf16\[14,4096,(1024|4096)\]\S* copy\(", ln)
    )
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp - relaid < 0.5e9, (temp, relaid)
    assert temp < 0.5e9 or chunk == 8, temp


def test_cell_prefill_insert_runs_its_head_over_one_row(one_chip):
    """The fresh-prefill program at the eval-batch cell's shape and its
    largest bucket: the chip's compiler holds no value of T rows by V columns.
    Until the row was taken before the head it held the product over every
    prompt row (``bf16[1024,32768]``) and sliced the sampled row out of it:
    the TPU's compiler does not push a dynamic slice through a product."""
    cfg = _cell_model()
    T = 1024
    ecfg = EngineConfig(
        num_slots=CELL_SLOTS, max_seq=CELL_SEQ, max_sessions=0,
        prefill_buckets=(384, 512, 640, 768, 896, T),
    )
    params, *cache = model_operands(
        cfg, lambda _spec: one_chip, CELL_SLOTS, CELL_SEQ
    )
    text = lower_program(build_programs(cfg, ecfg, None), "prefill_insert", T, params,
                         cache, CELL_SLOTS, one_chip).compile().as_text()
    V = cfg.vocab_size
    assert re.search(rf"f32\[1,{V}\]", text)  # the one row's logits
    assert ragged_dot_calls(text) == [] and grouped_matmul_calls(text) == []  # a dense model
    wide = {
        m.group(0) for m in re.finditer(r"\w+\[([\d,]+)\]", text)
        if (dims := [int(d) for d in m.group(1).split(",")])[-1] == V
        and int(np.prod(dims)) == T * V
    }
    assert not wide, wide


def test_tp4_engine_decode_chunk_compiles_sharded(tp4_mesh, kernel_route_on):
    """The engine's real decode program (the scan of decode_chunk steps)
    on a dp=1 × tp=4 mesh of described devices, operands sharded by the
    repo's own specs. Before the shard_map around the kernel this raised
    ``NotImplementedError: Mosaic kernels cannot be automatically
    partitioned``."""
    cfg = get_config("llama3-1b")
    ecfg = EngineConfig(num_slots=B, max_seq=S, tp=4, decode_chunk=8)
    rep = NamedSharding(tp4_mesh, P())
    params, *cache = model_operands(
        cfg, lambda spec: NamedSharding(tp4_mesh, spec)
    )
    compiled = lower_program(build_programs(cfg, ecfg, tp4_mesh), "decode", 8, params,
                             cache, B, rep).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The sampler's predicates are replicated scalars, so every chip
    # takes the same branch; the vocabulary-sharded sort and its
    # collectives live inside the branches.
    assert " conditional(" in text and re.search(r"\bsort\(", text)
    assert sorts_outside_conditionals(text) == []
    # Megatron tensor parallelism: all-reduces after the attention and
    # MLP output projections. The sampler, where a row asks for it, sorts
    # vocab-sharded logits, which brings all-gathers and all-to-alls of
    # [B, V]-sized arrays;
    # nothing may move cache rows ([.., S, Hkv, D]) between chips.
    found = collective_lines(text)
    assert len(found.get("all-reduce", [])) >= 2, sorted(found)
    assert "collective-permute" not in found, sorted(found)
    assert not kv_rows_moved(text, S, cfg.head_dim)
    # Each chip holds a quarter of the weights and the cache.
    mem = compiled.memory_analysis()
    whole = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves((params, cache))
    )
    assert mem.argument_size_in_bytes < 0.3 * whole


def test_tp4_engine_prefill_insert_keeps_the_einsums(tp4_mesh, kernel_route_on):
    """The fresh-prefill program of a tp=4 engine at a bucket of whole tiles
    and heads 128 wide, the route on: `build_programs` hands the model its
    mesh, so the chunk's attention stays the einsums that XLA partitions over
    the heads (the blocked kernel is a Mosaic call, which it cannot: the
    program would not compile, or would gather the heads onto every chip),
    and `prefill_blocked` says so. One chip's program of the same shape holds
    the kernel."""
    cfg, T = dataclasses.replace(get_config("llama3-8b"), num_layers=2), 256
    rep = NamedSharding(tp4_mesh, P())

    def lowered(ecfg, mesh, sharding_for, rep):
        params, *cache = model_operands(cfg, sharding_for)
        return lower_program(build_programs(cfg, ecfg, mesh), "prefill_insert", T, params,
                             cache, B, rep)

    kinds = dict(num_slots=B, max_seq=S, max_sessions=0, prefill_buckets=(T,))
    ecfg = EngineConfig(tp=4, **kinds)
    assert not prefill_blocked(cfg, ecfg, tp4_mesh, T, fresh=True)
    assert not prefill_blocked(cfg, ecfg, tp4_mesh, T, fresh=False)
    text = lowered(ecfg, tp4_mesh, lambda spec: NamedSharding(tp4_mesh, spec),
                   rep).compile().as_text()
    assert not re.search(r"%prefill_attention[.\d]* = ", text) and "tpu_custom_call" not in text
    assert len(collective_lines(text).get("all-reduce", [])) >= 2

    ecfg = EngineConfig(**kinds)
    one = SingleDeviceSharding(tp4_mesh.devices.flat[0])
    assert prefill_blocked(cfg, ecfg, None, T, fresh=True)
    assert "prefill_attention" in lowered(ecfg, None, lambda _spec: one, one).as_text()
