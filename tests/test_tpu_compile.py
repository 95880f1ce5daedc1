"""Ask the TPU's compiler, without a TPU.

The chip's compiler is installed here and compiles for a described,
unattached ``v5e:2x2``: it refuses what interpret mode and the CPU backend
let through (misaligned kernel slices, too much VMEM, a Mosaic kernel
inside a GSPMD-partitioned jit). A compile that passes is not a chip run;
these tests guard that the main path still *compiles* at real widths.

The topology is described inside a module-scoped fixture — never at
import — because only one process may load the TPU library and every
xdist worker imports this file. All of these compiles live in this one
file for the same reason. Nothing here starts a child process.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from chip_smoke import collective_lines, kv_rows_moved, result_dims
from omnia_tpu.engine.programs import build_programs
from omnia_tpu.engine.types import MAX_DEVICE_STOP_IDS, EngineConfig
from omnia_tpu.models import get_config, llama
from omnia_tpu.ops import attention as attn
from omnia_tpu.ops import moe
from omnia_tpu.ops import decode_attention as dk
from omnia_tpu.parallel import make_mesh

B, S, PAGE_S = 16, 1024, 64
WIDTHS = {"llama3-1b": (32, 8, 64), "llama3-8b": (32, 8, 128)}  # H, Hkv, D


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4_mesh(topo):
    return make_mesh(dp=1, tp=4, devices=topo.devices)


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def kernel_route_on(monkeypatch):
    """Steer ops/attention.py onto the route a TPU backend resolves to
    (``auto`` reads ``jax.default_backend()``, which is the CPU here)."""
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "1")
    attn._pallas_decode_mode.cache_clear()
    yield
    attn._pallas_decode_mode.cache_clear()


def _is_spec(x):
    return isinstance(x, P)


def _model_operands(cfg, sharding_for, batch=B, seq=S):
    """(params, ck, cv) ShapeDtypeStruct trees for one decode batch of
    batch × seq; ``sharding_for(spec)`` places each leaf."""
    def shapes(make, specs):
        return jax.tree.map(
            lambda spec, x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=sharding_for(spec)
            ),
            specs, jax.eval_shape(make), is_leaf=_is_spec,
        )

    params = shapes(
        lambda: llama.init_params(cfg, jax.random.key(0), jnp.bfloat16),
        llama.param_specs(cfg),
    )
    ck, cv = shapes(
        lambda: llama.init_kv_cache(cfg, batch, seq, dtype=jnp.bfloat16),
        llama.kv_cache_specs(),
    )
    return params, ck, cv


def _slot_vec(dtype, sharding, *tail):
    return jax.ShapeDtypeStruct((B, *tail), dtype, sharding=sharding)


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
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


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


@pytest.mark.parametrize("slots,dtype", [(32, jnp.bfloat16), (1, jnp.bfloat16),
                                         (1, jnp.float32)],
                         ids=["longdoc-batch", "the-check", "the-long-check-in-float32"])
def test_window_decode_kernel_compiles(one_chip, slots, dtype):
    """`decode_window_attention` at K-EXAONE's published widths (64 query
    heads on 8 KV heads of 128, a window of 128 rows in a ring of 128: one
    block a live slot whatever its context), over the four window layers'
    rings at the cell's 32 slots and at the one slot the checks run; the
    full layer's `decode_gqa_attention` beside it at the cell's 8960 rows."""
    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, pos = arr((slots, 64, 128), dtype), arr((slots,), jnp.int32)
    ring = arr((4, slots, 128, 8, 128), dtype)
    compiled = jax.jit(
        lambda q, k, v, pos, layer, live: dk.decode_window_attention(
            q, k, v, pos, layer, live=live, window=128, block_s=128)
    ).lower(q, ring, ring, pos, arr((), jnp.int32), pos).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    whole = arr((1, slots, 8960, 8, 128), dtype)
    compiled = dk.decode_gqa_attention.lower(q, whole, whole, pos, arr((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_one_chip_decode_step_holds_the_mosaic_call(one_chip, kernel_route_on):
    cfg = get_config("llama3-1b")
    params, ck, cv = _model_operands(cfg, lambda _spec: one_chip)
    toks = _slot_vec(jnp.int32, one_chip)

    def step(params, ck, cv, toks, pos):
        logits, ck, cv = llama.forward(
            params, cfg, toks[:, None], pos[:, None], ck, cv, pos
        )
        return jnp.argmax(logits[:, 0], -1), ck, cv

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, ck, cv, toks, toks
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


# The eval-batch cell's shape (benchmark/cells/mistral-7b.eval-batch.json over
# benchmark/configs/mistral-7b.json): Mistral-7B widths, 14 layers, 32 × 2048.
CELL_SLOTS, CELL_SEQ = 32, 2048


def _cell_model():
    from omnia_tpu.models.config import ModelConfig

    return ModelConfig(
        name="mistral-7b", vocab_size=32768, hidden_size=4096, num_layers=14,
        num_heads=32, num_kv_heads=8, head_dim=128, ffn_hidden_size=14336,
        rope_theta=1e6, rms_norm_eps=1e-5, tie_embeddings=False,
        max_seq_len=32768,
    )


def _computation_roots(text: str) -> dict[str, str]:
    """Each HLO computation's ROOT instruction line, by computation name."""
    roots, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"%([\w.\-]+) \(.*\{$", ln)
        if head:
            name = head.group(1)
        elif ln.lstrip().startswith("ROOT ") and name:
            roots[name] = ln.strip()
    return roots


def _sorts_outside_conditionals(text: str) -> list[str]:
    """``sort`` instructions (the sampler's top-k prefix and its full
    sort) that the compiled program would run whatever the batch asks
    for: those in a computation that no ``conditional`` branch reaches.
    Empty means the chip's compiler kept the sampler's gates as real
    conditionals instead of flattening them into selects."""
    comps, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name:
            comps[name].append(ln)
    gated, todo = set(), []
    for body in comps.values():
        for ln in body:
            if " conditional(" in ln:
                m = re.search(r"branch_computations=\{([^}]*)\}", ln)
                todo += re.findall(r"%([\w.\-]+)", m.group(1))
    while todo:
        c = todo.pop()
        if c in gated or c not in comps:
            continue
        gated.add(c)
        for ln in comps[c]:
            todo += re.findall(r"%([\w.\-]+)", ln.split("=", 1)[-1])
    return [
        f"{c}: {ln.strip()[:120]}" for c, body in comps.items()
        if c not in gated for ln in body if re.search(r"\bsort\(", ln)
    ]


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

    params, ck, cv = _model_operands(
        cfg, lambda _spec: one_chip, CELL_SLOTS, CELL_SEQ
    )

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((CELL_SLOTS, *tail), dtype, sharding=one_chip)

    i32, f32 = (lambda *t: vec(jnp.int32, *t)), vec(jnp.float32)
    compiled = build_programs(cfg, ecfg, None).decode_fns[chunk].lower(
        params, ck, cv, i32(), i32(), vec(jnp.bool_), i32(),
        i32(MAX_DEVICE_STOP_IDS), vec(jnp.uint32, 2), f32, f32, i32(),
    ).compile()
    text = compiled.as_text()
    # The sampler's gates are conditionals on the chip too: a greedy
    # batch runs no sort over the vocabulary (PR 33).
    assert " conditional(" in text and re.search(r"\bsort\(", text)
    assert _sorts_outside_conditionals(text) == []
    # One layer body, one Mosaic call in it; a dense model reaches neither
    # route of the experts' grouped matmul.
    assert text.count("tpu_custom_call") == 1
    assert "ragged-dot" not in text and "grouped_matmul" not in text
    # Nothing produces a second cache, a layer of it, or a re-laid-out one:
    # the only instructions as large as a layer of K are the row writes,
    # fusions whose root updates the carried buffer in place.
    layer_elems = CELL_SLOTS * CELL_SEQ * cfg.num_kv_heads * cfg.head_dim
    roots = _computation_roots(text)
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
    params, ck, cv = _model_operands(
        cfg, lambda _spec: one_chip, CELL_SLOTS, CELL_SEQ
    )

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = build_programs(cfg, ecfg, None).prefill_insert.lower(
        params, ck, cv, arg(jnp.int32, 1, T), arg(jnp.int32, 1, T),
        arg(jnp.int32), arg(jnp.int32), arg(jnp.uint32, 2),
        arg(jnp.float32), arg(jnp.float32), arg(jnp.int32),
    ).compile().as_text()
    V = cfg.vocab_size
    assert re.search(rf"f32\[1,{V}\]", text)  # the one row's logits
    assert "ragged-dot" not in text and "grouped_matmul" not in text  # a dense model
    wide = {
        m.group(0) for m in re.finditer(r"\w+\[([\d,]+)\]", text)
        if (dims := [int(d) for d in m.group(1).split(",")])[-1] == V
        and int(np.prod(dims)) == T * V
    }
    assert not wide, wide


# The experts' grouped matmuls (ops/moe.py::_grouped_matmul): every call of
# one row tile (128 rows) or more goes through the Pallas kernel of
# ops/grouped_matmul.py, so a served engine's prompt-side programs (2,048
# rows and more a call) and its decode programs (slots × k: 192–512 rows)
# hold the kernel's Mosaic calls and no ``ragged_dot`` (``ragged-dot-none*``
# custom calls on the chip); only a call shorter than a tile keeps that.
# The three sparse cells whose decode step called ``ragged_dot`` until PR 44,
# at their own sizes (benchmark/cells/, benchmark/configs/): the cell and,
# for the two whose prompt side is most of the device's time, a prompt-side
# program and its tokens a call.
SPARSE_CELLS = {
    "judge-batch": ("xing4-29b-a4b.judge-batch", "prefill_insert", 1536),
    "longdoc-batch": ("k-exaone-236b-a23b.longdoc-batch", "extend_nosample", 1024),
    "reason-batch": ("mistral-small-4.reason-batch", None, None),
}


def _sparse_cell(one_chip, name):
    """(cfg, ecfg, programs, params, cache) of a benchmark cell: the
    program's configs as the harness builds them, shapes on the described
    chip."""
    import os
    import sys

    from omnia_tpu.models import model_module

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import manifest

    cell = manifest.Cell(name)
    cfg, ecfg = cell.model_config(), cell.engine_config()
    model = model_module(cfg)

    def shapes(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make))

    params = shapes(lambda: model.init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16))
    cache = shapes(lambda: model.init_kv_cache(cfg, ecfg.num_slots, ecfg.max_seq,
                                               dtype=jnp.bfloat16))
    return cfg, ecfg, build_programs(cfg, ecfg, None), params, cache


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
    assert len(_grouped_matmul_calls(text)) == 1
    assert re.search(rf"ROOT \S+ = bf16\[{rows},{N}\]", text)
    assert not re.search(rf"bf16\[{Eh},{K},{N}\]\S* (copy|dynamic-slice|fusion)\(", text)


def _grouped_matmul_calls(text: str) -> list[str]:
    return re.findall(r"%grouped_matmul[.\d]* = \S+ custom-call\(", text)


def _ragged_dot_calls(text: str) -> list[str]:
    return re.findall(r"%ragged-dot[\w.\-]* = \S+ custom-call\(", text)


@pytest.mark.parametrize("cell", sorted(c for c, s in SPARSE_CELLS.items() if s[1]))
def test_sparse_cells_prompt_programs_hold_the_grouped_matmul_kernel(
        one_chip, kernel_route_on, cell):
    """``prefill_insert`` at judge-batch's middle bucket (6,144 rows a call)
    and ``extend_nosample`` at longdoc-batch's piece (8,192): the three
    matmuls of each sparse layer body are the kernel's Mosaic calls, no
    ``ragged_dot`` is left, and nothing as large as a layer's experts is
    copied, sliced or re-laid out in front of them: the kernel's operand is
    the scan's own stack (PR 32 measured that copy at 62 % of a decode
    step)."""
    name, program, T = SPARSE_CELLS[cell]
    cfg, ecfg, programs, params, cache = _sparse_cell(one_chip, name)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = arg(jnp.int32)
    tokens = (arg(jnp.int32, 1, T), arg(jnp.int32, 1, T))
    if program == "prefill_insert":
        lowered = programs.prefill_insert.lower(
            params, *cache, *tokens, i32, i32, arg(jnp.uint32, 2),
            arg(jnp.float32), arg(jnp.float32), i32)
    else:
        lowered = programs.extend_nosample.lower(params, *cache, *tokens, i32, i32)
    text = lowered.compile().as_text()
    assert T * cfg.num_experts_per_tok >= moe.GROUPED_MATMUL_MIN_ROWS
    _holds_the_kernel_on_the_scans_own_stack(text, cfg)


def _holds_the_kernel_on_the_scans_own_stack(text, cfg):
    """Three ``grouped_matmul`` Mosaic calls a sparse layer body, no
    ``ragged_dot``, and no copy, slice or re-layout as large as a layer's
    experts in front of them."""
    calls = _grouped_matmul_calls(text)
    assert calls and len(calls) % 3 == 0, calls
    assert _ragged_dot_calls(text) == []
    experts = cfg.experts_held * cfg.hidden_size * cfg.moe_ffn_hidden_size
    widths = {cfg.hidden_size, cfg.moe_ffn_hidden_size}
    for ln in text.splitlines():
        m = re.search(r"= \w+\[[\d,]+\]\S* (copy|copy-start|dynamic-slice|slice|"
                      r"transpose|fusion|convert)\(", ln)
        dims = result_dims(ln) if m else []
        if len(dims) >= 3 and set(dims[-2:]) == widths:
            assert int(np.prod(dims)) < experts, ln.strip()[:200]


@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("cell", sorted(SPARSE_CELLS))
def test_sparse_cells_decode_programs_hold_the_grouped_matmul_kernel(
        one_chip, kernel_route_on, cell, chunk):
    """The one-step and the chunk-of-8 decode programs of the same models
    (192, 256 and 384 rows a call: one row tile and more): the three
    matmuls of each sparse layer body are the kernel's Mosaic calls on the
    scan's own stack, as on the prompt side, and no ``ragged_dot`` is left
    (until PR 44 a step kept it; the step's rooflines read the
    ``moe.experts`` scope, which the kernel keeps)."""
    cfg, ecfg, programs, params, cache = _sparse_cell(one_chip, SPARSE_CELLS[cell][0])
    rows = ecfg.num_slots * cfg.num_experts_per_tok
    assert rows >= moe.GROUPED_MATMUL_MIN_ROWS

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((ecfg.num_slots, *tail), dtype, sharding=one_chip)

    i32, f32 = (lambda *t: vec(jnp.int32, *t)), vec(jnp.float32)
    text = programs.decode_fns[chunk].lower(
        params, *cache, i32(), i32(), vec(jnp.bool_), i32(),
        i32(MAX_DEVICE_STOP_IDS), vec(jnp.uint32, 2), f32, f32, i32(),
    ).compile().as_text()
    _holds_the_kernel_on_the_scans_own_stack(text, cfg)
    assert all(f"bf16[{rows}," in call for call in _grouped_matmul_calls(text))


# The latent family with linear-attention layers (models/mla.py, ops/kda.py): a
# float32 state a slot a layer in the cache, updated in place by a Pallas
# kernel in the decode step, at `kimi-linear-48b-a3b.longdoc-wide`'s sizes.


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


@pytest.mark.parametrize("program", ["decode_chunk", "extend_nosample"])
def test_linear_attention_cell_programs_fit_and_keep_the_state_in_place(
        one_chip, kernel_route_on, program):
    """The one-step decode program and a 1,024-token piece of
    `kimi-linear-48b-a3b.longdoc-wide` at the cell's sizes: the decode step
    holds the state kernel's and the latent kernel's Mosaic calls (a scan
    body a run of layers), the cache's three arrays are aliased through, and
    arguments and temporaries fit the chip; the piece holds neither kernel
    (the chunk-wise rule is plain XLA) and the rule's pairwise decays are a
    chunk's at a time, never a piece's 16 chunks at once."""
    cfg, ecfg, programs, params, cache = _sparse_cell(
        one_chip, "kimi-linear-48b-a3b.longdoc-wide")
    assert [c.shape for c in cache] == [(2, 64, 9216, 640), (6, 64, 32, 128, 128),
                                        (6, 64, 3, 12288)]

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def vec(dtype, *tail):
        return arg(dtype, ecfg.num_slots, *tail)

    if program == "decode_chunk":
        lowered = programs.decode_fns[1].lower(
            params, *cache, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), vec(jnp.int32),
            vec(jnp.int32, MAX_DEVICE_STOP_IDS), vec(jnp.uint32, 2), vec(jnp.float32),
            vec(jnp.float32), vec(jnp.int32))
    else:
        lowered = programs.extend_nosample.lower(
            params, *cache, arg(jnp.int32, 1, 1024), arg(jnp.int32, 1, 1024), arg(jnp.int32),
            arg(jnp.int32))
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize for c in cache)
    assert memory.alias_size_in_bytes >= cache_bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 13e9
    state_calls = re.findall(r"%decode_kda_state[.\d]* = \(.*\) custom-call\(", text)
    latent_calls = re.findall(r"%decode_mla_attention[.\d]* = \S+ custom-call\(", text)
    if program == "decode_chunk":
        # K | K K | M | K K K | M: three runs of linear-attention layers, two latent
        assert (len(state_calls), len(latent_calls)) == (3, 2)
        assert memory.temp_size_in_bytes < 0.5e9
    else:
        assert not state_calls and not latent_calls
        chunk = 32 * 64 * 64 * 128                       # heads x C x C x dk, one chunk
        pairwise = [int(np.prod(dims)) for ln in text.splitlines()
                    if (dims := result_dims(ln)) and dims[-3:] == [64, 64, 128]]
        assert pairwise and max(pairwise) <= 2 * chunk   # [q | k] rows against k


# The blocked prefill attention (ops/prefill_attention.py) at the three cells
# whose device time is mostly prompts: cell, prompt-side program, its tokens a
# call, the rows of keys they meet, heads, KV heads, key and value lanes a head.
BLOCKED_CELLS = {
    "judge-batch": ("xing4-29b-a4b.judge-batch", "prefill_insert", 2048, 2048, 32, 32, 256, 128),
    "longdoc-batch": ("k-exaone-236b-a23b.longdoc-batch", "extend_nosample", 1024, 8960,
                      64, 8, 128, 128),
    "longdoc-wide": ("kimi-linear-48b-a3b.longdoc-wide", "extend_nosample", 1024, 9216,
                     32, 32, 256, 128),
}


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


@pytest.mark.parametrize("cell", sorted(BLOCKED_CELLS))
def test_claimed_cells_prompt_programs_hold_no_score_tensor(one_chip, kernel_route_on,
                                                            monkeypatch, cell):
    """``prefill_insert`` at judge-batch's largest bucket and ``extend_nosample``
    at the two long-document cells' piece, as the harness builds them: the
    attention of every full / latent layer body is the kernel's Mosaic call, and
    no instruction has a result of heads × queries × rows elements in float32,
    or in any type with the queries and the rows among its dimensions (the
    masked scores, their exponentials, the probabilities): with the route off
    the same program holds them in float32, and its temporaries are larger by
    about their size."""
    name, program, T, S, H, *_ = BLOCKED_CELLS[cell]
    cfg, ecfg, _, params, cache = _sparse_cell(one_chip, name)
    assert S == (T if program == "prefill_insert" else ecfg.max_seq) and H == cfg.num_heads

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = arg(jnp.int32)
    tokens = (arg(jnp.int32, 1, T), arg(jnp.int32, 1, T))

    def compiled():
        programs = build_programs(cfg, ecfg, None)
        if program == "prefill_insert":
            return programs.prefill_insert.lower(
                params, *cache, *tokens, i32, i32, arg(jnp.uint32, 2),
                arg(jnp.float32), arg(jnp.float32), i32).compile()
        return programs.extend_nosample.lower(params, *cache, *tokens, i32, i32).compile()

    def scores(text):
        """Results of heads × queries × rows elements that are float32, or
        have the queries and the rows among their dimensions."""
        return {what[:40] for ln in text.splitlines()
                if (dims := result_dims(ln)) and int(np.prod(dims)) == H * T * S
                and ((what := ln.split(" = ")[1].lstrip("(")).startswith("f32[")
                     or sorted(d for d in dims if d in (T, S)) == sorted((T, S)))}

    blocked = compiled()
    text = blocked.as_text()
    assert attn.prefill_kernel_on(T, S, 128)
    assert re.search(r"%prefill_attention[.\d]* = \S+ custom-call\(", text)
    assert not scores(text), scores(text)
    if cell != "judge-batch":
        return  # one compile of the einsums is enough to show what the check finds
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "0")
    attn._pallas_decode_mode.cache_clear()
    einsum = compiled()
    assert any(found.startswith("f32[") for found in scores(einsum.as_text()))
    saved = (einsum.memory_analysis().temp_size_in_bytes
             - blocked.memory_analysis().temp_size_in_bytes)
    assert saved > 0.5 * H * T * S * 4, saved


def test_tp4_engine_decode_chunk_compiles_sharded(tp4_mesh, kernel_route_on):
    """The engine's real decode program (the scan of decode_chunk steps)
    on a dp=1 × tp=4 mesh of described devices, operands sharded by the
    repo's own specs. Before the shard_map around the kernel this raised
    ``NotImplementedError: Mosaic kernels cannot be automatically
    partitioned``."""
    cfg = get_config("llama3-1b")
    ecfg = EngineConfig(num_slots=B, max_seq=S, tp=4, decode_chunk=8)
    rep = NamedSharding(tp4_mesh, P())
    params, ck, cv = _model_operands(
        cfg, lambda spec: NamedSharding(tp4_mesh, spec)
    )
    i32, f32 = (lambda *t: _slot_vec(jnp.int32, rep, *t)), _slot_vec(
        jnp.float32, rep
    )
    decode = build_programs(cfg, ecfg, tp4_mesh).decode_fns[8]
    compiled = decode.lower(
        params, ck, cv, i32(), i32(), _slot_vec(jnp.bool_, rep), i32(),
        i32(MAX_DEVICE_STOP_IDS), _slot_vec(jnp.uint32, rep, 2), f32, f32,
        i32(),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The sampler's predicates are replicated scalars, so every chip
    # takes the same branch; the vocabulary-sharded sort and its
    # collectives live inside the branches.
    assert " conditional(" in text and re.search(r"\bsort\(", text)
    assert _sorts_outside_conditionals(text) == []
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
        for x in jax.tree.leaves((params, ck, cv))
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
    import dataclasses

    from omnia_tpu.engine.family import prefill_blocked

    cfg, T = dataclasses.replace(get_config("llama3-8b"), num_layers=2), 256
    rep = NamedSharding(tp4_mesh, P())

    def lowered(ecfg, mesh, sharding_for, rep):
        def arg(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

        params, ck, cv = _model_operands(cfg, sharding_for)
        return build_programs(cfg, ecfg, mesh).prefill_insert.lower(
            params, ck, cv, arg(jnp.int32, 1, T), arg(jnp.int32, 1, T),
            arg(jnp.int32), arg(jnp.int32), arg(jnp.uint32, 2),
            arg(jnp.float32), arg(jnp.float32), arg(jnp.int32))

    kinds = dict(num_slots=B, max_seq=S, max_sessions=0, prefill_buckets=(T,))
    ecfg = EngineConfig(tp=4, **kinds)
    assert not prefill_blocked(cfg, ecfg, tp4_mesh, T, fresh=True)
    assert not prefill_blocked(cfg, ecfg, tp4_mesh, T, fresh=False)
    text = lowered(ecfg, tp4_mesh, lambda spec: NamedSharding(tp4_mesh, spec),
                   rep).compile().as_text()
    assert "prefill_attention" not in text and "tpu_custom_call" not in text
    assert len(collective_lines(text).get("all-reduce", [])) >= 2

    ecfg = EngineConfig(**kinds)
    one = SingleDeviceSharding(tp4_mesh.devices.flat[0])
    assert prefill_blocked(cfg, ecfg, None, T, fresh=True)
    assert "prefill_attention" in lowered(ecfg, None, lambda _spec: one, one).as_text()
