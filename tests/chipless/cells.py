"""What the chipless compile files share: the cells' operands as shapes on
the described chip, a module's memo of compiled programs, the readers of
compiled text, and the bodies of the cases that more than one cell runs
(each cell's file parametrises them over its own cells)."""

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from chip_smoke import result_dims
from omnia_tpu.engine.programs import build_programs
from omnia_tpu.engine.types import MAX_DEVICE_STOP_IDS
from omnia_tpu.models import llama, model_module
from omnia_tpu.ops import attention as attn
from omnia_tpu.ops import moe

B, S = 16, 1024


def _is_spec(x):
    return isinstance(x, P)


def model_operands(cfg, sharding_for, batch=B, seq=S):
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


def slot_vec(dtype, sharding, *tail):
    return jax.ShapeDtypeStruct((B, *tail), dtype, sharding=sharding)


def lower_program(programs, program, size, params, cache, slots, sharding):
    """``program`` of an engine's ``programs`` lowered at ``size``: the steps
    a call of ``decode`` (one of ``decode_fns``), the tokens a call of
    ``prefill_insert`` or ``extend_nosample``."""
    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def vec(dtype, *tail):
        return arg(dtype, slots, *tail)

    i32, f32 = arg(jnp.int32), arg(jnp.float32)
    if program == "decode":
        return programs.decode_fns[size].lower(
            params, *cache, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), vec(jnp.int32),
            vec(jnp.int32, MAX_DEVICE_STOP_IDS), vec(jnp.uint32, 2), vec(jnp.float32),
            vec(jnp.float32), vec(jnp.int32))
    tokens = (arg(jnp.int32, 1, size), arg(jnp.int32, 1, size))
    if program == "prefill_insert":
        return programs.prefill_insert.lower(
            params, *cache, *tokens, i32, i32, arg(jnp.uint32, 2), f32, f32, i32)
    assert program == "extend_nosample", program
    return programs.extend_nosample.lower(params, *cache, *tokens, i32, i32)


def program_digest(text: str) -> list:
    """A lowered program's lines, and a digest of the text without the Mosaic
    kernels' serialized bodies: those embed the checkout's path in their
    source locations."""
    text = re.sub(r'(@tpu_custom_call\(.*?backend_config = )"[^"]*"', r"\1<kernel>", text)
    return [len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()[:16]]


class CellPrograms:
    """A benchmark cell's configs as the harness builds them, its operands
    as shapes on the described chip, and its programs compiled for it. Each
    is made once: two cases of a module that read one program (a cell's
    piece of 1,024 tokens, say) share its compile. The kernel route is part
    of a program's key, and an engine's programs are built anew for each
    route, because a jitted function that was traced under one route would
    hand the other its trace."""

    def __init__(self, one_chip):
        self.one_chip = one_chip
        self._cells, self._compiled = {}, {}

    def cell(self, name):
        """(cfg, ecfg, params, cache) of the cell ``name``."""
        if name not in self._cells:
            bench = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), "benchmark")
            if bench not in sys.path:
                sys.path.insert(0, bench)
            from harness import manifest

            cell = manifest.Cell(name)
            cfg, ecfg = cell.model_config(), cell.engine_config()
            model = model_module(cfg)

            def shapes(make):
                return jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=self.one_chip),
                    jax.eval_shape(make))

            params = shapes(lambda: model.init_params(cfg, jax.random.key(0),
                                                      dtype=jnp.bfloat16))
            cache = shapes(lambda: model.init_kv_cache(cfg, ecfg.num_slots, ecfg.max_seq,
                                                       dtype=jnp.bfloat16))
            self._cells[name] = cfg, ecfg, params, cache
        return self._cells[name]

    def compiled(self, name, program, size):
        """The cell's ``program`` at ``size`` (``lower_program``), compiled
        under the kernel route that holds now."""
        key = name, program, size, attn._pallas_decode_mode()
        if key not in self._compiled:
            cfg, ecfg, params, cache = self.cell(name)
            self._compiled[key] = lower_program(
                build_programs(cfg, ecfg, None), program, size, params, cache,
                ecfg.num_slots, self.one_chip).compile()
        return self._compiled[key]


def computation_roots(text: str) -> dict[str, str]:
    """Each HLO computation's ROOT instruction line, by computation name."""
    roots, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"%([\w.\-]+) \(.*\{$", ln)
        if head:
            name = head.group(1)
        elif ln.lstrip().startswith("ROOT ") and name:
            roots[name] = ln.strip()
    return roots


def sorts_outside_conditionals(text: str) -> list[str]:
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


def grouped_matmul_calls(text: str) -> list[str]:
    return re.findall(r"%grouped_matmul[.\d]* = \S+ custom-call\(", text)


def ragged_dot_calls(text: str) -> list[str]:
    return re.findall(r"%ragged-dot[\w.\-]* = \S+ custom-call\(", text)


def holds_the_kernel_on_the_scans_own_stack(text, cfg):
    """Three ``grouped_matmul`` Mosaic calls a sparse layer body, no
    ``ragged_dot``, and no copy, slice or re-layout as large as a layer's
    experts in front of them."""
    calls = grouped_matmul_calls(text)
    assert calls and len(calls) % 3 == 0, calls
    assert ragged_dot_calls(text) == []
    experts = cfg.experts_held * cfg.hidden_size * cfg.moe_ffn_hidden_size
    widths = {cfg.hidden_size, cfg.moe_ffn_hidden_size}
    for ln in text.splitlines():
        m = re.search(r"= \w+\[[\d,]+\]\S* (copy|copy-start|dynamic-slice|slice|"
                      r"transpose|fusion|convert)\(", ln)
        dims = result_dims(ln) if m else []
        if len(dims) >= 3 and set(dims[-2:]) == widths:
            assert int(np.prod(dims)) < experts, ln.strip()[:200]


def sparse_cell_prompt_program_holds_the_grouped_matmul_kernel(cell_programs, cell):
    """``prefill_insert`` at judge-batch's middle bucket (6,144 rows a call)
    and ``extend_nosample`` at longdoc-batch's piece (8,192): the three
    matmuls of each sparse layer body are the kernel's Mosaic calls, no
    ``ragged_dot`` is left, and nothing as large as a layer's experts is
    copied, sliced or re-laid out in front of them: the kernel's operand is
    the scan's own stack (PR 32 measured that copy at 62 % of a decode
    step)."""
    name, program, T = SPARSE_CELLS[cell]
    cfg = cell_programs.cell(name)[0]
    text = cell_programs.compiled(name, program, T).as_text()
    assert T * cfg.num_experts_per_tok >= moe.GROUPED_MATMUL_MIN_ROWS
    holds_the_kernel_on_the_scans_own_stack(text, cfg)


def sparse_cell_decode_program_holds_the_grouped_matmul_kernel(cell_programs, cell, chunk):
    """The one-step and the chunk-of-8 decode programs of the same models
    (192, 256 and 384 rows a call: one row tile and more): the three
    matmuls of each sparse layer body are the kernel's Mosaic calls on the
    scan's own stack, as on the prompt side, and no ``ragged_dot`` is left
    (until PR 44 a step kept it; the step's rooflines read the
    ``moe.experts`` scope, which the kernel keeps)."""
    name = SPARSE_CELLS[cell][0]
    cfg, ecfg, _, _ = cell_programs.cell(name)
    rows = ecfg.num_slots * cfg.num_experts_per_tok
    assert rows >= moe.GROUPED_MATMUL_MIN_ROWS
    text = cell_programs.compiled(name, "decode", chunk).as_text()
    holds_the_kernel_on_the_scans_own_stack(text, cfg)
    assert all(f"bf16[{rows}," in call for call in grouped_matmul_calls(text))


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


def claimed_cell_prompt_program_holds_no_score_tensor(cell_programs, monkeypatch, cell):
    """``prefill_insert`` at judge-batch's largest bucket and ``extend_nosample``
    at the two long-document cells' piece, as the harness builds them: the
    attention of every full / latent layer body is the kernel's Mosaic call, and
    no instruction has a result of heads × queries × rows elements in float32,
    or in any type with the queries and the rows among its dimensions (the
    masked scores, their exponentials, the probabilities): with the route off
    the same program holds them in float32, and its temporaries are larger by
    about their size."""
    name, program, T, S, H, *_ = BLOCKED_CELLS[cell]
    cfg, ecfg, _, _ = cell_programs.cell(name)
    assert S == (T if program == "prefill_insert" else ecfg.max_seq) and H == cfg.num_heads

    def scores(text):
        """Results of heads × queries × rows elements that are float32, or
        have the queries and the rows among their dimensions."""
        return {what[:40] for ln in text.splitlines()
                if (dims := result_dims(ln)) and int(np.prod(dims)) == H * T * S
                and ((what := ln.split(" = ")[1].lstrip("(")).startswith("f32[")
                     or sorted(d for d in dims if d in (T, S)) == sorted((T, S)))}

    blocked = cell_programs.compiled(name, program, T)
    text = blocked.as_text()
    assert attn.prefill_kernel_on(T, S, 128)
    assert re.search(r"%prefill_attention[.\d]* = \S+ custom-call\(", text)
    assert not scores(text), scores(text)
    if cell != "judge-batch":
        return  # one compile of the einsums is enough to show what the check finds
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "0")
    attn._pallas_decode_mode.cache_clear()
    einsum = cell_programs.compiled(name, program, T)
    assert any(found.startswith("f32[") for found in scores(einsum.as_text()))
    saved = (einsum.memory_analysis().temp_size_in_bytes
             - blocked.memory_analysis().temp_size_in_bytes)
    assert saved > 0.5 * H * T * S * 4, saved
