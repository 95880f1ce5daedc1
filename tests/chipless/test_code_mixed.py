"""`mellum2-12b-a2p5b.code-mixed`'s programs as the harness builds them,
compiled for the described chip at the real size (benchmark/README.md's third
rehearsal): the decode program, the three fresh prefills and the three extend
pieces each fit the chip beside the engine's weights and cache, hold the
experts' grouped-matmul kernel and both decode kernels, make both rotary
tables once, and on the prompt side hold the blocked attention kernel a run of
attention layers of either kind wherever the window route takes it (every
fresh prefill, and the piece of 1,024 rows, whose band would be 256 MiB of
float32 scores). And the programs of the seven cells that were there before lower
to the text they lowered to without `ModelConfig.rope_full_yarn`."""

import json
import re

import numpy as np
import pytest

from chip_smoke import result_dims
from omnia_tpu.engine.programs import build_programs
from omnia_tpu.models import stacks
from omnia_tpu.ops import attention as attn

from . import cells

CELL = "mellum2-12b-a2p5b.code-mixed"
BUCKETS = (256, 512, 1024)
CHIP_BYTES = 16e9


@pytest.mark.parametrize("program,size", [("decode", 8)]
                         + [("prefill_insert", b) for b in BUCKETS]
                         + [("extend_nosample", b) for b in BUCKETS])
def test_the_cells_programs_fit_the_chip_at_the_real_size(cell_programs, kernel_route_on,
                                                          program, size):
    """Arguments (the weights, the cache of 48 slots x 5888 rows with its six
    1,024-row rings, the step's operands) + temporaries under 16 GB, and the
    engine at least half the chip (the driver's floor is a quarter)."""
    cfg, ecfg, params, cache = cell_programs.cell(CELL)
    assert (ecfg.num_slots, ecfg.max_seq, ecfg.prefill_buckets) == (48, 5888, BUCKETS)
    compiled = cell_programs.compiled(CELL, program, size)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 0.5 * CHIP_BYTES < memory.argument_size_in_bytes and held < CHIP_BYTES, (
        program, size, memory.argument_size_in_bytes, memory.temp_size_in_bytes)
    text = compiled.as_text()
    cells.holds_the_kernel_on_the_scans_own_stack(text, cfg)
    if program == "decode":
        for kernel in ("decode_gqa_attention", "decode_window_attention"):
            assert re.search(rf"%{kernel}[.\d]* = \S+ custom-call\(", text), kernel
        # Both kernels meet K and V with the heads among the rows, [L, 48, rows
        # · 4, 128] (ops/decode_attention.py::flat_rows), and that view is the
        # cache's own bytes: a bitcast in front of each call, no copy as large
        # as a layer's rings anywhere in the chunk, the temporaries what they
        # were with the block [256, 4, 128] (227.5 MB; a copy of the caches a
        # call made them 1.2–1.9 GB while this was being written).
        for L, rows in ((2, 5888), (6, 1024)):
            assert re.search(rf"= bf16\[{L},48,{rows * 4},128\]\S* bitcast\(", text), (L, rows)
        a_layers_rings = 48 * 1024 * 4 * 128
        copies = [ln.strip()[:160] for ln in text.splitlines()
                  if re.search(r"= \w+\[[\d,]+\]\S* copy\(", ln)
                  and (dims := result_dims(ln))[1:2] == [48]   # slots: an array of the cache
                  and int(np.prod(dims)) >= a_layers_rings]
        assert not copies, copies
        assert memory.temp_size_in_bytes < 300e6, memory.temp_size_in_bytes
    else:
        # A full layer's prompt side is the blocked kernel, and a window layer's
        # where its route says so: a call a run of layers (a scan's body), and
        # then no float32 result as large as the band's scores [H, T, Q +
        # window] (2,048 wide at the piece of 1,024). The shorter pieces keep
        # the band, whose scores are 40 and 96 MiB.
        window, fresh = cfg.sliding_window, program == "prefill_insert"
        blocked = attn.window_kernel_on(size, window, cfg.num_heads, cfg.head_dim, fresh)
        assert blocked == (fresh or size == 1024)
        runs = [kind.split("_")[1] for _, kind, *_ in stacks._runs(cfg)]
        assert runs == ["window", "full"] * 2
        # (the compiler may merge two runs' equal bodies into one computation:
        # more calls than the full runs alone could make are the window runs')
        calls = re.findall(r"%prefill_attention[.\d]* = \S+ custom-call\(", text)
        assert (2 < len(calls) <= 4) if blocked else (1 <= len(calls) <= 2), calls
        if blocked:
            wide = (window if size % window == 0 else size) + window
            scores = [ln.strip()[:120] for ln in text.splitlines()
                      if " = f32[" in ln and (dims := result_dims(ln)) and dims[-1] == wide
                      and int(np.prod(dims)) >= cfg.num_heads * size * wide]
            assert not scores, scores
    assert "rope.tables" in text and "attn.rope" in text


# Each older cell's decode program (a chunk of 8 steps) and one prompt-side
# program at its largest size, lowered for the described chip with the kernels
# routed on: the lowered text's lines and a digest of it, as the parent commit
# (35b5e03) gave them; a mismatch prints what this tree gives.
OLDER_CELLS = {
    "mistral-7b.chat-steady": "prefill_insert",
    "mistral-7b.eval-batch": "prefill_insert",
    "mistral-7b.longprompt-steady": "prefill_insert",
    "mistral-small-4.reason-batch": "prefill_insert",
    "xing4-29b-a4b.judge-batch": "prefill_insert",
    "k-exaone-236b-a23b.longdoc-batch": "extend_nosample",
    "kimi-linear-48b-a3b.longdoc-wide": "extend_nosample",
}
PARENT_PROGRAMS = {
    # (the three mistral-7b cells' and longdoc-batch's decode since PR 53: 8 KV
    # heads of 128 take the decode kernels' block [rows · Hkv, D], a reshape and
    # a layout constraint in front of each call; f1e7612 gave [2494,
    # "11623e217650a845"] twice, [1886, "88d4d4ab1a4d70e3"] and [11512,
    # "f866f9795b95fd13"]. Every prompt-side digest is f1e7612's.)
    "mistral-7b.chat-steady": {"decode": [2498, "cf01041649897607"], "prefill_insert": [1239, "39ddc8bf32640128"]},
    "mistral-7b.eval-batch": {"decode": [2498, "cf01041649897607"], "prefill_insert": [1238, "a6e284ff5a7e377f"]},
    "mistral-7b.longprompt-steady": {"decode": [1890, "d510878b1eeef2f0"], "prefill_insert": [1239, "8792f2f464a6f54c"]},
    "mistral-small-4.reason-batch": {"decode": [3534, "07b25d7fe97dc9b8"], "prefill_insert": [1737, "5e61a19ed46983b5"]},
    "xing4-29b-a4b.judge-batch": {"decode": [15215, "dcedcd3c3a11519a"], "prefill_insert": [13422, "a659dcff38151baa"]},
    "k-exaone-236b-a23b.longdoc-batch": {"decode": [11520, "ded0cfe125c6a0ca"], "extend_nosample": [2698, "eb771a4d84f09057"]},
    # its piece since PR 49 (the chunk-wise rule in 16-row blocks: the one
    # program that PR changed; 35b5e03 and f451992 gave [3132, "2b06a5d865166331"])
    "kimi-linear-48b-a3b.longdoc-wide": {"decode": [6101, "8af547ae1889551b"], "extend_nosample": [3350, "562d3125b4cfdbed"]},
}


@pytest.mark.parametrize("name", list(OLDER_CELLS))
def test_an_older_cells_programs_lower_to_the_parents_text(cell_programs, kernel_route_on, name):
    cfg, ecfg, params, cache = cell_programs.cell(name)
    assert getattr(cfg, "rope_full_yarn", None) is None
    programs = build_programs(cfg, ecfg, None)
    prompt_side = OLDER_CELLS[name]
    got = {
        "decode": cells.program_digest(cells.lower_program(
            programs, "decode", 8, params, cache, ecfg.num_slots,
            cell_programs.one_chip).as_text()),
        prompt_side: cells.program_digest(cells.lower_program(
            programs, prompt_side, max(ecfg.prefill_buckets), params, cache, ecfg.num_slots,
            cell_programs.one_chip).as_text()),
    }
    assert got == PARENT_PROGRAMS.get(name), json.dumps({name: got})
