"""`kimi-linear-48b-a3b.longdoc-wide`'s and `mistral-small-4.reason-batch`'s
programs as the harness builds them, compiled for the described chip.

The latent family with linear-attention layers (models/mla.py, ops/kda.py): a
float32 state a slot a layer in the cache, updated in place by a Pallas
kernel in the decode step, at `longdoc-wide`'s sizes; one compile of its piece
serves the case of the state and the case of the score tensor.
`reason-batch` has the decode programs alone (its prompt side is not most of
the device's time)."""

import re

import numpy as np
import pytest

from chip_smoke import result_dims

from . import cells


@pytest.mark.parametrize("program", ["decode_chunk", "extend_nosample"])
def test_linear_attention_cell_programs_fit_and_keep_the_state_in_place(
        cell_programs, kernel_route_on, program):
    """The one-step decode program and a 1,024-token piece of
    `kimi-linear-48b-a3b.longdoc-wide` at the cell's sizes: the decode step
    holds the state kernel's and the latent kernel's Mosaic calls (a scan
    body a run of layers), the cache's three arrays are aliased through, and
    arguments and temporaries fit the chip; the piece holds neither kernel
    (the chunk-wise rule is plain XLA), and of that rule no `[64, 64, 128]`
    pairwise tensor and no triangular solve: what is pairwise over dk is a
    chunk's four diagonal 16-row blocks, and the unit-triangular system is
    matmuls."""
    name = "kimi-linear-48b-a3b.longdoc-wide"
    cache = cell_programs.cell(name)[3]
    assert [c.shape for c in cache] == [(2, 64, 9216, 640), (6, 64, 32, 128, 128),
                                        (6, 64, 3, 12288)]
    if program == "decode_chunk":
        compiled = cell_programs.compiled(name, "decode", 1)
    else:
        compiled = cell_programs.compiled(name, "extend_nosample", 1024)
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
        dims = [d for ln in text.splitlines() if (d := result_dims(ln))]
        assert not [d for d in dims if d[-3:] == [64, 64, 128]]
        # pairwise over dk: two equal dims before the 128 lanes. One chunk's
        # four diagonal blocks for its 32 heads, a sum for A and a sum for B.
        pairwise = [d for d in dims if len(d) >= 3 and d[-1] == 128 and d[-2] == d[-3]]
        assert pairwise and {tuple(d[-3:]) for d in pairwise} == {(16, 16, 128)}
        assert max(int(np.prod(d)) for d in pairwise) == 32 * 4 * 16 * 16 * 128
        # The parent's `solve_triangular` was this call, 64 rows one after
        # another. The Mosaic kernels left are the experts' and the latent
        # attention's, and under `kda.chunk` no call but the compiler's own
        # buffers.
        assert "InvertDiagBlocksLowerTriangular" not in text
        assert "triangular_solve" not in text
        kernels = set(re.findall(r"%([a-z_]+)[.\d]* = \S+ custom-call\(.*tpu_custom_call", text))
        assert kernels == {"grouped_matmul", "prefill_attention"}, kernels
        in_rule = {re.search(r'custom_call_target="([^"]+)"', ln).group(1)
                   for ln in text.splitlines() if " custom-call(" in ln and "kda.chunk" in ln}
        assert in_rule <= {"AllocateBuffer", "ConcatBitcast"}, in_rule


@pytest.mark.parametrize("cell", ["longdoc-wide"])
def test_claimed_cells_prompt_programs_hold_no_score_tensor(
        cell_programs, kernel_route_on, monkeypatch, cell):
    """``extend_nosample`` at the cell's piece of 1,024 tokens against 9,216
    rows."""
    cells.claimed_cell_prompt_program_holds_no_score_tensor(cell_programs, monkeypatch, cell)


@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("cell", ["reason-batch"])
def test_sparse_cells_decode_programs_hold_the_grouped_matmul_kernel(
        cell_programs, kernel_route_on, cell, chunk):
    """The one-step and the chunk-of-8 decode programs."""
    cells.sparse_cell_decode_program_holds_the_grouped_matmul_kernel(cell_programs, cell, chunk)
