"""`k-exaone-236b-a23b.longdoc-batch`'s programs as the harness builds them,
compiled for the described chip: a piece of a long prompt and the decode
programs hold the experts' grouped-matmul kernel, and the piece's attention
holds no score tensor in its full layer and keeps the band in its window
layers (one compile of the piece serves all three cases)."""

import pytest

from . import cells


@pytest.mark.parametrize("cell", ["longdoc-batch"])
def test_sparse_cells_prompt_programs_hold_the_grouped_matmul_kernel(
        cell_programs, kernel_route_on, cell):
    """``extend_nosample`` at the cell's piece (8,192 rows a call)."""
    cells.sparse_cell_prompt_program_holds_the_grouped_matmul_kernel(cell_programs, cell)


@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("cell", ["longdoc-batch"])
def test_sparse_cells_decode_programs_hold_the_grouped_matmul_kernel(
        cell_programs, kernel_route_on, cell, chunk):
    """The one-step and the chunk-of-8 decode programs."""
    cells.sparse_cell_decode_program_holds_the_grouped_matmul_kernel(cell_programs, cell, chunk)


@pytest.mark.parametrize("cell", ["longdoc-batch"])
def test_claimed_cells_prompt_programs_hold_no_score_tensor(
        cell_programs, kernel_route_on, monkeypatch, cell):
    """``extend_nosample`` at the cell's piece of 1,024 tokens against 8,960
    rows."""
    cells.claimed_cell_prompt_program_holds_no_score_tensor(cell_programs, monkeypatch, cell)


def test_the_window_layers_keep_the_band_at_a_128_row_window(cell_programs, kernel_route_on):
    """The window route keeps the einsum band where its float32 scores are
    small: 64 heads x 1,024 queries x (128 + 128) keys are 64 MiB a layer a
    piece, which never leave the chip's fast memory (0.15 ms against the
    kernel's 0.44 on the chip, PERF.md section 6, PR 48). So the piece holds
    the blocked kernel in its one run of full layers alone, and the cell's
    programs are the parent's (tests/chipless/test_code_mixed.py pins their
    text)."""
    import re

    from omnia_tpu.models import stacks
    from omnia_tpu.ops import attention as attn

    name, program, T, *_ = cells.BLOCKED_CELLS["longdoc-batch"]
    cfg, ecfg, _, _ = cell_programs.cell(name)
    assert (cfg.sliding_window, cfg.num_heads) == (128, 64)
    for bucket in ecfg.prefill_buckets:
        for fresh in (True, False):
            assert not attn.window_kernel_on(bucket, 128, 64, cfg.head_dim, fresh)
    full = [kind for _, kind, *_ in stacks._runs(cfg) if kind.endswith("full")]
    calls = re.findall(r"%prefill_attention[.\d]* = \S+ custom-call\(",
                       cell_programs.compiled(name, program, T).as_text())
    assert len(calls) == len(full) == 1
