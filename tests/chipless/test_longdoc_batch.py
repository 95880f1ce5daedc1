"""`k-exaone-236b-a23b.longdoc-batch`'s programs as the harness builds them,
compiled for the described chip: a piece of a long prompt and the decode
programs hold the experts' grouped-matmul kernel, and the piece's attention
holds no score tensor (one compile of the piece serves both cases)."""

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
