"""`xing4-29b-a4b.judge-batch`'s programs as the harness builds them,
compiled for the described chip: the prompt-side programs and the decode
programs hold the experts' grouped-matmul kernel, and a prompt's attention
holds no score tensor."""

import pytest

from . import cells


@pytest.mark.parametrize("cell", ["judge-batch"])
def test_sparse_cells_prompt_programs_hold_the_grouped_matmul_kernel(
        cell_programs, kernel_route_on, cell):
    """``prefill_insert`` at the cell's middle bucket (6,144 rows a call)."""
    cells.sparse_cell_prompt_program_holds_the_grouped_matmul_kernel(cell_programs, cell)


@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("cell", ["judge-batch"])
def test_sparse_cells_decode_programs_hold_the_grouped_matmul_kernel(
        cell_programs, kernel_route_on, cell, chunk):
    """The one-step and the chunk-of-8 decode programs."""
    cells.sparse_cell_decode_program_holds_the_grouped_matmul_kernel(cell_programs, cell, chunk)


@pytest.mark.parametrize("cell", ["judge-batch"])
def test_claimed_cells_prompt_programs_hold_no_score_tensor(
        cell_programs, kernel_route_on, monkeypatch, cell):
    """``prefill_insert`` at the cell's largest bucket, the route on and, to
    show what the check finds, off."""
    cells.claimed_cell_prompt_program_holds_no_score_tensor(cell_programs, monkeypatch, cell)
