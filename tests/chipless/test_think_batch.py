"""`olmo-hybrid-7b.think-batch`'s programs as the harness builds them,
compiled for the described chip at the real size (benchmark/README.md's third
rehearsal), one compile a program: the decode program, the three fresh
prefills and the three extend pieces each fit the chip beside the engine's
weights and cache (K and V of two full layers at 64 slots x 3,072 rows, six
layers' float32 states of 30 x 96 x 192 a slot held two heads side by side,
15 x 96 x 384, so that the chip's 128-lane tiles hold nothing but state, their
tails), the decode
program holds the state kernel and the full layers' kernel at a group of one
query head, and the prompt side holds the blocked attention kernel and no
kernel for the chunk-wise rule. And the state kernel alone at the published
widths: Mosaic takes blocks of five packed heads whose sublanes (96) are no
whole 128, in place, and the state operand is the states' own bytes."""

import re

import jax
import jax.numpy as jnp
import pytest

from omnia_tpu.models import stacks

CELL = "olmo-hybrid-7b.think-batch"
BUCKETS = (256, 512, 1024)
CHIP_BYTES = 16e9


@pytest.mark.parametrize("program,size", [("decode", 8)]
                         + [("prefill_insert", b) for b in BUCKETS]
                         + [("extend_nosample", b) for b in BUCKETS])
def test_the_cells_programs_fit_the_chip_at_the_real_size(cell_programs, kernel_route_on,
                                                          program, size):
    """Arguments (4.87 GB of weights, the cache, the step's operands) +
    temporaries under 16 GB, and the engine more than 70 % of the chip (the
    issue reckons 74 %; the driver's floor is a quarter)."""
    cfg, ecfg, params, cache = cell_programs.cell(CELL)
    assert (ecfg.num_slots, ecfg.max_seq, ecfg.prefill_buckets) == (64, 3072, BUCKETS)
    assert [tuple(c.shape) for c in cache] == [
        (2, 64, 3072, 32, 128), (2, 64, 3072, 32, 128), (6, 64, 15, 96, 384), (6, 64, 3, 11520)]
    assert cache[2].dtype == jnp.float32
    runs = [(kind, length) for _, kind, _, length, _ in stacks._runs(cfg)]
    assert runs == [("dense_delta", 3), ("dense_full", 1)] * 2
    compiled = cell_programs.compiled(CELL, program, size)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"\n{CELL} {program} {size}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    assert 0.7 * CHIP_BYTES < memory.argument_size_in_bytes and held < CHIP_BYTES, (
        program, size, memory.argument_size_in_bytes, memory.temp_size_in_bytes)
    text = compiled.as_text()

    def calls(kernel):
        return re.findall(rf"%{kernel}[.\d]* = .*? custom-call\(", text)

    if program == "decode":
        assert calls("decode_delta_state") and calls("decode_gqa_attention")
        assert not calls("prefill_attention")
        # The states come in as their own bytes, whole (8, 128) tiles with no
        # lane of padding (a head a row was 4/3 of them), and go out in place.
        state = re.search(r"f32\[6,64,15,96,384\]\{4,3,2,1,0:T\((\d+),(\d+)\)\} parameter\((\d+)\)",
                          text)
        assert state, "no states parameter in whole tiles"
        sublanes, lanes, index = map(int, state.groups())
        assert 96 % sublanes == 0 and 384 % lanes == 0, state.group(0)
        assert re.search(rf"input_output_alias=\{{.*\({index}, \{{\}}, may-alias\)", text)
        assert memory.temp_size_in_bytes < 6 * 64 * 30 * 96 * 192 * 4
    else:
        assert calls("prefill_attention") and not calls("decode_delta_state")
    assert "attn.delta" in text and "delta.conv" in text
    assert ("delta.state" if program == "decode" else "delta.chunk") in text


def test_32_held_heads_keep_the_parents_decode_program(cell_programs, kernel_route_on):
    """The cell's decode program (a chunk of 8 steps) lowers to the text it
    lowered to at f1e7612, before the decode kernels had a second block shape
    (PR 53): 32 held KV heads leave no sublane of a tile empty, `flat_rows`
    says so, and nothing in front of the call or in its arguments moved."""
    from omnia_tpu.engine.programs import build_programs
    from omnia_tpu.ops.decode_attention import flat_rows

    from . import cells

    cfg, ecfg, params, cache = cell_programs.cell(CELL)
    assert not flat_rows(cache[0].shape[3], cfg.head_dim, cache[0].dtype, 256)
    got = cells.program_digest(cells.lower_program(
        build_programs(cfg, ecfg, None), "decode", 8, params, cache, ecfg.num_slots,
        cell_programs.one_chip).as_text())
    assert got == [7339, "7b39056eda5b0cb2"], got


def test_the_state_kernel_compiles_in_place_at_the_published_widths(one_chip):
    """`decode_delta_state` at 30 heads of 96 x 192 float32 held two a row, 64
    slots, six layers: five packed heads a block (the choice the chip made:
    457 us a layer's call against 455 with all fifteen in one block of 2.2 MB,
    `chip_delta_state.py`, PR 51), the whole state goes in and comes out
    aliased, the operands are the states' own bytes and a few MB of step
    vectors (a head a row: 4/3 of the states), and nothing state-sized is
    copied around the call."""
    from omnia_tpu.ops import delta

    L, Bk, H, dk, dv = 6, 64, 30, 96, 192
    p = 2
    assert delta.head_block(H // p, dk, p * dv) == 5

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(state, q, k, v, g, beta, layer, live):
        return delta.decode_delta_state(state, q, k, v, g, beta, layer, live, kernel=True)

    f32 = jnp.float32
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        arg(f32, L, Bk, H // p, dk, p * dv), arg(f32, Bk, H, dk), arg(f32, Bk, H, dk),
        arg(f32, Bk, H, dv), arg(f32, Bk, H), arg(f32, Bk, H), arg(jnp.int32),
        arg(jnp.bool_, Bk)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%decode_delta_state[.\d]* = \(.*\) custom-call\(", text)) == 1
    memory = compiled.memory_analysis()
    state_bytes = L * Bk * H * dk * dv * 4
    assert state_bytes <= memory.argument_size_in_bytes < state_bytes + (8 << 20)
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 8
