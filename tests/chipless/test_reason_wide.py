"""`jamba2-3b.reason-wide`'s programs as the harness builds them, compiled for
the described chip at the real size (benchmark/README.md's third rehearsal),
one compile a program: the decode program, a fresh prefill and an extend piece
each fit the chip beside the engine's weights and cache (K and V of two
attention layers of ONE KV head at 256 slots x 2,560 rows, 26 layers' float32
states of 16 x 5120 a slot with the channels along the lanes, their tails three
rows side by side), the decode program holds the state kernel in place and the
attention layers' kernel at a group of 20 query heads on their one KV head, and
the prompt side holds the blocked attention kernel and the scan's kernel and no
[T, N, E] temporary of a whole piece. And the two kernels alone at the
published widths: Mosaic takes the state kernel's blocks of eight slots x 16 x
2560, in place, and the scan's of 128 tokens x 1280 channels."""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_smoke import result_dims
from omnia_tpu.models import stacks
from omnia_tpu.ops import mamba

CELL = "jamba2-3b.reason-wide"
CHIP_BYTES = 16e9
LM, SLOTS, N, E = 26, 256, 16, 5120
STATES = LM * SLOTS * N * E * 4          # 2.18 GB


@pytest.mark.parametrize("program,size", [("decode", 8), ("prefill_insert", 1024),
                                          ("extend_nosample", 1024)])
def test_the_cells_programs_fit_the_chip_at_the_real_size(cell_programs, kernel_route_on,
                                                          program, size):
    """Arguments (6.06 GB of weights, the cache, the step's operands) +
    temporaries under 16 GB, and the engine more than half of the chip (the
    issue reckons 57 %; the driver's floor is a quarter)."""
    cfg, ecfg, params, cache = cell_programs.cell(CELL)
    assert (ecfg.num_slots, ecfg.max_seq) == (SLOTS, 2560)
    assert [tuple(c.shape) for c in cache] == [
        (2, SLOTS, 2560, 1, 128), (2, SLOTS, 2560, 1, 128), (LM, SLOTS, N, E),
        (LM, SLOTS, 3 * E)]
    assert cache[2].dtype == jnp.float32 and cfg.tie_embeddings and "lm_head" not in params
    runs = [(kind, length) for _, kind, _, length, _ in stacks._runs(cfg)]
    assert runs == [("dense_mamba", 7), ("dense_full", 1), ("dense_mamba", 13),
                    ("dense_full", 1), ("dense_mamba", 6)]
    compiled = cell_programs.compiled(CELL, program, size)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"\n{CELL} {program} {size}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    assert 0.5 * CHIP_BYTES < memory.argument_size_in_bytes and held < CHIP_BYTES, (
        program, size, memory.argument_size_in_bytes, memory.temp_size_in_bytes)
    text = compiled.as_text()

    def calls(kernel):
        return re.findall(rf"%{kernel}[.\d]* = .*? custom-call\(", text)

    # No copy of a cache array, whatever the program: nothing but a parameter,
    # an element of a tuple or an update in place has a cache array's shape
    # (one KV head's rows are whole tiles in the chip's own layout of [.., S,
    # 1, D], and the kernel is told so: ops/decode_attention.py::_attend; a
    # decode step's convolution reads the tail's rows where they lie), and the
    # temporaries (the sampler's copies of [256, 65536] logits, mostly) are
    # smaller than a quarter of the states.
    shapes = {",".join(map(str, c.shape)) for c in cache}
    copied = [ln.strip()[:160] for ln in text.splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* ([\w\-]+)\(", ln))
              and m.group(1) in shapes and m.group(2) in ("copy", "copy-start", "transpose")]
    assert not copied, copied
    assert memory.temp_size_in_bytes < STATES // 4
    if program == "decode":
        assert calls("decode_mamba_state") and calls("decode_gqa_attention")
        assert not calls("prefill_attention")
        # The states come in as their own bytes, whole (8, 128) tiles with
        # no lane or sublane of padding, and go out in place.
        state = re.search(
            rf"f32\[{LM},{SLOTS},{N},{E}\]\{{3,2,1,0:T\((\d+),(\d+)\)\}} parameter\((\d+)\)", text)
        assert state, "no states parameter in whole tiles"
        sublanes, lanes, index = map(int, state.groups())
        assert N % sublanes == 0 and E % lanes == 0, state.group(0)
        assert re.search(rf"input_output_alias=\{{.*\({index}, \{{\}}, may-alias\)", text)
    else:
        assert calls("prefill_attention") and calls("mamba_scan")
        assert not calls("decode_mamba_state")
        # No [T, N, E] of a whole piece (336 MB in float32): the scan's kernel
        # keeps a block of channels' state in VMEM from token to token.
        whole = [ln.strip()[:160] for ln in text.splitlines()
                 if (dims := result_dims(ln)) and sorted(dims)[-3:] == sorted((size, N, E))]
        assert not whole, whole
        assert memory.temp_size_in_bytes < 1.5e9
    assert "attn.mamba" in text and "mamba.conv" in text and "mamba.gates" in text
    assert ("mamba.state" if program == "decode" else "mamba.scan") in text


def test_the_state_kernel_compiles_in_place_at_the_published_widths(one_chip):
    """`decode_mamba_state` at 16 x 5120 float32 a slot, 256 slots, 26 layers:
    eight slots x 2560 channels a block (1.3 MB), the whole state goes in and
    comes out aliased, the operands are the states' own bytes and some MB of
    step vectors, and nothing state-sized is copied around the call."""
    assert mamba.lane_block(N, E) == 2560 and mamba.kernel_takes(SLOTS, N, E)
    assert mamba.kernel_takes(1, N, E) and not mamba.kernel_takes(12, N, E)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(state, u, dt, b, c, a, d, layer, live):
        return mamba.decode_mamba_state(state, u, dt, b, c, a, d, layer, live, kernel=True)

    f32 = jnp.float32
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        arg(f32, LM, SLOTS, N, E), arg(f32, SLOTS, E), arg(f32, SLOTS, E), arg(f32, SLOTS, N),
        arg(f32, SLOTS, N), arg(f32, N, E), arg(f32, E), arg(jnp.int32),
        arg(jnp.bool_, SLOTS)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%decode_mamba_state[.\d]* = \(.*\) custom-call\(", text)) == 1
    memory = compiled.memory_analysis()
    assert STATES <= memory.argument_size_in_bytes < STATES + (16 << 20)
    assert memory.alias_size_in_bytes >= STATES
    assert memory.temp_size_in_bytes < STATES // 64


@pytest.mark.parametrize("T", [1024, 128])
def test_the_scan_kernel_compiles_at_the_published_widths(one_chip, T):
    """`mamba_scan` over a piece of the largest bucket and over the 128 rows
    `benchmark/harness/correct.py` prefills: four blocks of 1280 channels, blocks
    of 128 tokens, and beside its operands nothing but B and C spread over a
    tile's lanes (8 KB a token each)."""
    assert mamba.scan_takes(T, N, E) and mamba._scan_lanes(E) == 1280

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = mamba.mamba_scan.lower(arg(1, T, E), arg(1, T, E), arg(1, T, N), arg(1, T, N),
                                      arg(N, E), arg(E), arg(1, N, E)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%mamba_scan[.\d]* = \(.*\) custom-call\(", text)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * T * (E + 2 * N * 128) * 4
