#!/usr/bin/env python3
"""Microbenchmark: the two decode attention kernels alone, a grid step's K and
V rows as ``[rows, Hkv, D]`` beside ``[rows · Hkv, D]``, at each number of rows
a block can take.

One process on one chip. At the served shapes of four cells (``CASES``: slots,
query and KV heads of 128, layers, rows of a slot's cache or ring, the
contexts a step meets) it times ``ops/decode_attention.py``'s
``decode_window_attention`` and ``decode_gqa_attention``, a call a layer round
and round in one program, K and V donated and carried as the decode program
carries them, each call's queries the last one's plus its output times zero:

- ``layout`` "rows of heads": the block ``[rows, Hkv, D]`` (what every call
  took before ``flat_rows``; at fewer KV heads than a tile has sublanes each
  cache row is one padded tile);
- ``layout`` "heads among the rows": the block ``[rows · Hkv, D]``, where
  ``flat_rows`` allows it (a tree without ``flat_rows`` runs its one layout);

each at 256, 512 and 1,024 rows a block where they divide the cache and a
block of K is at most 2 MiB (a ring shorter than 256: the ring). One JSON line
a case, layout and block: µs a call (one layer, every slot) and a grid step,
ns a fetched row (a block's rows, read or masked), GB/s of the fetched rows'
K + V bytes (``Hkv · D`` values each, whatever the tile holds), and HBM's
floor for them. ``--check``: every case
and layout against the einsum path on the clean cache, with dead slots whose
rows are NaN, NaN in every block a live slot does not reach and 1e9 / -1e9 in
the rows of its last block past its position; and the two layouts' outputs
against each other.

    python chip_decode_attention.py [--check]      # on the chip
    python chip_decode_attention.py --rehearse-cpu # tiny, interpreted, says so

A number of the rehearsal is no measurement. No TPU and no
``--rehearse-cpu`` → exit 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HBM_BYTES_S = 819e9  # TPU v5e (Google Cloud documentation, "TPU v5e")
D = 128

# case: kernel, slots, query heads, KV heads (held), layers, rows of a slot's
# cache (a ring's R; its window is R), the contexts' lowest and highest
# position. Shapes: benchmark/cells/ and benchmark/configs/; contexts: what
# the cells' traffic holds in a decode step (PERF.md section 5).
CASES = {
    "code-mixed.ring": ("window", 48, 32, 4, 6, 1024, (600, 3400)),
    "code-mixed.full": ("full", 48, 32, 4, 2, 5888, (600, 3400)),
    "longdoc-batch.ring": ("window", 32, 64, 8, 4, 128, (4000, 8900)),
    "longdoc-batch.full": ("full", 32, 64, 8, 1, 8960, (4000, 8900)),
    "eval-batch.full": ("full", 32, 32, 8, 4, 2048, (200, 2047)),
    "think-batch.full": ("full", 64, 32, 32, 1, 3072, (1000, 3071)),
}
TINY = {name: (kernel, 3, 2 * kv, kv, 2, 128 if kernel == "window" else 512, (40, 500))
        for name, (kernel, _, _, kv, *_) in CASES.items()}


def operands(case, seed, dtype):
    """q [B, H, D], K and V [L, B, rows, Hkv, D] and the slots' positions."""
    import jax
    import jax.numpy as jnp

    _, B, H, Hkv, L, rows, (lo, hi) = case
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (L, B, rows, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (L, B, rows, Hkv, D), dtype)
    pos = jax.random.randint(ks[3], (B,), lo, hi + 1, jnp.int32)
    return q, k, v, pos


def kernel_call(dk, case, block, interpret):
    """``call(q, k, v, pos, layer, live) → [B, H, D]`` of the case's kernel."""
    kernel, *_, rows, _ = case
    if kernel == "window":
        return lambda q, k, v, pos, layer, live=None: dk.decode_window_attention(
            q, k, v, pos, layer, live=live, window=rows, block_s=block, interpret=interpret)
    return lambda q, k, v, pos, layer, live=None: dk.decode_gqa_attention(
        q, k, v, pos, layer, live=live, block_s=block, interpret=interpret)


def timed(call, q, k, v, pos, layers: int, rounds: int, iters: int):
    """Seconds a call, and K and V back: ``rounds`` rounds over the layers in
    one program, ``iters`` such programs enqueued behind each other, one wait."""
    import jax

    def program(k, v, q):
        def body(i, q):
            return q + call(q, k, v, pos, i % layers) * 0

        return k, v, jax.lax.fori_loop(0, rounds * layers, body, q)

    program = jax.jit(program, donate_argnums=(0, 1))
    k, v, _ = jax.block_until_ready(program(k, v, q))      # compile
    k, v, _ = jax.block_until_ready(program(k, v, q))
    t0 = time.perf_counter()
    for _ in range(iters):
        k, v, out = program(k, v, q)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (iters * rounds * layers), k, v


def checked(attn, call, case, block, q, k, v, pos):
    """The kernel on layer L - 1 of a poisoned cache against the einsum path on
    the clean one → (the check's line, the live slots' outputs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    kernel, B, _, _, L, rows, _ = case
    live = np.arange(B) % 3 != 1
    layer = jnp.int32(L - 1)
    os.environ["OMNIA_PALLAS_DECODE"] = "0"   # the reference: the route off
    attn._pallas_decode_mode.cache_clear()
    try:
        if kernel == "window":
            want = attn.ring_decode_attention(q[:, None], k, v, pos[:, None], layer, None,
                                              rows)[:, 0]
        else:
            want = attn.gqa_attention(q[:, None], k, v, pos[:, None], layer=layer)[:, 0]
    finally:
        del os.environ["OMNIA_PALLAS_DECODE"]
        attn._pallas_decode_mode.cache_clear()
    # What the kernel may not read: a dead slot's rows and every block a live
    # slot does not reach (NaN); what it reads and must mask: the rows of the
    # last block past the position (a ring's: the rows its positions have not
    # reached), finite, so that a probability of 0 times them is 0.
    at = np.arange(rows)[None, :]
    p = np.asarray(pos)[:, None]
    past = at > p                                         # (a full ring: none)
    unreached = (at // block > np.minimum(p // block, rows // block - 1)) | ~live[:, None]

    def poisoned(x, big):
        x = jnp.where(jnp.asarray(past)[None, :, :, None, None], jnp.asarray(big, x.dtype), x)
        return jnp.where(jnp.asarray(unreached)[None, :, :, None, None],
                         jnp.asarray(jnp.nan, x.dtype), x)

    got = jax.jit(call)(q, poisoned(k, 1e9), poisoned(v, -1e9), pos, layer, jnp.asarray(live))
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return {"outputs_distance": float(np.abs(got - want)[live].max()),
            "dead_slots_zero": bool((got[~live] == 0).all()),
            "finite": bool(np.isfinite(got).all())}, got[live]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=8, help="rounds over the layers in one program")
    ap.add_argument("--cases", nargs="*", default=None, help=f"of {', '.join(CASES)}")
    ap.add_argument("--check", action="store_true", help="compare with the einsum path first")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="a tiny size on the CPU, interpreted; no measurement")
    ap.add_argument("--out", default="chiprun_out/decode_attention.jsonl",
                    help="the lines again, for a tool that shows only the output's end")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from omnia_tpu.ops import attention as attn
    from omnia_tpu.ops import decode_attention as dk

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print("no TPU here: run through the chip tool, or --rehearse-cpu", file=sys.stderr)
        return 1
    cases = CASES if on_tpu else TINY
    iters, rounds = (args.iters, args.rounds) if on_tpu else (1, 1)
    dtype = jnp.bfloat16
    flat_rows = getattr(dk, "flat_rows", None)
    tolerance = 3e-2   # bfloat16 outputs of unit-normal values (tests/test_decode_attention.py)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    ok = True
    with open(args.out, "w") as out:
        for name in args.cases or cases:
            case = cases[name]
            kernel, B, H, Hkv, L, rows, _ = case
            q, k, v, pos = operands(case, args.seed, dtype)
            row_bytes = 2 * Hkv * D * jnp.dtype(dtype).itemsize
            # (a block of K or of V of at most 2 MiB: twice that, both of them
            # and the float32 copies of a block are the kernel's fast memory)
            blocks = [b for b in (256, 512, 1024)
                      if rows % b == 0 and b * row_bytes <= 4 << 20] or [min(rows, 256)]
            if not on_tpu:
                blocks = [b for b in (64, 128) if rows % b == 0]
            for block in blocks:
                steps = int(np.minimum(np.asarray(pos) // block + 1, rows // block).sum())
                layouts = [("rows of heads", False)]
                if flat_rows is not None and flat_rows(Hkv, D, dtype, block):
                    layouts.append(("heads among the rows", True))
                rows_of_heads = None
                for layout, flat in layouts:
                    if flat_rows is not None:
                        dk.flat_rows = flat_rows if flat else (lambda *a: False)
                    jax.clear_caches()             # the layout is read while tracing
                    call = kernel_call(dk, case, block, not on_tpu)
                    line = {"device": f"{device.platform}:{device.device_kind}",
                            "measured": on_tpu, "seed": args.seed, "case": name,
                            "kernel": kernel, "slots": B, "heads": H, "kv_heads": Hkv,
                            "layers": L, "rows": rows, "layout": layout,
                            "block": [block, Hkv, D] if not flat else [block * Hkv, D],
                            "block_rows": block, "grid_steps_a_call": steps,
                            "fetched_rows_a_call": steps * block,
                            "live_rows_a_call": int(np.minimum(np.asarray(pos) + 1, rows).sum())}
                    if args.check:
                        line["check"], got = checked(attn, call, case, block, q, k, v, pos)
                        if rows_of_heads is None:
                            rows_of_heads = got
                        line["check"]["distance_to_rows_of_heads"] = float(
                            np.abs(got - rows_of_heads).max())
                        ok = (ok and line["check"]["dead_slots_zero"] and line["check"]["finite"]
                              and line["check"]["outputs_distance"] <= tolerance)
                    s, k, v = timed(call, q, k, v, pos, L, rounds, iters)
                    fetched = steps * block * row_bytes
                    line.update(us_a_call=s * 1e6, us_a_grid_step=s * 1e6 / steps,
                                ns_a_fetched_row=s * 1e9 / (steps * block),
                                fetched_gb_s=fetched / s / 1e9,
                                hbm_floor_us=fetched / HBM_BYTES_S * 1e6,
                                roofline=fetched / HBM_BYTES_S / s)
                    text = json.dumps(line)
                    print(text, flush=True)
                    out.write(text + "\n")
            if flat_rows is not None:
                dk.flat_rows = flat_rows
            del q, k, v
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
