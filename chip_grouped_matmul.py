#!/usr/bin/env python3
"""Microbenchmark: the experts' grouped matmuls, ``ragged_dot`` against the
Pallas kernel, at the row counts of the three sparse cells.

One process on one chip. For each cell's shape (rows = tokens × k, the
layers' stack [L, Eh, K, N] at the published widths, ``sizes`` as a seeded
router of the cell's kind gives them: scores, selection bias and the share
of the experts that is held) it times each of a sparse layer's three
matmuls (gate, up, down) through ``ops/moe.py::_ragged_matmul`` (the call as
a prompt's programs made it until PR 42 and a decode step's until PR 44,
and as a call shorter than one row tile still makes it) and through
``ops/grouped_matmul.py`` at a few tilings, at a prompt's rows and at a
decode step's, and prints one JSON line a shape and route: milliseconds a
call and GB/s of the hit experts' bytes.

    python chip_grouped_matmul.py                 # on the chip
    python chip_grouped_matmul.py --rehearse-cpu  # tiny, interpreted, says so

A number of the rehearsal is no measurement. No TPU and no
``--rehearse-cpu`` → exit 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# cell: (prompt tokens, decode slots, k, E, Eh, L of the cut stack, d, f,
# scoring, bias). The widths and counts are the configurations' under
# benchmark/configs/, the tokens a placement's bucket (judge, reason) or a
# piece (longdoc), the slots the traffic files'.
SHAPES = {
    "xing4-29b-a4b.judge-batch": (1536, 48, 4, 64, 64, 4, 3584, 1024, "sigmoid", "normal"),
    "k-exaone-236b-a23b.longdoc-batch": (1024, 32, 8, 128, 16, 4, 6144, 2048, "sigmoid",
                                         "quantiles"),
    "mistral-small-4.reason-batch": (1024, 96, 4, 128, 32, 5, 4096, 2048, "softmax", None),
}
TINY = {name: (64, 8, s[2], 16, 16 * s[4] // s[3], 2, 256, 128, s[8], s[9])
        for name, s in SHAPES.items()}


def router_sizes(key, tokens, k, E, Eh, d, scoring, bias):
    """(sizes int32 [Eh], held rows): the runs a seeded router of this kind
    gives ``tokens`` normalised rows, as ``moe_dropless`` counts them."""
    import jax
    import jax.numpy as jnp

    from omnia_tpu.ops.moe import top_k_weights

    kh, kr, kb = jax.random.split(key, 3)
    h = jax.random.normal(kh, (tokens, d), jnp.float32)
    logits = h @ (jax.random.normal(kr, (d, E), jnp.float32) * 0.02)
    b = None
    if bias == "normal":            # models/mla.py
        b = jax.random.normal(kb, (E,), jnp.float32) * 0.05
    elif bias == "quantiles":       # models/stacks.py: the same on every rank
        values = 0.05 * jax.scipy.special.ndtri((jnp.arange(Eh) + 0.5) / Eh)
        b = jnp.tile(values[jax.random.permutation(kb, Eh)], E // Eh)
    top_i = top_k_weights(logits, k, scoring, b)[1].reshape(-1)
    sizes = jnp.bincount(jnp.where(top_i < Eh, top_i, Eh), length=Eh + 1)[:Eh]
    return sizes.astype(jnp.int32), int(sizes.sum())


def timed(fn, args, layers, iters):
    """Milliseconds a call: ``iters`` calls enqueued behind each other, a
    layer of the stack each in turn, one wait at the end (a call is
    0.2–3 ms of device time, more than its enqueue)."""
    import jax

    jax.block_until_ready(fn(*args, 0))      # compile
    jax.block_until_ready(fn(*args, 1 % layers))
    t0 = time.perf_counter()
    out = None
    for i in range(iters):
        out = fn(*args, i % layers)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes, the kernel interpreted; no measurement")
    ap.add_argument("--cells", nargs="*", default=sorted(SHAPES))
    ap.add_argument("--rows-of", nargs="*", default=["prompt", "decode"],
                    choices=["prompt", "decode"], help="which of a cell's two row counts")
    ap.add_argument("--out", default="chiprun_out/grouped_matmul.jsonl",
                    help="the lines again, for a tool that shows only the output's end")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from omnia_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, tiles
    from omnia_tpu.ops.moe import _ragged_matmul

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print("no TPU here: run through the chip tool, or --rehearse-cpu", file=sys.stderr)
        return 1
    shapes = SHAPES if on_tpu else TINY
    iters = args.iters if on_tpu else 2
    dtype = jnp.bfloat16
    say = {"device": f"{device.platform}:{device.device_kind}",
           "measured": on_tpu, "seed": args.seed}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "w")

    def line(**fields):
        text = json.dumps({**say, **fields})
        print(text, flush=True)
        print(text, file=out, flush=True)

    for cell in args.cells:
        tokens, slots, k, E, Eh, L, d, f, scoring, bias = shapes[cell]
        key = jax.random.key(args.seed & 0x7FFFFFFF)
        kx, kw, ks = jax.random.split(key, 3)
        for kind, toks in (("prompt", tokens), ("decode", slots)):
            if kind not in args.rows_of:
                continue
            rows = toks * k
            sizes, held = router_sizes(jax.random.fold_in(ks, toks), toks, k, E, Eh, d,
                                       scoring, bias)
            hit = int((sizes > 0).sum())
            for name, K, N in (("gate", d, f), ("up", d, f), ("down", f, d)):
                kk = jax.random.fold_in(kw, ("gate", "up", "down").index(name))
                w = (jax.random.normal(kk, (L, Eh, K, N), jnp.float32) * 0.02).astype(dtype)
                xs = jax.random.normal(jax.random.fold_in(kx, K), (rows, K), dtype)
                hit_bytes = hit * K * N * w.dtype.itemsize
                tk, tn = tiles(K, N, w.dtype.itemsize)
                if on_tpu:
                    tilings = [(ROW_TILE, tk, tn), (ROW_TILE, K, 512), (256, K, 512),
                               (ROW_TILE, K, 256), (ROW_TILE, 512, 512), (512, K, 512)]
                else:
                    tilings = [(16, tk, tn), (16, 128, 128)]
                routes = [("ragged_dot", jax.jit(_ragged_matmul))]
                for tiling in dict.fromkeys(tilings):
                    routes.append((
                        "grouped_matmul tm=%d tk=%d tn=%d" % tiling,
                        jax.jit(lambda xs, w, sizes, layer, tiling=tiling: grouped_matmul(
                            xs, w, sizes, layer, tiling=tiling, interpret=not on_tpu))))
                base = None
                for route, fn in routes:
                    try:
                        ms = timed(fn, (xs, w, sizes), L, iters)
                    except Exception as e:  # a tiling the compiler refuses is a line too
                        line(cell=cell, rows_of=kind, matmul=name, route=route,
                             error=str(e)[:200])
                        continue
                    base = base or ms
                    line(cell=cell, rows_of=kind, matmul=name, route=route, rows=rows,
                         held_rows=held, experts_hit=hit, groups=L * Eh, K=K, N=N,
                         ms=round(ms, 4), hit_GBps=round(hit_bytes / ms / 1e6, 1),
                         floor_ms=round(hit_bytes / 819e9 * 1e3, 4),
                         vs_ragged_dot=round(base / ms, 3))
                del w, xs
    if not on_tpu:
        print("REHEARSAL on the CPU: the control flow ran; no line above is a measurement")
    return 0


if __name__ == "__main__":
    sys.exit(main())
