#!/usr/bin/env python3
"""Microbenchmark: a prompt's attention, the einsums over the masked score
tensor against the blocked Pallas kernel, at the shapes of the cells whose
device time is mostly prompts.

One process on one chip. For a window layer's shapes (``WINDOW_SHAPES``: a
fresh chunk, or a piece behind the ``window`` rows before it) it times the
einsum band (``ops/attention.py::band_attention``, the route-off side) against
the kernel with its lower bound at a few query tiles and key blocks; the floor
there is over the rows a query can see, at most ``window`` of them. For each
other cell's shape (a fresh chunk, or a piece of
T rows at offset ``first`` over a slot of S rows in a cache of a few layers)
it times one layer's attention as the model calls it: the pair family through
``ops/attention.py`` (``einsum_attention`` against ``prefill_attention`` at a
few tilings), the latent family through ``models/mla.py`` (``_expanded_attention``
with the route off against ``_blocked_attention``, the rows' expansion through
``wkvb`` in both, and the kernel alone). One JSON line a shape and route:
milliseconds a call, the matmul unit's floor for the key rows the queries can
see, and the share of its peak that is.

    python chip_prefill_attention.py                 # on the chip
    python chip_prefill_attention.py --rehearse-cpu  # tiny, interpreted, says so

A number of the rehearsal is no measurement. No TPU and no
``--rehearse-cpu`` → exit 1.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

PEAK_FLOPS = 197e12  # TPU v5e, bf16 (Google Cloud documentation, "TPU v5e")

# cell: (family, T, S, heads, KV heads, key width (nope, rope), value width,
# offsets ``first`` of the piece). The widths are the configurations' under
# benchmark/configs/, T a placement's bucket or a piece, S the slot's rows.
SHAPES = {
    "xing4-29b-a4b.judge-batch": ("latent", 2048, 2048, 32, 32, (128, 64), 128, (0,)),
    "k-exaone-236b-a23b.longdoc-batch": ("pair", 1024, 8960, 64, 8, (128, 0), 128,
                                         (0, 3072, 7936)),
    "kimi-linear-48b-a3b.longdoc-wide": ("latent", 1024, 9216, 32, 32, (128, 64), 128,
                                         (0, 3072, 8192)),
    "mistral-7b.longprompt-steady": ("pair", 2048, 2048, 32, 8, (128, 0), 128, (0,)),
    "mistral-small-4.reason-batch": ("latent", 1024, 1024, 32, 32, (64, 64), 128, (0,)),
}
# cell: (chunk lengths T, window, heads, KV heads, head width, offsets ``first`` of
# the piece; None: a fresh chunk, which meets its own rows alone).
WINDOW_SHAPES = {
    "mellum2-12b-a2p5b.code-mixed": ((256, 512, 1024), 1024, 32, 4, 128, (None, 1024, 4096)),
    "k-exaone-236b-a23b.longdoc-batch": ((1024,), 128, 64, 8, 128, (None, 1024, 4096)),
}
TINY_WINDOW = {name: ((256,), 128, 4, 2, 128, (None, 64, 256)) for name in WINDOW_SHAPES}
TINY = {name: (s[0], 256, 512 if s[2] > s[1] else 256, 4, 4 if s[3] == s[4] else 2,
               s[5], 128, (0, 256) if s[2] > s[1] else (0,)) for name, s in SHAPES.items()}


def timed(fn, args, iters):
    """Milliseconds a call: ``iters`` calls enqueued behind each other, one
    wait at the end (a call is 0.5–15 ms of device time)."""
    import jax

    jax.block_until_ready(fn(*args))      # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def chained(fn, calls: int):
    """``calls`` calls of ``fn`` in one program, each one's result the next
    one's queries (an attention's result has its queries' shape): a call of
    under half a millisecond is otherwise timed by the host's dispatch."""
    import jax

    return jax.jit(lambda q, *rest: jax.lax.fori_loop(
        0, calls, lambda _, q: fn(q, *rest).reshape(q.shape).astype(q.dtype), q))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes, the kernel interpreted; no measurement")
    ap.add_argument("--cells", nargs="*", default=sorted(SHAPES))
    ap.add_argument("--window-cells", nargs="*", default=sorted(WINDOW_SHAPES),
                    help="the cells whose window layers' shapes to time (none: leave them out)")
    ap.add_argument("--window", type=int, default=0,
                    help="time the window cells' shapes at this window, not their own "
                         "(where the band and the kernel cross)")
    ap.add_argument("--chain", type=int, default=8,
                    help="calls of a window shape's attention in one program")
    ap.add_argument("--check", action="store_true",
                    help="no timing: the window shapes in float32, the compiled kernel's largest "
                         "distance from the band, beside that of a window one row too wide")
    ap.add_argument("--sweep", action="store_true",
                    help="query tiles 256–1024 × key blocks 512–2048, not the default alone")
    ap.add_argument("--kernel-only", action="store_true",
                    help="leave the einsums out (a sweep's second run)")
    ap.add_argument("--out", default="chiprun_out/prefill_attention.jsonl",
                    help="the lines again, for a tool that shows only the output's end")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from omnia_tpu.models import mla
    from omnia_tpu.models.config import ModelConfig
    from omnia_tpu.ops import attention
    from omnia_tpu.ops.prefill_attention import prefill_attention, tiles

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print("no TPU here: run through the chip tool, or --rehearse-cpu", file=sys.stderr)
        return 1
    shapes = SHAPES if on_tpu else TINY
    iters = args.iters if on_tpu else 1
    dtype = jnp.bfloat16
    say = {"device": f"{device.platform}:{device.device_kind}",
           "measured": on_tpu, "seed": args.seed}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "w")

    def line(**fields):
        text = json.dumps({**say, **fields})
        print(text, flush=True)
        print(text, file=out, flush=True)

    def route(mode):
        os.environ["OMNIA_PALLAS_DECODE"] = mode
        attention._pallas_decode_mode.cache_clear()

    kernel_mode = "1" if on_tpu else "interpret"
    for cell in args.window_cells:
        Ts, W, H, Hkv, D, firsts = (WINDOW_SHAPES if on_tpu else TINY_WINDOW)[cell]
        W, G, calls = args.window or W, H // Hkv, args.chain if on_tpu else 1
        if args.check:  # and a piece whose slot holds less than a window: the lowest row binds
            dtype, firsts = jnp.float32, (*firsts, W // 2 + 7)
        keys = jax.random.split(jax.random.key(args.seed & 0x7FFFFFFF), 5)
        for T in Ts:
            q = jax.random.normal(keys[0], (1, T, H, D), dtype)
            k, v = (jax.random.normal(key, (1, T, Hkv, D), dtype) for key in keys[1:3])
            for first in firsts:
                fresh = first is None
                prev = () if fresh else tuple(
                    jax.random.normal(key, (1, W, Hkv, D), dtype) for key in keys[3:])
                S = T if fresh else W + T
                # Where the window never binds the route makes the causal call.
                window = W if not fresh or T > W else 0
                # A query sees its own row and at most W - 1 before it, none before 0.
                pairs = sum(min((first or 0) + t + 1, W) for t in range(T))
                floor_ms = 2 * pairs * H * 2 * D / PEAK_FLOPS * 1e3
                tilings = [tiles(T, S, G, window)]
                if args.sweep and window:
                    tilings += [(tq, tk) for tq in (256, 512, 1024) for tk in (256, 512, 1024)
                                if T % tq == 0 and tk <= S and G * tq <= 4096]
                at = jnp.full((1,), first or 0, jnp.int32)

                def band(q, k, v, at, *prev, fresh=fresh):
                    pk, pv = prev or (None, None)
                    return attention.band_attention(q, k, v, pk, pv, None if fresh else at, W)

                def kernel(q, k, v, at, *prev, fresh=fresh, S=S, T=T, window=window, tiling=None):
                    if prev:
                        k, v = (jnp.concatenate([p, x], axis=1) for p, x in zip(prev, (k, v)))
                    pos = (S - T + jnp.arange(T, dtype=jnp.int32))[None]
                    return prefill_attention(
                        q.reshape(1, T, H * D), k.reshape(1, S, Hkv * D), v.reshape(1, S, Hkv * D),
                        pos, None, None if fresh else jnp.maximum(W - at, 0), kv_heads=Hkv,
                        scale=D ** -0.5, window=window, tiling=tiling, interpret=not on_tpu)

                if args.check:
                    with jax.default_matmul_precision("highest"):
                        want = jax.jit(band)(q, k, v, at, *prev)
                    far = {name: float(jnp.abs(jax.jit(functools.partial(kernel, window=w))(
                        q, k, v, at, *prev).reshape(want.shape) - want).max())
                        for name, w in (("kernel", window), ("one_row_too_wide", window + 1))
                        if window or name == "kernel"}
                    line(cell=cell, family="window", T=T, S=S, window=W, first=first,
                         check="float32, largest distance from the band", **far)
                    continue
                routes = [] if args.kernel_only else [("band", chained(band, calls))]
                routes += [("prefill_attention window=%d tq=%d tk=%d" % (window, *tiling),
                            chained(functools.partial(kernel, tiling=tiling), calls))
                           for tiling in dict.fromkeys(tilings)]
                base = None
                for name, fn in routes:
                    try:
                        ms = timed(fn, (q, k, v, at, *prev), iters) / calls
                    except Exception as e:  # a tiling the compiler refuses is a line too
                        line(cell=cell, T=T, window=W, first=first, route=name, error=str(e)[:200])
                        continue
                    base = base or ms
                    line(cell=cell, family="window", T=T, S=S, window=W, heads=H, kv_heads=Hkv,
                         first=first, route=name, ms=round(ms, 4), floor_ms=round(floor_ms, 4),
                         peak_share=round(floor_ms / ms, 4), vs_band=round(base / ms, 3))
    for cell in args.cells:
        family, T, S, H, Hkv, (dn, dr), dv, firsts = shapes[cell]
        G = H // Hkv
        kq, kk, kv_, kw = jax.random.split(jax.random.key(args.seed & 0x7FFFFFFF), 4)
        tq0, tk0 = tiles(T, S, G)
        tilings = [(tq0, tk0)]
        if args.sweep:
            tilings += [(tq, tk) for tq in (256, 512, 1024) for tk in (512, 1024, 2048)
                        if T % tq == 0 and tk <= S and G * tq <= 4096]
        tilings = list(dict.fromkeys(tilings))
        for first in firsts:
            pos = (first + jnp.arange(T, dtype=jnp.int32))[None]
            # The key rows a query sees, summed over the queries: what the
            # two products must multiply at the least.
            pairs = sum(min(first + t + 1, S) for t in range(T))
            dk = -(-(dn + dr) // 128) * 128
            floor_ms = 2 * pairs * H * (dn + dr + dv) / PEAK_FLOPS * 1e3
            routes = []
            if family == "pair":
                L, layer = 2, 1
                q = jax.random.normal(kq, (1, T, H, dn), dtype)
                ck = jax.random.normal(kk, (L, 1, S, Hkv, dn), dtype)
                cv = jax.random.normal(kv_, (L, 1, S, Hkv, dv), dtype)
                operands = (q, ck, cv, pos)
                routes.append(("einsum", "0", jax.jit(
                    lambda q, ck, cv, pos: attention.einsum_attention(q, ck, cv, pos, layer))))
                for tiling in tilings:
                    routes.append((
                        "prefill_attention tq=%d tk=%d" % tiling, kernel_mode,
                        jax.jit(lambda q, ck, cv, pos, tiling=tiling: prefill_attention(
                            q.reshape(1, T, H * dn), ck.reshape(L, 1, S, Hkv * dn),
                            cv.reshape(L, 1, S, Hkv * dv), pos, layer, kv_heads=Hkv,
                            scale=dn ** -0.5, tiling=tiling, interpret=not on_tpu))))
            else:
                R = 512 if on_tpu else 128
                cfg = ModelConfig(
                    name="shape", vocab_size=128, hidden_size=128, num_layers=1, num_heads=H,
                    num_kv_heads=H, head_dim=dn + dr, ffn_hidden_size=128, kv_rank=R,
                    qk_nope_head_dim=dn, qk_rope_head_dim=dr, v_head_dim=dv)
                W = mla.row_width(cfg)
                q_nope = jax.random.normal(kq, (1, T, H, dn), dtype)
                q_rope = jax.random.normal(kk, (1, T, H, dr), dtype)
                rows = jax.random.normal(kv_, (1, S, W), dtype)
                wkvb = (jax.random.normal(kw, (R, H * (dn + dv)), jnp.float32)
                        * R ** -0.5).astype(dtype)
                operands = (q_nope, q_rope, rows, wkvb, pos)
                routes.append(("einsum", "0", jax.jit(
                    lambda a, b, c, d, e: mla._expanded_attention(a, b, c, d, cfg, e))))
                routes.append(("expand + prefill_attention tq=%d tk=%d" % (tq0, tk0),
                               kernel_mode, jax.jit(
                    lambda a, b, c, d, e: mla._expanded_attention(a, b, c, d, cfg, e))))
                qk = jax.random.normal(kq, (1, T, H * dk), dtype)
                kk_ = jax.random.normal(kk, (1, S, H * dk), dtype)
                vv = jax.random.normal(kv_, (1, S, H * dv), dtype)
                for tiling in tilings:
                    routes.append((
                        "prefill_attention tq=%d tk=%d" % tiling, kernel_mode,
                        jax.jit(lambda a, b, c, d, e, tiling=tiling: prefill_attention(
                            qk, kk_, vv, e, kv_heads=H, scale=0.07, tiling=tiling,
                            interpret=not on_tpu))))
            base = None
            if args.kernel_only:
                routes = [r for r in routes if r[0] != "einsum"]
            for name, mode, fn in routes:
                route(mode)
                try:
                    ms = timed(fn, operands, iters)
                except Exception as e:  # a tiling the compiler refuses is a line too
                    line(cell=cell, first=first, route=name, error=str(e)[:200])
                    continue
                base = base or ms
                line(cell=cell, family=family, T=T, S=S, heads=H, kv_heads=Hkv,
                     widths=[dn, dr, dv], first=first, route=name, ms=round(ms, 4),
                     floor_ms=round(floor_ms, 4), peak_share=round(floor_ms / ms, 4),
                     vs_einsum=round(base / ms, 3))
    if not on_tpu:
        print("REHEARSAL on the CPU: the control flow ran; no line above is a measurement")
    return 0


if __name__ == "__main__":
    sys.exit(main())
