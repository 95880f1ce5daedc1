#!/usr/bin/env python3
"""The builder's long comparison for a model whose rings are several blocks of
the decode kernel and whose queue holds both placement routes, on the chip
(ISSUE 47, Tentpole 6 d): `harness/correct.py` part (a) runs 128 + 8 tokens,
so at a 1,024-row window it never meets the window's edge, never fills a ring
and never wraps one. This takes the cell's own sizes instead:

    chiprun --chips 1 --timeout 2400 -- python3 benchmark/tests/chip_long_mixed.py \
        --workload mellum2-12b-a2p5b.code-mixed --seed <n> [--long 3000] [--short 700] [--decode 64]

Two prompts into the two slots of one cache of the cell's rows. Slot 0 is
placed whole, as `engine/programs.py::prefill_insert` places a prompt of at
most the largest bucket: `forward_prefill` over the bucket that
`EngineConfig.bucket_for` picks, its last real row named, its chunks put at
the slot's row 0 (the ring comes partly filled: the last real rows where the
ring holds them, the rest whatever the chunk held). Slot 1 is placed piece by
piece exactly as `engine/placement.py::_extend_pieces` cuts a prompt longer
than the largest bucket (the last piece padded to its bucket and named by its
last real row, as `extend` names it), over the slot's own view, wrapping the
ring twice. Then `--decode` single-token steps of BOTH slots in one batch
through the decode kernels as served: the window kernel runs a slot whose
position has not reached the ring's end (three of its four blocks) beside one
that has passed it.

As `correct` does it, never at the model's whole depth: every layer alone on
the stream the reference saw enter it (`correct._sub_model`), and layers 0
and 1 together; the reference is the configuration's own module in float32
at "highest" precision, which computes its scores a block of 512 queries at a
time. A layer's logits are compared at every decode position and at every
`STRIDE`-th prompt position (the rows of a logit matrix are independent, and
[T, 98304] float32 a layer a sequence does not fit the host three times
over). Each sequence is judged by `correct.judge_sparse` with `correct`'s own
limits (MAX_TOL, MEAN_TOL, NOISE_FACTOR, PAIR_TOL), whose reasons are that
file's. One control has to fail them: the full layers run with YaRN's
attention factor left out (the table's cos and sin unscaled). The last line
printed is one JSON object with every reading and `ok`: both sequences within
the limits, the control outside them. Not a pytest file: it needs the chip
(on the CPU it runs at the rehearsal's widths with `--rehearse-cpu`, as a
check of its own control flow, and says so).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import types

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

STRIDE = 8


def log(*a) -> None:
    print("[long]", *a, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--long", type=int, default=3000)
    ap.add_argument("--short", type=int, default=700)
    ap.add_argument("--decode", type=int, default=64)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from harness.manifest import Cell, load_model_module, load_reference, reference_sizes

    cell = Cell(args.workload)
    if args.rehearse_cpu:
        cell.rehearse()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from omnia_tpu.engine.placement import _PlacementMixin
    from omnia_tpu.engine.types import resolve_dtype

    from harness import correct
    from harness.weights import seeded_params

    platform = jax.devices()[0].platform
    if args.rehearse_cpu:
        log(f"REHEARSAL on {platform}: tiny widths, no result")
    elif platform != "tpu":
        log(f"needs a TPU chip; JAX reports {platform}. No result.")
        return 2
    mc = cell.model_config(rehearse=args.rehearse_cpu)
    ecfg = cell.engine_config()
    dtype = resolve_dtype(ecfg.dtype)
    model = load_model_module(cell.model_module)
    ref_mod = load_reference(cell.reference)
    sizes = reference_sizes(mc, cell.config_as_run(args.rehearse_cpu))
    params = seeded_params(mc, ecfg, None, args.seed, dtype, model_module=cell.model_module)
    order = correct.layer_order(model, mc, params["layers"])
    kinds = [model.stack_kinds(mc)[stack] for stack, _ in order]
    ring = model.ring_rows(mc)
    if not args.short <= max(ecfg.usable_buckets()) < args.long:
        raise SystemExit("--short has to fit the largest bucket and --long must not")
    if args.long + args.decode > ecfg.max_seq - 2:
        raise SystemExit(f"{args.long + args.decode} tokens do not fit {ecfg.max_seq} rows")
    bucket = ecfg.bucket_for(args.short)
    pieces = _PlacementMixin._extend_pieces(types.SimpleNamespace(cfg=ecfg), 0, args.long)
    log(f"slot 0: {args.short} tokens whole in a bucket of {bucket}; slot 1: {args.long} in "
        f"{len(pieces)} pieces, the last {pieces[-1][1]} real rows of {pieces[-1][2]}; rings of "
        f"{ring} rows, wrapped {(args.long + args.decode) / ring:.2f} times by slot 1; "
        f"{args.decode} decode steps of both; layers {kinds}")

    # name -> (prompt tokens, rows of the shared table its stream starts at)
    lengths = {"pieces": args.long, "whole": args.short}
    base = {"pieces": 0, "whole": args.long + args.decode}
    keep = {name: np.concatenate([np.arange(0, n, STRIDE), np.arange(n, n + args.decode)])
            for name, n in lengths.items()}
    cuts = correct._cuts(order, len(order), 1)

    def reference(name, seed):
        """(residual [L + 1, T, D] on the device, then at the kept positions:
        float32 logits [L, K, V], served-type logits, decided [L, K])."""
        n = lengths[name] + args.decode
        tokens = correct._seeded_tokens(mc, seed, n)
        where, rows = jnp.arange(n, dtype=jnp.int32), jnp.asarray(keep[name])
        _, _, _, residual = jax.jit(lambda p, t: ref_mod.forward_routed(p, sizes, t))(
            params, jnp.asarray(tokens))
        programs, per = {}, []
        for layer, (first, count, cut) in enumerate(cuts):
            if (count, cut) not in programs:
                cut_sizes = correct._cut_sizes(sizes, cut)

                def one(p, stream, first, count=count, cut_sizes=cut_sizes):
                    sub = correct._sub_model(p, stream, first, count, dtype)
                    logits, margin, sigma, _ = ref_mod.forward_routed(sub, cut_sizes, where)
                    plain = ref_mod.forward(sub, cut_sizes, where, compute=dtype)
                    return logits[rows], plain[rows], margin[0][rows], sigma[0]

                programs[count, cut] = jax.jit(one)
            per.append([np.asarray(x, np.float32)
                        for x in programs[count, cut](params, residual[layer], first)])
        ref, plain, margin, sigma = (np.stack([x[i] for x in per]) for i in range(4))
        return residual, ref, plain, correct.decided_pairs(margin, sigma)

    refs = {name: reference(name, args.seed + i) for i, name in enumerate(lengths)}
    for name, (_, _, _, decided) in refs.items():
        log(f"reference of {name!r}: {int(decided.sum())} decided pairs of {decided.size}")

    @functools.cache
    def programs(cfg, count, cut):
        """(`whole`, `piece`, `step`, a fresh two-slot cache's maker) of one
        kind of cut: layers alike but for where they start share the three."""
        cut_cfg = correct.cut_config(model, cfg, cut)

        def sub_model(p, table, first):
            return correct._sub_model(p, table, first, count, dtype)

        @jax.jit
        def whole(p, table, first, cache, toks, last):
            """Slot 0 as `prefill_insert` fills it; every row's logits too."""
            sub = sub_model(p, table, first)
            pos = jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
            every, *_ = model.forward_prefill(sub, cut_cfg, toks, pos)
            _, *chunks = model.forward_prefill(sub, cut_cfg, toks, pos, row=last)
            return every, tuple(
                jax.lax.dynamic_update_slice(c, chunk.astype(c.dtype), (0, 0, 0, 0, 0))
                for c, chunk in zip(cache, chunks))

        @jax.jit
        def piece(p, table, first, cache, toks, start, last):
            """One piece over slot 1's view, the view written back, as
            `_extend_slot` does; every row's logits too."""
            sub = sub_model(p, table, first)
            pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
            views = [c[:, 1:2] for c in cache]
            every, *_ = model.forward(sub, cut_cfg, toks, pos, *views, jnp.reshape(start, (1,)))
            _, *views = model.forward(sub, cut_cfg, toks, pos, *views, jnp.reshape(start, (1,)),
                                      row=last)
            return every, tuple(jax.lax.dynamic_update_slice(c, v, (0, 1, 0, 0, 0))
                                for c, v in zip(cache, views))

        @jax.jit
        def step(p, table, first, cache, toks, start):
            logits, *cache = model.forward(sub_model(p, table, first), cut_cfg, toks,
                                           start[:, None], *cache, start)
            return logits[:, 0], tuple(cache)

        return whole, piece, step, lambda: tuple(
            model.init_kv_cache(cut_cfg, 2, ecfg.max_seq, dtype=dtype))

    def served(cfg, layer, first, count, cut):
        """The `count` layers from model layer `layer` alone on both streams
        (one table: the long sequence's rows, then the short one's), placed
        and decoded as the docstring says → {name: float32 [K, V]}."""
        whole, piece, step, fresh_cache = programs(cfg, count, cut)
        table = jnp.concatenate([refs["pieces"][0][layer], refs["whole"][0][layer]], axis=0)
        cache = fresh_cache()
        out = {"pieces": [], "whole": []}
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :args.short] = base["whole"] + np.arange(args.short)
        every, cache = whole(params, table, first, cache, jnp.asarray(toks),
                             jnp.int32(args.short - 1))
        out["whole"].append(np.asarray(every[0, :args.short:STRIDE], np.float32))
        for off, take, b in pieces:
            toks = np.zeros((1, b), np.int32)
            toks[0, :take] = np.arange(off, off + take)
            every, cache = piece(params, table, first, cache, jnp.asarray(toks), jnp.int32(off),
                                 jnp.int32(take - 1))
            rows = np.arange(off, off + take)
            out["pieces"].append(np.asarray(every[0, :take], np.float32)[rows % STRIDE == 0])
        for t in range(args.decode):
            start = np.asarray([args.short + t, args.long + t], np.int32)
            toks = (start + np.asarray([base["whole"], base["pieces"]], np.int32))[:, None]
            logits, cache = step(params, table, first, cache, jnp.asarray(toks),
                                 jnp.asarray(start))
            logits = np.asarray(logits, np.float32)
            out["whole"].append(logits[0:1])
            out["pieces"].append(logits[1:2])
        return {name: np.concatenate(rows) for name, rows in out.items()}

    def one_layer_runs(cfg, which):
        got = {name: np.array(refs[name][1]) for name in lengths}  # not run: the reference
        for layer in which:
            for name, logits in served(cfg, layer, *cuts[layer]).items():
                got[name][layer] = logits
        return got

    def pair_of(cfg):
        first, count, cut = correct._cut(order, 0, correct.PAIR)
        got = served(cfg, 0, jax.tree_util.tree_map(jnp.int32, first), count, cut)
        want = {}
        for name, n in lengths.items():
            where, rows = jnp.arange(n + args.decode, dtype=jnp.int32), jnp.asarray(keep[name])
            want[name] = np.asarray(jax.jit(lambda p, stream, where=where, rows=rows: ref_mod.forward(
                correct._sub_model(p, stream, first, count, dtype),
                correct._cut_sizes(sizes, cut), where)[rows])(params, refs[name][0][0]),
                np.float32)
        return got, want

    def judged(layers, pair=None, pair_ref=None):
        return {name: correct.judge_sparse(
            layers[name], refs[name][1], refs[name][2], refs[name][3],
            len(keep[name]) - args.decode,
            None if pair is None else pair[name], None if pair_ref is None else pair_ref[name])
            for name in lengths}

    result = {"sound": judged(one_layer_runs(mc, range(len(order))), *pair_of(mc))}
    log("sound:", json.dumps(result["sound"]))
    fulls = [layer for layer, kind in enumerate(kinds) if kind.endswith("full")]
    unscaled = dataclasses.replace(mc, rope_full_yarn=(*mc.rope_full_yarn[:4], 1.0))
    result["attention_factor_left_out"] = judged(one_layer_runs(unscaled, fulls))
    log("attention factor left out:", json.dumps(result["attention_factor_left_out"]))
    result["ok"] = bool(all(v["ok"] for v in result["sound"].values())
                        and not any(v["ok"] for v in result["attention_factor_left_out"].values()))
    result["run"] = {"workload": cell.name, "seed": args.seed, "long": args.long,
                     "short": args.short, "bucket": bucket, "decode": args.decode,
                     "pieces": [list(p) for p in pieces], "ring_rows": ring,
                     "ring_wraps": (args.long + args.decode) / ring, "stride": STRIDE,
                     "platform": platform, "layers": kinds}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"long_mixed.{args.seed}.json"), "w") as f:
        json.dump(result, f, indent=1)
    if args.rehearse_cpu:
        log("REHEARSAL line (not a result):", json.dumps(result))
        return 3
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
