#!/usr/bin/env python3
"""The builder's long comparison for a model with a recurrent state in the
slot's cache, on the chip (ISSUE 43, Tentpole 5): `harness/correct.py` part (a)
runs 128 + 8 tokens through one slot in one piece, so it never hands a state
or a convolution's tail from piece to piece and never pads a piece. This takes
the cell's own sizes instead:

    chiprun --chips 1 --timeout 2400 -- python3 benchmark/tests/chip_long_state.py \
        --workload kimi-linear-48b-a3b.longdoc-wide --seed <n> [--prompt 6000] [--decode 64]

A prompt of `--prompt` tokens (4096-8192) placed piece by piece exactly as
`engine/placement.py::_extend_pieces` cuts it for the cell's buckets (the
last piece padded to its bucket, and named by its last real row as
`engine/programs.py::extend` names it), then `--decode` single-token steps
through the cache with the decode kernels as served (`decode_kda_state`,
`decode_mla_attention`), in a one-slot cache of the cell's rows. Every token
is run; the logits compared are those of the positions `kept_positions`
names (the rows on either side of every piece's boundary, every eighth row
between, the prompt's last rows and every decode step): at a vocabulary
slice of 40960 every position's logits of eight layers three times over
are more than the machine's 40 GiB. As `correct`
does it, never at the model's whole depth: every layer alone on the stream
the reference saw enter it (`correct._sub_model`, `reference_layers`), and
layers 0 and 1 together; the reference is the configuration's own module in
float32 at "highest" precision: the per-token recurrence over the whole
sequence, the latent scores a block of 512 queries at a time. Judged by
`correct.judge_sparse` with `correct`'s own limits (MAX_TOL, MEAN_TOL,
NOISE_FACTOR, PAIR_TOL), whose reasons are that file's. Layers of one kind
share one compiled program (the cut's first layer is an operand).

Then the one-layer cuts of the sparse linear-attention layers once more in
float32 at "highest" precision (the same code paths: the chunk-wise rule
over every piece, the state and the tail handed on, the state kernel; the
values are the served ones upcast), where nothing but the order of the sums
separates the program from the reference, and there the two controls that
have to fail: the state rounded to bfloat16 on its way into and out of every
piece and step, and the decay applied behind the update instead of before it.
The decided positions' mean distance and the worst one's are held to
`FLOAT32_MEAN_LIMIT` and `FLOAT32_MAX_LIMIT`. The last line printed is one JSON
object with every reading and `ok`: the served-type run within `correct`'s
limits, the float32 run within both of its own, each control outside one.
Not a pytest file: it needs the chip (on the CPU it runs at the rehearsal's
widths with `--rehearse-cpu`, as a check of its own control flow, and says
so).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


# The most a linear-attention layer's decided positions may be off in float32
# at "highest" precision, as shares of the logit range: their mean distance,
# and the worst position's largest. Each lies between the sound run's reading
# on the chip and a control's (PERF.md section 6, PR 43; two seeds): sound mean
# 1.3e-4 and worst 9.0e-4 to 9.2e-4; the decay behind the update 1.2e-3 and
# 1.6e-2, outside both; the state rounded to bfloat16 1.4e-4 and 1.45e-3,
# outside the worst position's alone (a rounded state moves few positions far
# and the mean hardly). A control has to fail one of the two, the sound run
# neither. The sound run's own floor is higher than float32 sums explain
# (PERF.md section 7): the limits stand on readings, with a quarter of room.
FLOAT32_MEAN_LIMIT, FLOAT32_MAX_LIMIT = 4e-4, 1.15e-3


def kept_positions(pieces, prompt: int, total: int):
    """bool [total]: the positions whose logits are compared."""
    import numpy as np

    p = np.arange(total)
    keep = (p % 8 == 0) | (p >= prompt - 64)
    for off, _take, _bucket in pieces:  # a piece's first rows, and the rows before it
        keep |= (p >= off - 16) & (p < off + 32)
    return keep


def log(*a) -> None:
    print("[state]", *a, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt", type=int, default=6000)
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
    total = args.prompt + args.decode
    if total > ecfg.max_seq - 2:
        raise SystemExit(f"{total} tokens do not fit the cell's {ecfg.max_seq} rows")
    # The engine's own plan for a prompt longer than its largest bucket.
    pieces = _PlacementMixin._extend_pieces(types.SimpleNamespace(cfg=ecfg), 0, args.prompt)
    log(f"{args.prompt} prompt tokens in {len(pieces)} pieces, the last {pieces[-1][1]} real "
        f"rows of {pieces[-1][2]}; {args.decode} decode steps; layers {kinds}")

    keep = kept_positions(pieces, args.prompt, total)
    kept = jnp.asarray(np.flatnonzero(keep))
    kept_prompt = int(keep[:args.prompt].sum())
    log(f"{int(keep.sum())} of {total} positions compared, {kept_prompt} of them the prompt's")

    def reference_layers():
        """`correct.reference_layers`, a layer's results taken to the host
        before the next is run, the kept positions' alone: at these lengths a
        layer's two [T, V] logits are 2 GB, and that function keeps every
        layer's on the device."""
        programs, per = {}, []
        for n, (first, count, cut) in enumerate(correct._cuts(order, len(order), 1)):
            if (count, cut) not in programs:
                cut_sizes = correct._cut_sizes(sizes, cut)

                def one(p, stream, first, count=count, cut_sizes=cut_sizes):
                    sub = correct._sub_model(p, stream, first, count, dtype)
                    where = jnp.asarray(positions)
                    logits, margin, sigma, _ = ref_mod.forward_routed(sub, cut_sizes, where)
                    plain = ref_mod.forward(sub, cut_sizes, where, compute=dtype)
                    return logits[kept], plain[kept], margin[0][kept], sigma[0]

                programs[count, cut] = jax.jit(one)
            per.append([np.asarray(x, np.float32)
                        for x in programs[count, cut](params, residual[n], first)])
        return tuple(np.stack([x[i] for x in per]) for i in range(4))

    tokens = correct._seeded_tokens(mc, args.seed, total)
    positions = np.arange(total, dtype=np.int32)
    _, _, _, residual = jax.jit(lambda p, t: ref_mod.forward_routed(p, sizes, t))(
        params, jnp.asarray(tokens))
    layers_ref, layers_plain, margin, sigma = reference_layers()
    decided = correct.decided_pairs(margin, sigma)
    log(f"reference done: {int(decided.sum())} decided pairs of {decided.size}")

    programs = {}

    def served(stream, first, count, cut, compute=dtype, variant="sound"):
        """The `count` layers from `first` alone on `stream`, placed in the
        engine's pieces and decoded through a one-slot cache: float32 [T, V].
        `compute`: the type the cut model and its cache are run in (the
        served type; float32 is the same values upcast). Cuts alike but for
        where they start share one compiled program; `variant` names what is
        patched into the program while it is traced."""
        cut_cfg = correct.cut_config(model, mc, cut)
        key = (count, cut, jnp.dtype(compute).name, variant)
        if key not in programs:
            def forward(p, stream, first, cache, toks, start, last):
                sub = correct._sub_model(p, stream, first, count, dtype)
                sub = jax.tree_util.tree_map(
                    lambda a: a.astype(compute) if a.dtype == dtype else a, sub)
                pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
                every, *_ = model.forward(sub, cut_cfg, toks, pos, *cache,
                                          jnp.reshape(start, (1,)))
                # ... and the cache as a placement writes it: pad rows are not real.
                _, *cache = model.forward(sub, cut_cfg, toks, pos, *cache,
                                          jnp.reshape(start, (1,)), row=last)
                return every, tuple(cache)

            programs[key] = jax.jit(forward, donate_argnums=(3,))
        forward = programs[key]
        cache = tuple(model.init_kv_cache(cut_cfg, 1, ecfg.max_seq, dtype=compute))
        out = []
        plan = list(pieces) + [(t, 1, 1) for t in range(args.prompt, total)]
        for off, take, bucket in plan:
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :take] = positions[off:off + take]
            logits, cache = forward(params, stream, first, cache, jnp.asarray(toks),
                                    jnp.int32(off), jnp.int32(take - 1))
            out.append(np.asarray(logits[0, :take], np.float32)[keep[off:off + take]])
        return np.concatenate(out)

    def one_layer_runs(which, compute=dtype, variant="sound"):
        """[len(which), kept positions, V]: each of those layers alone."""
        cuts = correct._cuts(order, len(order), 1)
        return np.stack([served(residual[n], *cuts[n], compute, variant) for n in which])

    every = list(range(len(order)))
    sound = one_layer_runs(every)
    first, count, cut = correct._cut(order, 0, correct.PAIR)
    pair = served(residual[0], jax.tree_util.tree_map(jnp.int32, first), count, cut)
    pair_ref = np.asarray(jax.jit(lambda p, stream: ref_mod.forward(
        correct._sub_model(p, stream, first, count, dtype), correct._cut_sizes(sizes, cut),
        jnp.asarray(positions))[kept])(params, residual[0]), np.float32)
    result = {"sound": correct.judge_sparse(sound, layers_ref, layers_plain, decided,
                                            kept_prompt, pair, pair_ref)}
    del sound, pair, pair_ref, layers_plain
    log("sound:", json.dumps(result["sound"]))

    # In float32 at "highest" precision the same code paths (the chunk-wise
    # rule over the pieces, the state and the tail handed on, the state
    # kernel) leave the served type's rounding behind, and what is left
    # between the program and the reference is the order of their sums: the
    # controls are judged there, on the sparse linear-attention layers alone,
    # by the decided positions' mean and worst |logit difference| as shares
    # of the range.
    from omnia_tpu.ops import moe

    # (The experts' matmuls through `ragged_dot`, which follows the ambient
    # precision: the Pallas grouped matmul multiplies float32 operands at the
    # matmul unit's default precision, which set a floor of 1.3e-4 under the
    # first run of this script and hid the rounded state beneath it.)
    moe.GROUPED_MATMUL_MIN_ROWS = 1 << 40
    states = [n for n, kind in enumerate(kinds) if kind == "sparse_kda"]
    chunked, state_step = model.kda_chunked, model.decode_kda_state

    def bf16(a):
        """Rounded to bfloat16's eight bits of mantissa. (`astype` there and
        back is removed by the chip's compiler, which may keep excess
        precision: PR 43's first run read this control equal to the sound run
        to every digit.)"""
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def rounded_chunked(q, k, v, g, beta, S):
        o, S = chunked(q, k, v, g, beta, bf16(S))
        return o, bf16(S)

    def rounded_step(states, *a, **kw):
        o, states = state_step(bf16(states), *a, **kw)
        return o, bf16(states)

    def decay_behind(S, q, k, v, g, beta):
        """S <- Diag(alpha) (S + beta k (v - S^T k)^T): the decay behind the update."""
        f32 = jnp.float32
        q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
        r = jnp.einsum("...kv,...k->...v", S, k, precision=jax.lax.Precision.HIGHEST)
        S = S + k[..., :, None] * (beta[..., None] * (v - r))[..., None, :]
        S = S * jnp.exp(g)[..., :, None]
        return jnp.einsum("...kv,...k->...v", S, q, precision=jax.lax.Precision.HIGHEST), S

    def behind_chunked(q, k, v, g, beta, S):
        def body(S, x):
            o, S = decay_behind(S, *x)
            return S, o

        S, o = jax.lax.scan(body, S.astype(jnp.float32),
                            tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), S

    def behind_step(states, q, k, v, g, beta, layer, live=None, **kw):
        S = jax.lax.dynamic_index_in_dim(states, layer, 0, keepdims=False)
        o, new = decay_behind(S, q, k, v, g, beta)
        return o, jax.lax.dynamic_update_slice_in_dim(states, new[None], layer, axis=0)

    patches = {"float32_sound": (chunked, state_step),
               "float32_bfloat16_state": (rounded_chunked, rounded_step),
               "float32_decay_behind_the_update": (behind_chunked, behind_step)}

    def control(name):
        model.kda_chunked, model.decode_kda_state = patches[name]  # called by these names
        try:
            with jax.default_matmul_precision("highest"):
                got = one_layer_runs(states, jnp.float32, name)
        finally:
            model.kda_chunked, model.decode_kda_state = chunked, state_step
        worst, mean = correct._over_range(got, layers_ref[states])
        result[name] = {"max_over_range": float(worst[decided[states]].max()),
                        "mean_over_range": float(mean[decided[states]].mean())}
        log(f"{name}:", json.dumps(result[name]))

    for name in patches:
        control(name)

    limits = {"mean_over_range": FLOAT32_MEAN_LIMIT, "max_over_range": FLOAT32_MAX_LIMIT}
    controls = ("float32_bfloat16_state", "float32_decay_behind_the_update")
    result["float32_limits"] = limits
    result["float32_room"] = {
        key: {"sound_under_limit": limit / max(result["float32_sound"][key], 1e-12),
              "nearer_control_over_limit": min(result[c][key] for c in controls) / limit}
        for key, limit in limits.items()}
    result["ok"] = bool(
        result["sound"]["ok"]
        and all(result["float32_sound"][key] <= limit for key, limit in limits.items())
        and all(any(result[c][key] > limit for key, limit in limits.items()) for c in controls))
    result["run"] = {"workload": cell.name, "seed": args.seed, "prompt": args.prompt,
                     "decode": args.decode, "pieces": [list(p) for p in pieces],
                     "platform": platform, "layers": kinds}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"long_state.{args.seed}.json"), "w") as f:
        json.dump(result, f, indent=1)
    if args.rehearse_cpu:
        log("REHEARSAL line (not a result):", json.dumps(result))
        return 3
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
