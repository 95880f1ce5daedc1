#!/usr/bin/env python3
"""The builder's long comparison for the pair family's model with a recurrent
state in the slot's cache, on the chip (ISSUE 50, Tentpole 7d), after
`chip_long_state.py`: `harness/correct.py` runs 128 + 8 tokens through one
slot in one piece, so it never hands a state or a convolution's tail from
piece to piece, never pads a piece and never starts a piece at an offset.
This takes the cell's own sizes instead:

    chiprun --chips 1 --timeout 2400 -- python3 benchmark/tests/chip_long_delta.py \
        --workload olmo-hybrid-7b.think-batch --seed <n> [--prompt 2000] [--decode 64]

A prompt of `--prompt` tokens placed piece by piece exactly as
`engine/placement.py::_extend_pieces` cuts it for the cell's buckets (the
last piece padded to its bucket, and named by its last real row as
`engine/programs.py::extend` names it), then `--decode` single-token steps
through the cache with the decode kernels as served (`decode_delta_state`,
`decode_gqa_attention` at a group of one), in a one-slot cache of the cell's
rows. Every token is run; the logits compared are those of the positions
`kept_positions` names (the rows on either side of every piece's boundary,
every eighth row between, the prompt's last rows and every decode step): at
a vocabulary of 100352 every position's logits several times over are more
than the host holds. The model is dense, so it is judged whole as `correct`
judges it: the program's logits against the configuration's own reference in
float32 at "highest" precision over the whole sequence (the per-token
recurrence, the full layers' scores a block of 512 queries at a time), the
prompt's positions and the decode positions each within `correct.MAX_TOL` and
`correct.MEAN_TOL` of the logit range, whose reasons are that file's. Three
controls at the served type have to fail those limits: beta left in (0, 1),
the norms in front of the sublayers, the QK-norm a head.

Then the first period alone (L L L F on the real embedding table, cut as
`correct._sub_model` cuts it) in float32 at "highest" precision (the same
code paths: the chunk-wise rule over every piece, the state and the tail
handed on at an offset, the state kernel; the values are the served ones
upcast), where nothing but the order of the sums and the stated precision of
the four state einsums separates the program from the reference, and there
the control that the served type's rounding would hide: the state rounded to
bfloat16 on its way into and out of every piece and step. Its mean and worst
distances are held to `FLOAT32_MEAN_LIMIT` and `FLOAT32_MAX_LIMIT`. The last
line printed is one JSON object with every reading and `ok`: the served-type
run within `correct`'s limits and its controls outside one, the float32 run
within both of its own and its control outside one. Not a pytest file: it
needs the chip (on the CPU it runs at the rehearsal's widths with
`--rehearse-cpu`, as a check of its own control flow, and says so).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import types

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


# The most the first period's positions may be off in float32 at "highest"
# precision, as shares of the logit range: the worst position's largest
# distance and the mean distance, the prompt's positions and the decode
# positions each. Each lies between the sound run's reading on the chip and
# the rounded state's (PERF.md section 6, PR 50, two seeds): sound worst 5.5e-5
# to 6.5e-5 (prompt) and 4.4e-5 to 4.9e-5 (decode), mean 6.0e-6 to 6.8e-6 and
# 5.4e-6 to 6.3e-6 (the four state einsums run at "highest" here, so what is
# left is the order of the sums); the state rounded to bfloat16 worst 1.4e-3
# to 1.5e-3 and 8.2e-3 to 9.8e-3, mean 2.6e-5 to 2.8e-5 and 9.9e-4 to 1.0e-3:
# the decode positions, which read the state a step after it was rounded, are
# outside both limits by twenty-seven times and more, the prompt's outside the
# worst position's by four. The control has to fail one of them, the sound
# run none.
FLOAT32_MEAN_LIMIT, FLOAT32_MAX_LIMIT = 2e-5, 3e-4
PERIOD = 4


def kept_positions(pieces, prompt: int, total: int):
    """bool [total]: the positions whose logits are compared."""
    import numpy as np

    p = np.arange(total)
    keep = (p % 8 == 0) | (p >= prompt - 64)
    for off, _take, _bucket in pieces:  # a piece's first rows, and the rows before it
        keep |= (p >= off - 16) & (p < off + 32)
    return keep


def log(*a) -> None:
    print("[delta]", *a, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt", type=int, default=2000)
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
    from omnia_tpu.models import stacks

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
    total = args.prompt + args.decode
    if total > ecfg.max_seq - 2:
        raise SystemExit(f"{total} tokens do not fit the cell's {ecfg.max_seq} rows")
    # The engine's own plan for a prompt longer than its largest bucket.
    pieces = _PlacementMixin._extend_pieces(types.SimpleNamespace(cfg=ecfg), 0, args.prompt)
    log(f"{args.prompt} prompt tokens in {len(pieces)} pieces {[list(p) for p in pieces]}; "
        f"{args.decode} decode steps; layers {mc.attention_kinds}")

    keep = kept_positions(pieces, args.prompt, total)
    kept = jnp.asarray(np.flatnonzero(keep))
    kept_prompt = int(keep[:args.prompt].sum())
    log(f"{int(keep.sum())} of {total} positions compared, {kept_prompt} of them the prompt's")
    tokens = correct._seeded_tokens(mc, args.seed, total)

    programs = {}

    def served(cfg, tree_of, compute=dtype, variant="sound"):
        """The model `cfg` over `tree_of(params)`, the prompt placed in the
        engine's pieces and decoded through a one-slot cache: float32 [kept, V].
        `compute`: the type the tree and its cache are run in (the served
        type; float32 is the same values upcast). `variant` names what is
        patched into the program while it is traced."""
        key = (cfg, jnp.dtype(compute).name, variant)
        if key not in programs:
            def forward(p, cache, toks, start, last):
                tree = jax.tree_util.tree_map(
                    lambda a: a.astype(compute) if a.dtype == dtype else a, tree_of(p))
                pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
                every, *_ = model.forward(tree, cfg, toks, pos, *cache, jnp.reshape(start, (1,)))
                # ... and the cache as a placement writes it: pad rows are not real.
                _, *cache = model.forward(tree, cfg, toks, pos, *cache,
                                          jnp.reshape(start, (1,)), row=last)
                return every, tuple(cache)

            programs[key] = jax.jit(forward, donate_argnums=(1,))
        forward = programs[key]
        cache = tuple(model.init_kv_cache(cfg, 1, ecfg.max_seq, dtype=compute))
        out = []
        plan = list(pieces) + [(t, 1, 1) for t in range(args.prompt, total)]
        for off, take, bucket in plan:
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :take] = tokens[off:off + take]
            logits, cache = forward(params, cache, jnp.asarray(toks), jnp.int32(off),
                                    jnp.int32(take - 1))
            out.append(np.asarray(logits[0, :take], np.float32)[keep[off:off + take]])
        return np.concatenate(out)

    def judged(got, want, limits):
        """`correct.check`'s four numbers and whether each is inside its limit."""
        span = float(want.max() - want.min())
        diff = np.abs(got - want) / span
        out = {"logit_range": span}
        for name, sl in (("prefill", slice(0, kept_prompt)), ("decode", slice(kept_prompt, None))):
            out[f"{name}_max_over_range"] = float(diff[sl].max())
            out[f"{name}_mean_over_range"] = float(diff[sl].mean())
        out["ok"] = bool(np.isfinite(got).all() and all(
            out[f"{name}_{key}"] <= limit
            for name in ("prefill", "decode") for key, limit in limits.items()))
        return out

    whole_ref = np.asarray(jax.jit(lambda p, t: ref_mod.forward(p, sizes, t)[kept])(
        params, jnp.asarray(tokens)), np.float32)
    log("reference done")
    served_limits = {"max_over_range": correct.MAX_TOL, "mean_over_range": correct.MEAN_TOL}
    result = {"sound": judged(served(mc, lambda p: p), whole_ref, served_limits)}
    log("sound:", json.dumps(result["sound"]))

    # Three faults the served type's limits have to refuse, each a change of
    # what the configuration states and none of them of the weights.
    sound_norm = stacks.rms_norm

    def norm_a_head(x, w, eps=1e-5):
        if w.shape[-1] == mc.q_dim == x.shape[-1]:
            heads = x.reshape(*x.shape[:-1], mc.num_heads, mc.head_dim)
            return sound_norm(heads, w[:mc.head_dim], eps).reshape(x.shape)
        return sound_norm(x, w, eps)

    controls = {"beta_in_0_1": (dataclasses.replace(mc, linear_allow_neg_eigval=False), None),
                "norms_in_front": (dataclasses.replace(mc, norm_placement="pre"), None),
                "qk_norm_a_head": (mc, norm_a_head)}
    for name, (cfg, norm) in controls.items():
        stacks.rms_norm = norm or sound_norm
        try:
            result[name] = judged(served(cfg, lambda p: p, variant=name), whole_ref, served_limits)
        finally:
            stacks.rms_norm = sound_norm
        log(f"{name}:", json.dumps(result[name]))
    del whole_ref
    programs.clear()

    # The first period alone, in float32 at "highest" precision.
    first, count, cut = correct._cut(order, 0, min(PERIOD, len(order)))
    first = jax.tree_util.tree_map(jnp.int32, first)
    cut_cfg = correct.cut_config(model, mc, cut)

    def period(p):
        return correct._sub_model(p, p["embed"], first, count, dtype)

    period_ref = np.asarray(jax.jit(lambda p, t: ref_mod.forward(
        period(p), correct._cut_sizes(sizes, cut), t)[kept])(params, jnp.asarray(tokens)),
        np.float32)
    chunked, state_step = stacks.delta_chunked, stacks.decode_delta_state

    def bf16(a):
        """Rounded to bfloat16's eight bits of mantissa. (`astype` there and
        back is removed by the chip's compiler, which may keep excess
        precision: `chip_long_state.py` says how that was found.)"""
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def rounded_chunked(q, k, v, g, beta, S):
        o, S = chunked(q, k, v, g, beta, bf16(S))
        return o, bf16(S)

    def rounded_step(states, *a, **kw):
        o, states = state_step(bf16(states), *a, **kw)
        return o, bf16(states)

    limits = {"mean_over_range": FLOAT32_MEAN_LIMIT, "max_over_range": FLOAT32_MAX_LIMIT}
    for name, patch in (("float32_sound", (chunked, state_step)),
                        ("float32_bfloat16_state", (rounded_chunked, rounded_step))):
        stacks.delta_chunked, stacks.decode_delta_state = patch  # called by these names
        try:
            with jax.default_matmul_precision("highest"):
                got = served(cut_cfg, period, jnp.float32, name)
        finally:
            stacks.delta_chunked, stacks.decode_delta_state = chunked, state_step
        result[name] = judged(got, period_ref, limits)
        log(f"{name}:", json.dumps(result[name]))

    result["float32_limits"] = limits
    result["ok"] = bool(
        result["sound"]["ok"] and not any(result[name]["ok"] for name in controls)
        and result["float32_sound"]["ok"] and not result["float32_bfloat16_state"]["ok"])
    result["run"] = {"workload": cell.name, "seed": args.seed, "prompt": args.prompt,
                     "decode": args.decode, "pieces": [list(p) for p in pieces],
                     "platform": platform, "layers": list(mc.attention_kinds)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"long_delta.{args.seed}.json"), "w") as f:
        json.dump(result, f, indent=1)
    if args.rehearse_cpu:
        log("REHEARSAL line (not a result):", json.dumps(result))
        return 3
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
