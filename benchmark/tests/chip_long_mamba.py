#!/usr/bin/env python3
"""The builder's long comparison for the pair family's model with state-space
layers in the slot's cache, on the chip (ISSUE 54), after `chip_long_delta.py`:
`harness/correct.py` runs 128 + 8 tokens through one slot in one piece, so it
never hands a state or a convolution's tail from piece to piece, never pads a
piece, never starts a piece at an offset and never leaves a slot dead. This
takes the cell's own sizes instead:

    chiprun --chips 1 --timeout 2400 -- python3 benchmark/tests/chip_long_mamba.py \
        --workload jamba2-3b.reason-wide --seed <n> [--prompt 2400] [--decode 64]

A prompt of `--prompt` tokens placed piece by piece exactly as
`engine/placement.py::_extend_pieces` cuts it for the cell's buckets (2,400:
1,024 + 1,024 + 352, the last padded to 512 and named by its last real row as
`engine/programs.py::extend` names it, the second and third at an offset), then
`--decode` single-token steps through the cache with the kernels as served
(`mamba_scan` over every piece, `decode_mamba_state`, `decode_gqa_attention` at
a group of 20 on one KV head, the blocked prefill kernel), in a one-slot cache
of the cell's rows that ANOTHER TENANT HAS LEFT FULL (every array + 3). Every
token is run; the logits compared are those of the positions `kept_positions`
names. The model is dense, so it is judged whole as `correct` judges it: the
program's logits against the configuration's own reference in float32 at
"highest" precision over the whole sequence, the prompt's positions and the
decode positions each within `correct.MAX_TOL` and `correct.MEAN_TOL` of the
logit range. Two controls at the served type have to fail those limits, a slot
rule each: the pad rows of the padded piece taken for real (they enter the
state and the tail), and a first piece that keeps what the last tenant left
(the state and the tail not zeroed at position 0). The third slot rule has a
section of its own: a decode step over sixteen slots of which some are dead,
in a group of eight that the state kernel visits and in one that it does not,
leaves their states and tails bit for bit, and with `live` not passed on (the
fault) it does not.

Then layers 5 to 8 alone (M M A M on the real embedding table, cut as
`correct._sub_model` cuts it: both joins) in float32 at "highest" precision
(the same code paths and kernels; the values are the served ones upcast), where
nothing but the order of the sums separates the program from the reference,
and there the control that the served type's rounding would hide: the state
rounded to bfloat16 on its way into and out of every piece and step. Its mean
and worst distances are held to `FLOAT32_MEAN_LIMIT` and `FLOAT32_MAX_LIMIT`.
The last line printed is one JSON object with every reading and `ok`. Not a
pytest file: it needs the chip (on the CPU it runs at the rehearsal's widths
with `--rehearse-cpu`, as a check of its own control flow, and says so).
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


# The most layers 5 to 8's positions may be off in float32 at "highest"
# precision, as shares of the logit range: the worst position's largest
# distance and the mean distance, the prompt's positions and the decode
# positions each. Each lies between the sound run's reading on the chip and
# the rounded state's (PERF.md section 6, PR 54, seed 3540000101): sound worst
# 6.9e-7 (prompt) and 6.8e-7 (decode), mean 8.2e-8 and 9.8e-8 (the scan is the
# reference's arithmetic in its order and the projections run at "highest", so
# what is left is the order of the matmuls' sums); the state rounded to
# bfloat16 worst 3.6e-4 and 2.0e-3, mean 8.0e-6 and 1.9e-4. The limits stand
# forty and thirty times over the sound readings and twelve and 2.7 times
# under the control's nearest (the prompt's, which reads the state through one
# rounding a piece; the decode positions, which read it a step after it was
# rounded, are outside by sixty times). The control has to fail one of them,
# the sound run none.
FLOAT32_MEAN_LIMIT, FLOAT32_MAX_LIMIT = 3e-6, 3e-5
CUT_FROM, CUT_DEPTH = 5, 4


def kept_positions(pieces, prompt: int, total: int):
    """bool [total]: the positions whose logits are compared: the rows on
    either side of every piece's boundary, every eighth row between, the
    prompt's last rows and every decode step (at a vocabulary of 65536 every
    position's logits several times over are more than the host holds)."""
    import numpy as np

    p = np.arange(total)
    keep = (p % 8 == 0) | (p >= prompt - 64)
    for off, _take, _bucket in pieces:  # a piece's first rows, and the rows before it
        keep |= (p >= off - 16) & (p < off + 32)
    return keep


def log(*a) -> None:
    print("[mamba]", *a, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt", type=int, default=2400)
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

    def served(cfg, tree_of, compute=dtype, variant="sound", pad_is_real=False):
        """The model `cfg` over `tree_of(params)`, the prompt placed in the
        engine's pieces and decoded through a one-slot cache that the last
        tenant left full: float32 [kept, V]. `compute`: the type the tree and
        its cache are run in (the served type; float32 is the same values
        upcast). `variant` names what is patched into the program while it is
        traced; `pad_is_real` writes the cache with no last real row named."""
        key = (cfg, jnp.dtype(compute).name, variant)
        if key not in programs:
            def forward(p, cache, toks, start, last):
                tree = jax.tree_util.tree_map(
                    lambda a: a.astype(compute) if a.dtype == dtype else a, tree_of(p))
                pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
                every, *_ = model.forward(tree, cfg, toks, pos, *cache, jnp.reshape(start, (1,)))
                # ... and the cache as a placement writes it: pad rows are not real.
                _, *cache = model.forward(tree, cfg, toks, pos, *cache,
                                          jnp.reshape(start, (1,)),
                                          row=None if pad_is_real else last)
                return every, tuple(cache)

            programs[key] = jax.jit(forward, donate_argnums=(1,))
        forward = programs[key]
        cache = tuple(c + 3 for c in model.init_kv_cache(cfg, 1, ecfg.max_seq, dtype=compute))
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

    # Two slot rules broken, each a fault the served type's limits have to refuse.
    sound_mixer = stacks._mamba_mixer

    def never_fresh(h, a, cfg, cache, cache_layer, write_start, n_real, live):
        return sound_mixer(h, a, cfg, cache, cache_layer, write_start + 1, n_real, live)

    controls = {"pad_rows_enter_the_state": (sound_mixer, True),
                "first_piece_keeps_the_last_tenants": (never_fresh, False)}
    for name, (mixer, pad_is_real) in controls.items():
        stacks._mamba_mixer = mixer
        try:
            result[name] = judged(served(mc, lambda p: p, variant=name, pad_is_real=pad_is_real),
                                  whole_ref, served_limits)
        finally:
            stacks._mamba_mixer = sound_mixer
        log(f"{name}:", json.dumps(result[name]))
    del whole_ref
    programs.clear()

    # The third slot rule: a decode step leaves a dead slot's state and tail
    # bit for bit, in a group of eight the state kernel visits and in one it
    # does not; with `live` not passed on it does not.
    slots = 16
    live = np.arange(slots) % 3 != 1
    live[8:] = False
    cache = tuple(c + 3 for c in model.init_kv_cache(mc, slots, 256, dtype=dtype))

    def step(p, live, *cache):
        at = jnp.full((slots,), 11, jnp.int32)
        return model.forward(p, mc, jnp.asarray(tokens[:slots, None]), at[:, None], *cache, at,
                             live=live)[1:]

    _, _, states, tails = jax.jit(step)(params, jnp.asarray(live), *cache)
    _, _, wrong_states, wrong_tails = jax.jit(lambda p, *c: step(p, None, *c))(params, *cache)
    dead = ~live
    result["dead_slots"] = {
        "dead_untouched": bool(np.all(np.asarray(states)[:, dead] == 3.0)
                               and np.all(np.asarray(tails.astype(jnp.float32))[:, dead] == 3.0)),
        "live_updated": bool(np.all(np.abs(np.asarray(states)[:, live] - 3.0).max((0, 2, 3)) > 0)),
        "fault_touches_them": bool(np.abs(np.asarray(wrong_states)[:, dead] - 3.0).max() > 0
                                   and np.abs(np.asarray(wrong_tails.astype(jnp.float32))[:, dead]
                                              - 3.0).max() > 0)}
    result["dead_slots"]["ok"] = all(result["dead_slots"].values())
    log("dead_slots:", json.dumps(result["dead_slots"]))
    del cache, states, tails, wrong_states, wrong_tails

    # Layers 5 to 8 alone, in float32 at "highest" precision.
    first, count, cut = correct._cut(order, min(CUT_FROM, len(order) - 1),
                                     min(CUT_DEPTH, len(order) - min(CUT_FROM, len(order) - 1)))
    first = jax.tree_util.tree_map(jnp.int32, first)
    cut_cfg = correct.cut_config(model, mc, cut)

    def period(p):
        return correct._sub_model(p, p["embed"], first, count, dtype)

    period_ref = np.asarray(jax.jit(lambda p, t: ref_mod.forward(
        period(p), correct._cut_sizes(sizes, cut), t)[kept])(params, jnp.asarray(tokens)),
        np.float32)
    chunked, scan, state_step = (stacks.mamba_chunked, stacks.mamba_scan,
                                 stacks.decode_mamba_state)

    def bf16(a):
        """Rounded to bfloat16's eight bits of mantissa. (`astype` there and
        back is removed by the chip's compiler, which may keep excess
        precision: `chip_long_state.py` says how that was found.)"""
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def rounded(rule):
        def piece(u, dt, Bv, Cv, A, D, S, **how):
            y, S = rule(u, dt, Bv, Cv, A, D, bf16(S), **how)
            return y, bf16(S)
        return piece

    def rounded_step(states, *a, **kw):
        y, states = state_step(bf16(states), *a, **kw)
        return y, bf16(states)

    limits = {"mean_over_range": FLOAT32_MEAN_LIMIT, "max_over_range": FLOAT32_MAX_LIMIT}
    for name, patch in (("float32_sound", (chunked, scan, state_step)),
                        ("float32_bfloat16_state", (rounded(chunked), rounded(scan),
                                                    rounded_step))):
        # (called by these names)
        stacks.mamba_chunked, stacks.mamba_scan, stacks.decode_mamba_state = patch
        try:
            with jax.default_matmul_precision("highest"):
                got = served(cut_cfg, period, jnp.float32, name)
        finally:
            stacks.mamba_chunked, stacks.mamba_scan, stacks.decode_mamba_state = (
                chunked, scan, state_step)
        result[name] = judged(got, period_ref, limits)
        log(f"{name}:", json.dumps(result[name]))

    result["float32_limits"] = limits
    result["ok"] = bool(
        result["sound"]["ok"] and not any(result[name]["ok"] for name in controls)
        and result["dead_slots"]["ok"]
        and result["float32_sound"]["ok"] and not result["float32_bfloat16_state"]["ok"])
    result["run"] = {"workload": cell.name, "seed": args.seed, "prompt": args.prompt,
                     "decode": args.decode, "pieces": [list(p) for p in pieces],
                     "platform": platform,
                     "float32_layers": list(cut_cfg.attention_kinds)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"long_mamba.{args.seed}.json"), "w") as f:
        json.dump(result, f, indent=1)
    if args.rehearse_cpu:
        log("REHEARSAL line (not a result):", json.dumps(result))
        return 3
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
