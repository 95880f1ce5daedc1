#!/usr/bin/env python3
"""Microbenchmark: one linear-attention layer's chunk-wise rule over a
prompt's piece, the parent's form against the tree's.

One process on one chip. At the served shape of
`kimi-linear-48b-a3b.longdoc-wide` (one slot, a piece of 1,024 tokens, 32
heads of 128 × 128, float32, a state that is not zero; two draws, ``DRAWS``:
`tests/test_kimi_linear.py::_rule_inputs`' and one whose keys share a
direction under a weak decay) it times ``omnia_tpu/ops/kda.py::kda_chunked``
as `models/mla.py::_kda_layer` calls it, beside a frozen copy of what it was before the block form (``_parent_chunk``:
the `[C, C, dk]` pairwise decays of a whole chunk in one reduction on the
vector unit, then `jax.scipy.linalg.solve_triangular`). One JSON line a
draw and form: milliseconds a piece, and the largest distance of its outputs
and of the state it leaves from ``kda_recurrent``'s (one token a step,
float32 at ``HIGHEST``), at the program's own precision and with "highest"
forced on every matmul (what is left then is the order of the sums).

    python chip_kda_chunk.py                 # on the chip
    python chip_kda_chunk.py --rehearse-cpu  # tiny, says so

A number of the rehearsal is no measurement. No TPU and no
``--rehearse-cpu`` → exit 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SERVED = (1, 1024, 32, 128)    # slots, a piece's tokens, heads, dk = dv
TINY = (1, 128, 2, 16)


def _parent_chunk(S, x):
    """`ops/kda.py::_chunk` as it stood before the block form (PR 43–48),
    kept here to be measured against: S [B, H, dk, dv]; q, k, g [B, H, C,
    dk]; v [B, H, C, dv]; beta [B, H, C] → (S_C, o [B, H, C, dv])."""
    import jax
    import jax.numpy as jnp

    q, k, v, g, beta = x
    C = q.shape[2]
    G = jnp.cumsum(g, axis=2)
    i = jnp.arange(C)
    lower = i[:, None] >= i[None, :]
    diff = G[:, :, :, None, :] - G[:, :, None, :, :]            # [B, H, C, C, dk]
    decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
    both = jnp.stack([q, k], axis=2)
    AB = jnp.sum(both[:, :, :, :, None, :] * k[:, :, None, None, :, :]
                 * decay[:, :, None], axis=-1)                  # [B, H, 2, C, C]
    Bq, A = AB[:, :, 0], AB[:, :, 1]
    strict = i[:, None] > i[None, :]
    M = jnp.eye(C, dtype=jnp.float32) + jnp.where(strict, beta[..., None] * A, 0.0)
    eG = jnp.exp(G)
    rhs = beta[..., None] * (v - jnp.einsum("bhck,bhkv->bhcv", k * eG, S))
    U = jax.scipy.linalg.solve_triangular(M, rhs, lower=True, unit_diagonal=True)
    o = jnp.einsum("bhck,bhkv->bhcv", q * eG, S) + jnp.einsum("bhij,bhjv->bhiv", Bq, U)
    last = G[:, :, -1:, :]
    S = (jnp.exp(last[:, :, 0, :, None]) * S
         + jnp.einsum("bhck,bhcv->bhkv", k * jnp.exp(last - G), U))
    return S, o


def parent_chunked(q, k, v, g, beta, S0, chunk: int = 64):
    """The parent's `kda_chunked` over a whole number of chunks."""
    import jax
    import jax.numpy as jnp

    B, T, H, _ = q.shape
    C = min(chunk, T)

    def chunks(a):  # [B, T, H, ...] → [T / C, B, H, C, ...]
        a = a.astype(jnp.float32).reshape(B, T // C, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    S, o = jax.lax.scan(_parent_chunk, S0.astype(jnp.float32),
                        tuple(map(chunks, (q, k, v, g, beta))))
    return jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(B, T, H, -1), S


# draws: (mean of the channels that queries and keys are normalised from,
# least and largest decay a token and a channel). The first is `tests/
# test_kimi_linear.py::_rule_inputs`'; in the second two keys' product is 0.9
# in the mean and hardly anything decays inside a chunk, so Diag(β)·A has
# entries near 1 all over its triangle: what an inverse has to stand.
DRAWS = {"seeded": (0.0, 1e-3, 1.6), "shared direction": (3.0, 1e-4, 1e-2)}


def rule_inputs(B, T, H, d, seed, shared, least, largest):
    """Unit keys, queries scaled as the model scales them, log-decays
    uniform in the logarithm, write strengths in (0, 1), a normal state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (B, T, H, d)) + shared) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, d)) + shared)
    v = jax.random.normal(ks[2], (B, T, H, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, d), minval=np.log(least),
                                    maxval=np.log(largest)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, H, d, d))


def timed(fn, args, iters: int, chain: int):
    """Milliseconds a call: ``chain`` calls in one program, each one's
    queries nudged by a zero the last one's results were summed into (a
    call of a millisecond is otherwise timed by the host's dispatch),
    ``iters`` such programs enqueued behind each other, one wait."""
    import jax
    import jax.numpy as jnp

    def program(q, *rest):
        def step(zero, _):
            o, S = fn(q + zero, *rest)
            return (jnp.sum(o) + jnp.sum(S)) * 0, None

        return jax.lax.scan(step, jnp.zeros((), q.dtype), None, length=chain)[0]

    program = jax.jit(program)
    jax.block_until_ready(program(*args))      # compile
    jax.block_until_ready(program(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = program(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (iters * chain) * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--chain", type=int, default=8,
                    help="calls of the rule in one program")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="a tiny size on the CPU; no measurement")
    ap.add_argument("--out", default="chiprun_out/kda_chunk.jsonl",
                    help="the lines again, for a tool that shows only the output's end")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from omnia_tpu.ops import kda

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print("no TPU here: run through the chip tool, or --rehearse-cpu", file=sys.stderr)
        return 1
    B, T, H, d = SERVED if on_tpu else TINY
    iters, chain = (args.iters, args.chain) if on_tpu else (1, 2)

    def distances(fn, x, o_ref, S_ref):
        o, S = jax.jit(fn)(*x)
        return float(jnp.abs(o - o_ref).max()), float(jnp.abs(S - S_ref).max())

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as out:
        for draw, params in DRAWS.items():
            x = rule_inputs(B, T, H, d, args.seed, *params)
            o_ref, S_ref = jax.jit(kda.kda_recurrent)(*x)
            for form, fn in (("parent", parent_chunked), ("tree", kda.kda_chunked)):
                ms = timed(fn, x, iters, chain)
                at_default = distances(fn, x, o_ref, S_ref)
                with jax.default_matmul_precision("highest"):
                    at_highest = distances(fn, x, o_ref, S_ref)
                text = json.dumps({
                    "device": f"{device.platform}:{device.device_kind}", "measured": on_tpu,
                    "seed": args.seed, "draw": draw, "form": form, "shape": [B, T, H, d],
                    "ms_a_piece": ms,
                    "outputs_distance": at_default[0], "state_distance": at_default[1],
                    "outputs_distance_highest": at_highest[0],
                    "state_distance_highest": at_highest[1],
                    "outputs_largest": float(jnp.abs(o_ref).max()),
                    "state_largest": float(jnp.abs(S_ref).max())})
                print(text, flush=True)
                out.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
