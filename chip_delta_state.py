#!/usr/bin/env python3
"""Microbenchmark: the delta layers' decode kernel alone, the state in the
parent's layout beside the packed one, at each block a grid step can take.

One process on one chip. At the served shape of `olmo-hybrid-7b.think-batch`
(six layers' float32 states of 64 slots, 30 heads of 96 × 192, every slot
live) it times ``omnia_tpu/ops/delta.py::decode_delta_state`` with the Pallas
kernel, a call a layer round and round in one program, the state donated
and carried as the decode program carries it:

- ``layout`` "a head a row": ``[6, 64, 30, 96, 192]`` (p = 1, what the cache
  held before the heads were packed: the chip stores a row of 192 lanes in
  256);
- ``layout`` "packed": ``[6, 64, 15, 96, 384]`` (p = 2), once for each number
  of packed heads a grid step's block may hold (``--blocks``; the module's
  ``BLOCK_BYTES`` is set so that ``head_block`` gives that number).

One JSON line a layout and block: µs a call (one layer, every slot) and a
grid step, GB/s of the states' REAL bytes read and written (a state's 30 ×
96 × 192 × 4 twice, whatever the layout moves), with the step vectors built
in front of the kernel (``delta.state``'s scope) and, where the tree has
``_step_vectors``, the kernel alone. ``--check``: outputs and states against
``delta_step`` on the layer unpacked, with dead slots that must stay bit for
bit, and whether a packed layout's outputs and states equal a head a row's
bit for bit (the same float32 operations in the same order). A tree without
``pack_state`` (the parent's) runs its one layout.

    python chip_delta_state.py [--check]      # on the chip
    python chip_delta_state.py --rehearse-cpu # tiny, interpreted, says so

A number of the rehearsal is no measurement. No TPU and no
``--rehearse-cpu`` → exit 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SERVED = (6, 64, 30, 96, 192)   # delta layers, slots, heads, dk, dv
TINY = (2, 3, 12, 8, 64)
#: ``ops/delta.py::BLOCK_BYTES`` as it stood with a head a row (PR 50).
PARENT_BLOCK_BYTES = 5 << 18


def step_inputs(B, H, dk, dv, seed):
    """A step's q, k (unit, q scaled as the model scales it), v, log-decay,
    write strength near 2, and a state that is not zero [B, H, dk, dv]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (B, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, H, dk)))
    v = jax.random.normal(ks[2], (B, H, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, H), minval=np.log(1e-3), maxval=np.log(1.6)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, H)) + 2.0)
    return (q, k, v, g, beta), 0.1 * jax.random.normal(ks[5], (B, H, dk, dv))


def timed(call, state, x, layers: int, rounds: int, iters: int):
    """Seconds a call of ``call(state, x, layer) → (o, state)``: ``rounds``
    rounds over the layers in one program, each call's queries nudged by a
    zero the last one's outputs were summed into, the state donated;
    ``iters`` such programs enqueued behind each other, one wait."""
    import jax
    import jax.numpy as jnp

    def program(state, x):
        def body(i, carry):
            state, zero = carry
            o, state = call(state, (x[0] + zero, *x[1:]), i % layers)
            return state, jnp.sum(o) * 0

        return jax.lax.fori_loop(0, rounds * layers, body, (state, jnp.zeros((), x[0].dtype)))[0]

    program = jax.jit(program, donate_argnums=(0,))
    state = jax.block_until_ready(program(state, x))      # compile
    state = jax.block_until_ready(program(state, x))
    t0 = time.perf_counter()
    for _ in range(iters):
        state = program(state, x)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / (iters * rounds * layers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=8, help="rounds over the layers in one program")
    ap.add_argument("--blocks", type=int, nargs="*", default=None,
                    help="packed heads a block, each tried (default: every divisor of H/p "
                         "from 3 up)")
    ap.add_argument("--check", action="store_true", help="compare with delta_step first")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="a tiny size on the CPU, interpreted; no measurement")
    ap.add_argument("--out", default="chiprun_out/delta_state.jsonl",
                    help="the lines again, for a tool that shows only the output's end")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from omnia_tpu.ops import delta

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print("no TPU here: run through the chip tool, or --rehearse-cpu", file=sys.stderr)
        return 1
    L, B, H, dk, dv = SERVED if on_tpu else TINY
    iters, rounds = (args.iters, args.rounds) if on_tpu else (1, 1)
    packs = hasattr(delta, "pack_state")
    # p as models/stacks.py::state_heads_a_row reads it off the same shapes
    p = next((n for n in range(1, H + 1) if H % n == 0 and n * dv % 128 == 0), 1) if packs else 1
    x, S0 = step_inputs(B, H, dk, dv, args.seed)
    real_bytes = 2 * B * H * dk * dv * 4

    def whole(state, x, layer):
        return delta.decode_delta_state(state, *x, layer, None, kernel=True,
                                        interpret=not on_tpu)

    def cases():
        yield "a head a row", 1, max(
            h for h in range(1, H + 1) if H % h == 0 and h * dk * dv * 4 <= PARENT_BLOCK_BYTES)
        if p > 1:
            for hb in args.blocks or [h for h in range(3, H // p + 1) if H // p % h == 0]:
                yield "packed", p, hb

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    ok, a_head_a_row = True, None
    with open(args.out, "w") as out:
        for layout, n, hb in cases():
            delta.BLOCK_BYTES = hb * dk * n * dv * 4
            jax.clear_caches()                 # head_block is read while tracing
            assert delta.head_block(H // n, dk, n * dv) == hb, (layout, hb)

            def pack(S, n=n):
                return delta.pack_state(S, n) if packs else S

            def fresh_state():
                return jnp.stack([pack(S0 * (1 + i)) for i in range(L)])

            line = {"device": f"{device.platform}:{device.device_kind}", "measured": on_tpu,
                    "seed": args.seed, "layout": layout, "heads_a_row": n,
                    "state": [L, B, H // n, dk, n * dv], "heads_a_block": hb,
                    "block_bytes": hb * dk * n * dv * 4, "grid_steps_a_call": B * (H // n // hb)}
            if args.check:
                live = jnp.arange(B) % 3 != 1
                state = fresh_state()
                o, new = jax.jit(lambda s, x: delta.decode_delta_state(
                    s, *x, jnp.int32(L - 1), live, kernel=True, interpret=not on_tpu))(state, x)
                want_o, want_S = delta.delta_step(S0 * L, *x)
                got_S = delta.unpack_state(new[L - 1], n) if packs else new[L - 1]
                alive = np.asarray(live)
                got = np.asarray(o)[alive], np.asarray(got_S)[alive]
                if n == 1:
                    a_head_a_row = got
                line["check"] = {
                    "equal_to_a_head_a_row": all(map(np.array_equal, got, a_head_a_row)),
                    "outputs_distance": float(jnp.abs(o - want_o)[alive].max()),
                    "state_distance": float(jnp.abs(got_S - want_S)[alive].max()),
                    "dead_slots_untouched": bool(
                        np.array_equal(np.asarray(new[L - 1])[~alive],
                                       np.asarray(state[L - 1])[~alive])
                        and np.array_equal(np.asarray(new[:L - 1]), np.asarray(state[:L - 1])))}
                ok = ok and line["check"]["dead_slots_untouched"] and max(
                    line["check"]["outputs_distance"], line["check"]["state_distance"]) <= 1e-5
            s = timed(whole, fresh_state(), x, L, rounds, iters)
            line.update(us_a_call=s * 1e6, us_a_grid_step=s * 1e6 / line["grid_steps_a_call"],
                        real_gb_s=real_bytes / s / 1e9)
            if hasattr(delta, "_step_vectors"):
                vectors = jax.jit(lambda x, n=n: delta._step_vectors(*x, n))(x)

                def alone(state, x, layer, vectors=vectors, n=n):
                    return delta._state_call(state, vectors, layer, None, p=n,
                                             interpret=not on_tpu)

                s = timed(alone, fresh_state(), x, L, rounds, iters)
                line.update(kernel_us_a_call=s * 1e6,
                            kernel_us_a_grid_step=s * 1e6 / line["grid_steps_a_call"],
                            kernel_real_gb_s=real_bytes / s / 1e9)
            text = json.dumps(line)
            print(text, flush=True)
            out.write(text + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
