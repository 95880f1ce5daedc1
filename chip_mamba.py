"""The selective scan's two kernels alone, on one chip (about two minutes):

- `decode_mamba_state` at the served shape of `jamba2-3b.reason-wide`, 26
  layers x 256 slots x 16 x 5120 float32, a call a layer round and round in
  one program: µs a call, GB/s of the states' real bytes (read and written)
  against HBM's peak, every slot live and one slot in eight dead;
- a prompt's piece through `mamba_scan` (the Pallas kernel) beside
  `mamba_chunked` (plain jax.numpy) at 256 and 1,024 tokens: ms a layer's
  call, and the distance of each to `mamba_recurrent`.

One JSON line a case (also `chiprun_out/mamba.jsonl`). `--check` holds the
state kernel to `mamba_step` with dead slots; `--rehearse-cpu` runs the
control flow tiny and interpreted and reports no time."""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from omnia_tpu.ops import mamba

HBM = 819e9


def _inputs(B, T, N, E, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    u = jax.random.normal(ks[0], (B, T, E))
    dt = jnp.exp(jax.random.uniform(ks[1], (B, T, E), minval=np.log(1e-3), maxval=np.log(0.1)))
    Bv, Cv = jax.random.normal(ks[2], (B, T, N)), jax.random.normal(ks[3], (B, T, N))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, E))
    return u, dt, Bv, Cv, A, jnp.ones((E,)), 0.1 * jax.random.normal(ks[4], (B, N, E))


def _time(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - start)
    return best, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    tiny = args.rehearse_cpu
    if not tiny and jax.default_backend() != "tpu":
        raise SystemExit("no TPU: --rehearse-cpu runs the control flow on the CPU")
    L, B, N, E = (3, 16, 16, 256) if tiny else (26, 256, 16, 5120)
    lines = []

    def say(**line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    # -- the state kernel, a call a layer ----------------------------------------
    u, dt, Bv, Cv, A, D, _ = _inputs(B, 1, N, E)
    step = [a[:, 0] for a in (u, dt, Bv, Cv)]
    for name, live in (("every slot live", jnp.ones((B,), bool)),
                       ("one slot in eight dead", jnp.arange(B) % 8 != 3)):
        def layers(state, live=live):
            def body(l, carry):
                state, acc = carry
                y, state = mamba.decode_mamba_state(state, *step, A, D, l, live, kernel=True,
                                                    interpret=tiny)
                return state, acc + y
            return jax.lax.fori_loop(0, L, body, (state, jnp.zeros((B, E))))

        run = jax.jit(layers, donate_argnums=0)
        state = 0.1 * jax.random.normal(jax.random.key(1), (L, B, N, E))
        if args.check or tiny:
            want_y, want = mamba.decode_mamba_state(state, *step, A, D, 1, live)
            y, got = mamba.decode_mamba_state(state, *step, A, D, 1, live, kernel=True,
                                              interpret=tiny)
            alive = np.asarray(live)
            assert np.abs(np.asarray(y - want_y))[alive].max() < 1e-4, name
            assert np.abs(np.asarray(got - want)).max() < 1e-5, name
            assert np.array_equal(np.asarray(got[1])[~alive], np.asarray(state[1])[~alive]), name
        best = float("inf")
        for _ in range(4):
            start = time.perf_counter()
            state, acc = run(state)
            jax.block_until_ready(acc)
            best = min(best, time.perf_counter() - start)
        moved = int(live.sum()) * 2 * N * E * 4
        say(case="decode_mamba_state", slots=name, layers=L, B=B,
            us_a_call=None if tiny else best / L * 1e6,
            gb_s=None if tiny else moved / (best / L) / 1e9,
            share_of_hbm=None if tiny else moved / (best / L) / HBM)

    # -- a piece: the kernel beside the plain scan -----------------------------------
    for T in ((128,) if tiny else (256, 1024)):
        ops = _inputs(1, T, N, E, seed=T)
        want_y, want_S = mamba.mamba_recurrent(*ops)
        for route, fn in (("mamba_scan", lambda *a: mamba.mamba_scan(*a, interpret=tiny)),
                          ("mamba_chunked", jax.jit(mamba.mamba_chunked))):
            seconds, (y, S) = _time(fn, *ops)
            say(case=route, T=T, ms_a_call=None if tiny else seconds * 1e3,
                y_err=float(jnp.abs(y - want_y).max()), s_err=float(jnp.abs(S - want_S).max()))
            assert float(jnp.abs(y - want_y).max()) < 1e-3, (route, T)

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mamba.jsonl", "w") as f:
        f.writelines(json.dumps(line) + "\n" for line in lines)
    if tiny:
        print("REHEARSAL on the CPU: no time is a result")


if __name__ == "__main__":
    main()
