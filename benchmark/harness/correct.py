"""The comparison that decides `correct`, part (a): the program's forward
through the cache against the plain reference, on logits.

In set-up, outside the window: a seeded sequence of PREFILL + DECODE
tokens. The program side is `models/llama.py::forward` with the engine's
own parameters, its mesh and the kernel route as served: one prefill of
the first PREFILL tokens into a cache, then DECODE single-token steps
through that cache (teacher-forced with the seeded tokens, because with
random weights the largest logit changes on rounding). The reference side
is one whole-sequence float32 forward (`reference/llama_ref.py`).

Tolerance, as shares of the reference's logit range (max - min): max
|diff| <= 5e-2 and mean |diff| <= 1e-2. PR 22 measured one bf16 run at
0.098-0.124 absolute from an f32 "highest" evaluation on logits of range
~4.6, that is 2.1e-2 to 2.7e-2 of the range at its worst element, mean
3.5e-3 to 4.1e-3 (PERF.md section 6); the bound is about twice that. A
wrong mask, a wrong RoPE, a dropped expert or a cache row out of place
moves logits by tenths of the range, far outside it.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

PREFILL, DECODE, CACHE_ROWS = 128, 8, 256
MAX_TOL, MEAN_TOL = 5e-2, 1e-2

_REF_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "reference")


def _reference_forward():
    if _REF_DIR not in sys.path:
        sys.path.insert(0, _REF_DIR)
    import llama_ref

    return llama_ref.forward


def check(engine, model_cfg, sizes: dict, seed: int) -> dict:
    from omnia_tpu.models import llama
    from omnia_tpu.parallel import init_sharded

    mesh = engine._mesh  # the mesh the engine's parameters live on
    dtype = engine.params["embed"].dtype
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC0FFEE])
    tokens = rng.integers(0, model_cfg.vocab_size, size=PREFILL + DECODE).astype(np.int32)

    ck, cv = init_sharded(
        lambda: llama.init_kv_cache(model_cfg, 1, CACHE_ROWS, dtype=dtype),
        llama.kv_cache_specs(None), mesh)

    @jax.jit
    def step(params, ck, cv, toks, start):
        pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
        return llama.forward(params, model_cfg, toks, pos, ck, cv,
                             jnp.reshape(start, (1,)), mesh=mesh)

    logits, ck, cv = step(engine.params, ck, cv, jnp.asarray(tokens[None, :PREFILL]),
                          jnp.int32(0))
    served = [np.asarray(logits[0], np.float32)]
    for i in range(PREFILL, PREFILL + DECODE):
        logits, ck, cv = step(engine.params, ck, cv,
                              jnp.asarray(tokens[None, i:i + 1]), jnp.int32(i))
        served.append(np.asarray(logits[0], np.float32))
    served = np.concatenate(served, axis=0)

    forward = _reference_forward()
    ref = jax.jit(lambda params, toks: forward(params, sizes, toks))(
        engine.params, jnp.asarray(tokens))
    ref = np.asarray(ref, np.float32)

    rng_ = float(ref.max() - ref.min())
    diff = np.abs(served - ref)
    out = {"logit_range": rng_}
    for name, sl in (("prefill", slice(0, PREFILL)), ("decode", slice(PREFILL, None))):
        out[f"{name}_max_over_range"] = float(diff[sl].max() / rng_)
        out[f"{name}_mean_over_range"] = float(diff[sl].mean() / rng_)
    out["ok"] = bool(
        np.isfinite(served).all()
        and max(out["prefill_max_over_range"], out["decode_max_over_range"]) <= MAX_TOL
        and max(out["prefill_mean_over_range"], out["decode_mean_over_range"]) <= MEAN_TOL
    )
    return out

