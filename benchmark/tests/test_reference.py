"""The plain reference against `models/llama.py::forward` on the CPU at tiny
widths: prefill, then decode through the cache.

Tolerance: both sides run in float32 here (parameters made in float32), so
they differ only in the order of summation: 1e-4 of the logit range, about
a thousand float32 ulps over a few thousand-term dot products. A wrong
mask, RoPE convention, GQA grouping or expert weighting is off by tenths
of the range. (On the chip the served side is bfloat16 and the bound is
the one written in harness/correct.py.) At `test-tiny-moe` the prefill of
72 tokens takes `moe_dispatch`, whose capacity is ceil(N x 2 x 2 / 4) = N,
so no assignment can be dropped there; the reference drops nothing ever.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness.manifest import BENCH_DIR, reference_sizes
from omnia_tpu.models import llama
from omnia_tpu.models.config import get_config

sys.path.insert(0, os.path.join(BENCH_DIR, "reference"))
import llama_ref  # noqa: E402

PREFILL, DECODE = 72, 6


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-gqa8", "test-tiny-moe"])
def test_reference_agrees_with_served_forward(preset):
    cfg = get_config(preset)
    params = llama.init_params(cfg, jax.random.key(5), dtype=jnp.float32)
    tokens = np.random.default_rng(11).integers(0, cfg.vocab_size, PREFILL + DECODE)
    tokens = tokens.astype(np.int32)
    ck, cv = llama.init_kv_cache(cfg, 1, 128, dtype=jnp.float32)

    def step(ck, cv, toks, start):
        pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
        return llama.forward(params, cfg, toks, pos, ck, cv, jnp.array([start]))

    logits, ck, cv = step(ck, cv, jnp.asarray(tokens[None, :PREFILL]), 0)
    served = [np.asarray(logits[0])]
    for i in range(PREFILL, PREFILL + DECODE):
        logits, ck, cv = step(ck, cv, jnp.asarray(tokens[None, i:i + 1]), i)
        served.append(np.asarray(logits[0]))
    served = np.concatenate(served)
    ref = np.asarray(llama_ref.forward(params, reference_sizes(cfg), jnp.asarray(tokens)))
    span = ref.max() - ref.min()
    assert np.abs(served - ref).max() / span < 1e-4


def test_reference_is_not_fooled_by_a_wrong_cache_row():
    """The comparison has teeth: shift one decode position and it fails."""
    cfg = get_config("test-tiny")
    params = llama.init_params(cfg, jax.random.key(5), dtype=jnp.float32)
    tokens = jnp.asarray(np.arange(40, dtype=np.int32) % cfg.vocab_size)
    ref = np.asarray(llama_ref.forward(params, reference_sizes(cfg), tokens))
    ck, cv = llama.init_kv_cache(cfg, 1, 128, dtype=jnp.float32)
    pos = jnp.arange(40, dtype=jnp.int32)[None, :] + 1  # off by one position
    logits, _, _ = llama.forward(params, cfg, tokens[None], pos, ck, cv, jnp.array([1]))
    span = ref.max() - ref.min()
    assert np.abs(np.asarray(logits[0]) - ref).max() / span > 1e-3
