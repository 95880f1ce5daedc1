"""Vectorized token sampling: temperature / top-k / top-p / greedy.

All knobs — temperature, top_p, AND top_k — are per-row *dynamic* values, so
one compiled decode step serves heterogeneous requests in the same
continuous batch (the point of slot-based serving: no per-request shape
specialization). top_k is implemented as a threshold gathered from the
descending sort that top_p already pays for, which keeps it dynamic without
a second sort or a static lax.top_k shape.

Greedy is expressed as temperature <= 0 and resolved per row with
jnp.where; what the whole batch pays for is decided on the device by
``lax.cond`` over the same per-row values (``_gated_sample``): a batch in
which no row samples takes the argmax and nothing else, one in which no
sampling row filters skips the thresholds. No Python branching, so the
step stays one traceable program.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


# Decode sampling is on the per-token critical path: a full-vocab sort
# (O(V log² V) bitonic passes on TPU, V = 128k) per step can rival the
# model forward once dispatch overhead is amortized. The threshold only
# needs the DESCENDING PREFIX of the distribution, so the fast path uses
# lax.top_k over this many entries and falls back to the exact full-sort
# path (one lax.cond) whenever any row's answer could lie past the
# prefix — semantics are bit-identical either way.
_FAST_PREFIX_K = 256


def _thresholds_from_prefix(prefix: jnp.ndarray, denom: jnp.ndarray,
                            m: jnp.ndarray, top_p: jnp.ndarray,
                            k: jnp.ndarray):
    """Shared threshold math over a descending prefix of the scaled
    logits. prefix: [B, K] descending; denom: [B] total survivor mass in
    exp(x - m) units; m: [B] row max; k: [B] effective top-k (0 = off).
    Returns [B, 1] threshold."""
    K = prefix.shape[-1]
    kth = jnp.take_along_axis(
        prefix, jnp.clip(k - 1, 0, K - 1)[:, None], axis=-1
    )
    k_thresh = jnp.where((k > 0)[:, None], kth, _NEG_INF)

    in_topk = jnp.arange(K)[None, :] < jnp.where(k > 0, k, K)[:, None]
    e = jnp.where(in_topk, jnp.exp(prefix - m[:, None]), 0.0)
    cum = jnp.cumsum(e, axis=-1)
    # mass strictly before each entry < top_p * survivor mass
    keep = in_topk & ((cum - e) < top_p[:, None] * denom[:, None])
    p_thresh = jnp.min(jnp.where(keep, prefix, jnp.inf), axis=-1, keepdims=True)
    return jnp.maximum(k_thresh, p_thresh)


def _filter_thresholds(scaled: jnp.ndarray, top_p: jnp.ndarray, top_k: jnp.ndarray):
    """Per-row admission threshold combining top-k and top-p (nucleus).

    Sequential-filter semantics (the HF/vLLM convention): top-k first, then
    the nucleus is computed over the *renormalized top-k survivors* — so
    top_p admits the smallest prefix of the top-k set whose renormalized
    mass reaches top_p.

    scaled: [B, V] temperature-scaled logits; top_p: [B] (>= 1 disables);
    top_k: [B] int32 (<= 0 disables). Returns [B, 1] threshold.
    """
    V = scaled.shape[-1]
    k = jnp.clip(top_k, 0, V)
    K = min(_FAST_PREFIX_K, V)

    # Descending prefix + survivor-mass denominators (no sort needed).
    prefix, _idx = jax.lax.top_k(scaled, K)
    m = prefix[:, 0]
    e_prefix = jnp.exp(prefix - m[:, None])
    cum_prefix = jnp.cumsum(e_prefix, axis=-1)
    z_all = jnp.sum(jnp.exp(scaled - m[:, None]), axis=-1)
    k_in_prefix = (k > 0) & (k <= K)
    denom = jnp.where(
        k_in_prefix,
        jnp.take_along_axis(
            cum_prefix, jnp.clip(k - 1, 0, K - 1)[:, None], axis=-1
        )[:, 0],
        z_all,
    )
    # Rows with BOTH knobs off (the SamplingParams defaults) admit the
    # whole vocabulary: no threshold to find, trivially fast-feasible —
    # without this exemption one default-params request in the batch
    # would force every decode step onto the full sort.
    no_filter = (top_p >= 1.0) & (k <= 0)

    def fast(_):
        th = _thresholds_from_prefix(prefix, denom, m, top_p, k)
        # A prefix-only computation would wrongly cut unfiltered rows at
        # the K-th value; force their threshold open.
        return jnp.where(no_filter[:, None], _NEG_INF, th)

    def slow(_):
        sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
        # Survivor mass from the SAME sorted cumsum the keep-comparison
        # uses (not z_all): a different summation order can differ by an
        # ulp, which at top_p=1.0 would wrongly exclude the final
        # element (cum - e < top_p*denom must hold for every survivor).
        cum_full = jnp.cumsum(jnp.exp(sorted_desc - m[:, None]), axis=-1)
        denom_full = jnp.where(
            k > 0,
            jnp.take_along_axis(
                cum_full, jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1
            )[:, 0],
            cum_full[:, -1],
        )
        return _thresholds_from_prefix(sorted_desc, denom_full, m, top_p, k)

    if K == V:
        # top_k(V) already IS the full sort; no fallback needed.
        return fast(None)
    # Fast path is exact iff every row is one of: unfiltered (exempt),
    # top-k cutoff inside the prefix, or nucleus threshold inside it
    # (prefix mass under the survivor distribution reaches top_p).
    feasible = jnp.all(
        no_filter
        | (k_in_prefix  # survivors ⊂ prefix ⇒ threshold in prefix
           | ((k <= 0) & (cum_prefix[:, -1] >= top_p * z_all)))
    )
    return jax.lax.cond(feasible, fast, slow, None)


def fast_path_feasible(scaled, top_p, top_k) -> bool:
    """Test/diagnostic hook: would _filter_thresholds take the prefix
    fast path for this batch? Mirrors the feasibility predicate above."""
    V = scaled.shape[-1]
    K = min(_FAST_PREFIX_K, V)
    if K == V:
        return True
    k = jnp.clip(jnp.asarray(top_k, jnp.int32), 0, V)
    top_p = jnp.asarray(top_p, jnp.float32)
    prefix, _ = jax.lax.top_k(jnp.asarray(scaled, jnp.float32), K)
    m = prefix[:, 0]
    cum_last = jnp.sum(jnp.exp(prefix - m[:, None]), axis=-1)
    z_all = jnp.sum(jnp.exp(jnp.asarray(scaled, jnp.float32) - m[:, None]), axis=-1)
    no_filter = (top_p >= 1.0) & (k <= 0)
    k_in_prefix = (k > 0) & (k <= K)
    return bool(jnp.all(
        no_filter | (k_in_prefix | ((k <= 0) & (cum_last >= top_p * z_all)))
    ))


def _gated_sample(logits, temperature, top_p, top_k, mask_bias, draw):
    """The sampler's one piece of mathematics, gated on what some row of
    the batch asks for. Two nested ``lax.cond``s in front of the
    fast/slow one in ``_filter_thresholds``, decided on the device from
    the per-row parameters alone:

    1. no row samples (every ``temperature <= 0``, which is also what a
       slot that never ran or has ended holds) → the argmax, nothing else;
    2. some row samples but no SAMPLING row filters (the SamplingParams
       defaults; a greedy row's ``top_p`` asks for nothing) → Gumbel
       argmax with the threshold open: no top_k, no prefix arithmetic;
    3. else the thresholds.

    A row's token does not depend on the branch its batch takes: a
    greedy row always gets ``argmax(logits + mask_bias)``, and a sampling
    row holds the outer gate open in every step in which it is live.
    ``draw(filtered [B, V]) -> int32 [B]`` is the Gumbel argmax with the
    caller's keys. Returns int32 [B]."""
    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    if mask_bias is not None:
        # Grammar-constrained decoding (engine/grammar): additive mask,
        # 0 for admissible tokens / -inf for masked. Applied BEFORE the
        # greedy argmax and the filter thresholds so every path —
        # greedy, top-k, top-p — samples inside the grammar.
        logits = logits + mask_bias
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    sampling = temperature > 0.0
    filtering = sampling & ((top_p < 1.0) | (top_k > 0))

    def greedy(_):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sample(_):
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        thresh = jax.lax.cond(
            jnp.any(filtering),
            lambda _: _filter_thresholds(scaled, top_p, top_k),
            lambda _: jnp.full((B, 1), _NEG_INF, jnp.float32),
            None,
        )
        filtered = jnp.where(scaled < thresh, _NEG_INF, scaled)
        return jnp.where(temperature <= 0.0, greedy(None), draw(filtered))

    return jax.lax.cond(jnp.any(sampling), sample, greedy, None)


def sample_tokens(
    logits: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: Union[int, jnp.ndarray] = 0,
    mask_bias: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Sample one token per row with a single PRNG key for the whole batch.

    logits: [B, V]; temperature: [B] (<= 0 → greedy); top_p: [B];
    top_k: int or [B] int32; mask_bias: optional additive [B, V] grammar
    mask (0 / -inf). Returns int32 [B].
    """
    def draw(filtered):
        gumbel = jax.random.gumbel(key, filtered.shape, dtype=jnp.float32)
        return jnp.argmax(filtered + gumbel, axis=-1).astype(jnp.int32)

    return _gated_sample(logits, temperature, top_p, top_k, mask_bias, draw)


@jax.named_scope("sample")
def sample_tokens_per_slot(
    logits: jnp.ndarray,
    key_data: jnp.ndarray,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: Union[int, jnp.ndarray] = 0,
    mask_bias: Optional[jnp.ndarray] = None,
):
    """Per-slot PRNG streams: each continuous-batching slot owns a key so a
    request's sample sequence is reproducible regardless of which other
    requests share the batch.

    key_data: uint32 [B, 2] raw key data (jax.random.key_data of threefry
    keys); mask_bias: optional additive [B, V] grammar mask (0 / -inf).
    Returns (tokens int32 [B], new_key_data [B, 2]).
    """
    # Every row's key advances in every step, whatever branch the batch
    # takes: a [B, 2] split, so a request without a seed (it inherits
    # its slot's key) sees one stream however many greedy steps ran
    # before it. The [B, V] noise is drawn only where a row samples.
    def split(kd):
        k, sub = jax.random.split(jax.random.wrap_key_data(kd))
        return jax.random.key_data(k), jax.random.key_data(sub)

    new_key_data, sub_data = jax.vmap(split)(key_data)

    def one(row, sd):
        sub = jax.random.wrap_key_data(sd)
        g = jax.random.gumbel(sub, row.shape, dtype=jnp.float32)
        return jnp.argmax(row + g).astype(jnp.int32)

    tok = _gated_sample(
        logits, temperature, top_p, top_k, mask_bias,
        lambda filtered: jax.vmap(one)(filtered, sub_data),
    )
    return tok, new_key_data


def make_slot_key_data(seed: int) -> jnp.ndarray:
    """uint32 [2] key data for one slot from an integer seed."""
    return jax.random.key_data(jax.random.key(seed))
