"""Numerics tests for core ops against straightforward NumPy references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.ops.attention import gqa_attention
from omnia_tpu.ops.norms import rms_norm
from omnia_tpu.ops.rope import apply_rope, rope_cos_sin
from omnia_tpu.ops.sampling import sample_tokens


def test_rms_norm_matches_reference():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 16)).astype(np.float32)
    w = rng.standard_normal(16).astype(np.float32)
    eps = 1e-5
    expected = x / np.sqrt((x**2).mean(-1, keepdims=True) + eps) * w
    got = rms_norm(jnp.asarray(x), jnp.asarray(w), eps)
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5, atol=1e-5)


def test_rms_norm_preserves_dtype():
    x = jnp.ones((2, 8), dtype=jnp.bfloat16)
    w = jnp.ones(8, dtype=jnp.bfloat16)
    assert rms_norm(x, w).dtype == jnp.bfloat16


def test_rope_preserves_norm():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 6, 4, 32)).astype(np.float32))
    pos = jnp.arange(6, dtype=jnp.int32)[None, :]
    cos, sin = rope_cos_sin(pos, 32, 10000.0)
    out = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-4,
    )


def test_rope_relative_property():
    """<rope(q,m), rope(k,n)> depends only on m-n."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 1, 1, 16)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 1, 1, 16)).astype(np.float32))

    def dot_at(m, n):
        pos_q = jnp.full((1, 1), m, dtype=jnp.int32)
        pos_k = jnp.full((1, 1), n, dtype=jnp.int32)
        cq, sq = rope_cos_sin(pos_q, 16, 10000.0)
        ck, sk = rope_cos_sin(pos_k, 16, 10000.0)
        return float(jnp.sum(apply_rope(q, cq, sq) * apply_rope(k, ck, sk)))

    assert abs(dot_at(5, 3) - dot_at(12, 10)) < 1e-4
    assert abs(dot_at(0, 0) - dot_at(7, 7)) < 1e-4


def _naive_attention(q, k, v, q_pos):
    """NumPy GQA reference. q [B,T,H,D]; k,v [B,S,Hkv,D]; q_pos [B,T]."""
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    out = np.zeros_like(q, dtype=np.float32)
    for b in range(B):
        for h in range(H):
            kv_h = h // G
            scores = q[b, :, h] @ k[b, :, kv_h].T / np.sqrt(D)  # [T,S]
            mask = np.arange(S)[None, :] <= q_pos[b][:, None]
            scores = np.where(mask, scores, -1e30)
            e = np.exp(scores - scores.max(-1, keepdims=True))
            p = e / e.sum(-1, keepdims=True)
            out[b, :, h] = p @ v[b, :, kv_h]
    return out


def test_gqa_attention_matches_naive():
    rng = np.random.default_rng(3)
    B, T, S, H, Hkv, D = 2, 4, 8, 4, 2, 16
    q = rng.standard_normal((B, T, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    q_pos = np.array([[0, 1, 2, 3], [2, 3, 4, 5]], dtype=np.int32)
    got = gqa_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(q_pos))
    expected = _naive_attention(q, k, v, q_pos)
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-4, atol=1e-4)


def test_gqa_attention_mha_case():
    """H == Hkv (no grouping) still works."""
    rng = np.random.default_rng(4)
    B, T, S, H, D = 1, 2, 4, 2, 8
    q = rng.standard_normal((B, T, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, H, D)).astype(np.float32)
    q_pos = np.array([[1, 2]], dtype=np.int32)
    got = gqa_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(q_pos))
    expected = _naive_attention(q, k, v, q_pos)
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-4, atol=1e-4)


class TestSampling:
    def test_greedy_when_temperature_zero(self):
        logits = jnp.asarray([[0.1, 5.0, 0.2], [3.0, 0.0, -1.0]])
        toks = sample_tokens(
            logits,
            jax.random.key(0),
            temperature=jnp.zeros(2),
            top_p=jnp.ones(2),
        )
        assert toks.tolist() == [1, 0]

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]] * 64, dtype=jnp.float32)
        toks = sample_tokens(
            logits,
            jax.random.key(1),
            temperature=jnp.full(64, 10.0),  # near-uniform over survivors
            top_p=jnp.ones(64),
            top_k=2,
        )
        assert set(np.asarray(toks).tolist()) <= {2, 3}

    def test_top_p_restricts_support(self):
        # softmax([0,0,10,10]) ≈ [~0, ~0, .5, .5]; top_p=0.9 keeps {2,3}.
        logits = jnp.asarray([[0.0, 0.0, 10.0, 10.0]] * 64, dtype=jnp.float32)
        toks = sample_tokens(
            logits,
            jax.random.key(2),
            temperature=jnp.ones(64),
            top_p=jnp.full(64, 0.9),
        )
        assert set(np.asarray(toks).tolist()) <= {2, 3}

    def test_mixed_batch_greedy_and_sampled(self):
        logits = jnp.asarray([[0.0, 4.0], [4.0, 0.0]])
        toks = sample_tokens(
            logits,
            jax.random.key(3),
            temperature=jnp.asarray([0.0, 1.0]),
            top_p=jnp.ones(2),
        )
        assert int(toks[0]) == 1

    def test_jittable(self):
        f = jax.jit(lambda l, k, t, p: sample_tokens(l, k, t, p, top_k=4))
        out = f(
            jnp.zeros((2, 16)),
            jax.random.key(0),
            jnp.ones(2),
            jnp.full(2, 0.9),
        )
        assert out.shape == (2,)


def test_top_k_top_p_sequential_semantics():
    """top_p nucleus must be computed over the RENORMALIZED top-k survivors
    (HF/vLLM sequential filtering), not the full distribution."""
    # probs: [0.3, 0.2, 0.05 x 10] -> top_k=2 survivors renormalize to
    # [0.6, 0.4]; top_p=0.5 then admits only token 0.
    probs = np.array([[0.3, 0.2] + [0.05] * 10], dtype=np.float32)
    logits = jnp.asarray(np.log(probs))
    logits64 = jnp.tile(logits, (64, 1))
    toks = sample_tokens(
        logits64,
        jax.random.key(7),
        temperature=jnp.ones(64),
        top_p=jnp.full(64, 0.5),
        top_k=jnp.full(64, 2, dtype=jnp.int32),
    )
    assert set(np.asarray(toks).tolist()) == {0}


def test_fast_prefix_threshold_matches_full_sort():
    """The top_k-prefix fast path must be semantics-identical to the
    full-sort path across regimes: peaked rows (fast path engages), flat
    rows (nucleus past the prefix → fallback), and top_k beyond the
    prefix (fallback)."""
    import numpy as np

    from omnia_tpu.ops import sampling as S

    rng = np.random.default_rng(0)
    V = 4096  # > _FAST_PREFIX_K so the prefix is a strict subset

    def full_sort_reference(scaled, top_p, top_k):
        # Full-sort formulation: smallest descending prefix of the top-k
        # survivors whose mass reaches top_p * survivor mass.
        scaled = jnp.asarray(scaled, jnp.float32)
        sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
        k = jnp.clip(jnp.asarray(top_k, jnp.int32), 0, V)
        kth = jnp.take_along_axis(
            sorted_desc, jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1)
        k_thresh = jnp.where((k > 0)[:, None], kth, -1e30)
        in_topk = jnp.arange(V)[None, :] < jnp.where(k > 0, k, V)[:, None]
        m = sorted_desc[:, :1]
        e = jnp.where(in_topk, jnp.exp(sorted_desc - m), 0.0)
        cum = jnp.cumsum(e, axis=-1)
        denom = jnp.where(
            k > 0,
            jnp.take_along_axis(
                cum, jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1)[:, 0],
            cum[:, -1],
        )
        keep = in_topk & (
            (cum - e) < jnp.asarray(top_p)[:, None] * denom[:, None])
        p_thresh = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True)
        # Disabled knobs (top_p>=1, k=0) mean NO filtering: express that
        # as an open threshold rather than the row minimum — at f32 the
        # cumsum boundary is ulp-noisy there, and "admit everything" is
        # the defined semantics.
        no_filter = (jnp.asarray(top_p) >= 1.0) & (k <= 0)
        p_thresh = jnp.where(no_filter[:, None], -1e30, p_thresh)
        return jnp.maximum(k_thresh, p_thresh)

    cases = [
        # peaked logits, typical serving knobs (incl. a default-params
        # row: top_p=1/k=0 is exempt, not a fallback trigger) → FAST
        (rng.normal(0, 4, (4, V)), [0.9, 0.95, 0.5, 1.0], [0, 40, 8, 0], True),
        # near-flat logits: top-256 mass << top_p → full-sort fallback
        (rng.normal(0, 0.01, (3, V)), [0.99, 0.9, 0.999], [0, 0, 0], False),
        # top_k beyond the prefix → fallback
        (rng.normal(0, 2, (2, V)), [0.9, 1.0], [1000, 2000], False),
        # mixed batch: one row would be fast, one forces fallback
        (rng.normal(0, 2, (2, V)) * np.array([[4.0], [0.01]]),
         [0.9, 0.99], [0, 0], False),
        # all-defaults batch (the common serving case) must be FAST
        (rng.normal(0, 2, (4, V)), [1.0] * 4, [0] * 4, True),
    ]
    fast_seen = slow_seen = False
    for logits, top_p, top_k, want_fast in cases:
        # Guard the guard: assert each case exercises the intended branch.
        assert S.fast_path_feasible(logits, top_p, top_k) is want_fast, (
            "case no longer hits its intended path", top_p, top_k)
        fast_seen |= want_fast
        slow_seen |= not want_fast
        scaled = jnp.asarray(logits, jnp.float32)
        got = S._filter_thresholds(
            scaled,
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(top_k, jnp.int32),
        )
        want = full_sort_reference(logits, np.asarray(top_p, np.float32), top_k)
        # Compare ADMITTED SETS, not raw thresholds: an unfiltered row's
        # threshold may be -inf on one path and the row minimum on the
        # other — same admitted vocabulary either way.
        np.testing.assert_array_equal(
            np.asarray(scaled >= got), np.asarray(scaled >= want))
    assert fast_seen and slow_seen


# ---------------------------------------------------------------------------
# The gated sampler (PR 33) against a frozen copy of its parent's arithmetic
# ---------------------------------------------------------------------------

def _parent_sample_per_slot(logits, key_data, temperature, top_p, top_k,
                            mask_bias=None):
    """Commit 773850e's ``sample_tokens_per_slot``, kept verbatim: every row
    pays the thresholds, the [B, V] noise and a second argmax, and a greedy
    row's result is thrown away by the last ``where``. The thresholds
    function is the module's own (the gates sit in front of it, unchanged)."""
    from omnia_tpu.ops import sampling as S

    logits = logits.astype(jnp.float32)
    if mask_bias is not None:
        logits = logits + mask_bias
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    thresh = S._filter_thresholds(scaled, top_p, jnp.asarray(top_k, jnp.int32))
    filtered = jnp.where(scaled < thresh, S._NEG_INF, scaled)

    def one(row, kd):
        k = jax.random.wrap_key_data(kd)
        k, sub = jax.random.split(k)
        g = jax.random.gumbel(sub, row.shape, dtype=jnp.float32)
        return jnp.argmax(row + g).astype(jnp.int32), jax.random.key_data(k)

    sampled_tok, new_key_data = jax.vmap(one)(filtered, key_data)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled_tok), new_key_data


# name: (V, logit scale, temperature, top_p, top_k, mask, branch) where branch
# is the gate the batch must take: 1 argmax only, 2 no thresholds, 3 thresholds.
_GATE_CASES = {
    "all_greedy": (1024, 3.0, [0.0] * 4, [1.0] * 4, [0] * 4, False, 1),
    "all_sampling_no_filter": (1024, 3.0, [0.7, 1.0, 0.3, 1.5], [1.0] * 4, [0] * 4, False, 2),
    "greedy_and_sampling_mixed": (1024, 3.0, [0.0, 0.7, 0.0, 1.0], [1.0] * 4, [0] * 4, False, 2),
    "top_p_only": (1024, 3.0, [0.7, 0.0, 1.0, 0.9], [0.9, 1.0, 0.5, 1.0], [0] * 4, False, 3),
    "top_k_only": (1024, 3.0, [0.7, 0.0, 1.0, 0.9], [1.0] * 4, [40, 0, 1, 0], False, 3),
    "top_p_and_top_k": (1024, 3.0, [0.7, 0.0, 1.0, 0.9], [0.9, 0.5, 0.95, 1.0], [40, 8, 0, 0], False, 3),
    "greedy_row_with_top_p_takes_argmax": (1024, 3.0, [0.0] * 4, [0.9, 1.0, 0.5, 1.0], [0, 0, 7, 0], False, 1),
    "greedy_filter_row_beside_plain_sampling": (1024, 3.0, [0.0, 0.7, 0.0, 1.0], [0.9, 1.0, 1.0, 1.0], [0, 0, 5, 0], False, 2),
    "mask_bias_greedy": (1024, 3.0, [0.0] * 4, [1.0] * 4, [0] * 4, True, 1),
    "mask_bias_sampling": (1024, 3.0, [0.7, 0.0, 1.0, 0.9], [0.9, 1.0, 1.0, 0.8], [0, 0, 3, 0], True, 3),
    "vocab_is_the_prefix": (256, 3.0, [0.7, 0.0, 1.0, 0.9], [0.9, 1.0, 0.5, 1.0], [0, 0, 300, 0], False, 3),
    "infeasible_prefix_slow_sort": (4096, 0.01, [1.0, 0.0, 0.8, 1.0], [0.99, 1.0, 0.9, 1.0], [0, 0, 2000, 0], False, 3),
    "one_row_greedy": (1024, 3.0, [0.0], [1.0], [0], False, 1),
    "one_row_sampling": (1024, 3.0, [0.7], [0.9], [50], False, 3),
    "one_row_default_params": (1024, 3.0, [0.7], [1.0], [0], False, 2),
}


@pytest.mark.parametrize("name", list(_GATE_CASES))
def test_gated_sampler_bit_equal_to_parent(name):
    """Tokens AND key data of the gated sampler equal the parent's, bit for
    bit, over three steps of each stream (the key advances in every branch),
    jitted as the engine calls it; and the batch takes the gate it should."""
    from omnia_tpu.ops import sampling as S

    V, scale, temp, top_p, top_k, masked, branch = _GATE_CASES[name]
    B = len(temp)
    rng = np.random.default_rng(sum(map(ord, name)))
    temp = jnp.asarray(temp, jnp.float32)
    top_p = jnp.asarray(top_p, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    sampling = np.asarray(temp) > 0
    filtering = sampling & ((np.asarray(top_p) < 1) | (np.asarray(top_k) > 0))
    assert branch == (3 if filtering.any() else 2 if sampling.any() else 1)
    if name == "infeasible_prefix_slow_sort":
        probe = jnp.asarray(rng.normal(0, scale, (B, V)), jnp.float32)
        assert not S.fast_path_feasible(
            probe / jnp.maximum(temp, 1e-6)[:, None], top_p, top_k)
        rng = np.random.default_rng(sum(map(ord, name)))

    new = jax.jit(S.sample_tokens_per_slot)
    old = jax.jit(_parent_sample_per_slot)
    kd_new = kd_old = jnp.stack(
        [S.make_slot_key_data(1000 + i) for i in range(B)])
    for _step in range(3):
        logits = jnp.asarray(rng.normal(0, scale, (B, V)), jnp.float32)
        bias = None
        if masked:
            bias = jnp.where(
                jnp.asarray(rng.random((B, V)) < 0.5), S._NEG_INF, 0.0
            ).astype(jnp.float32)
        tok_new, kd_new = new(logits, kd_new, temp, top_p, top_k, bias)
        tok_old, kd_old = old(logits, kd_old, temp, top_p, top_k, bias)
        np.testing.assert_array_equal(np.asarray(tok_new), np.asarray(tok_old))
        np.testing.assert_array_equal(np.asarray(kd_new), np.asarray(kd_old))
        if masked:
            picked = np.take_along_axis(
                np.asarray(bias), np.asarray(tok_new)[:, None], axis=1)
            assert (picked == 0.0).all()


def _eqn_names(jaxpr, into_cond=True):
    """Primitive names (with the output shapes) of a jaxpr, descending into
    every sub-jaxpr except, when ``into_cond`` is false, a ``cond``'s."""
    out = []
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name,
                    tuple(getattr(v.aval, "shape", ()) for v in eqn.outvars)))
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_eqn_names(sub, into_cond))
    return out


def test_sampler_gates_hold_the_vocabulary_wide_work():
    """Structure: ``top_k``, ``sort``, ``cumsum`` and the [B, V] random bits
    occur only inside ``cond`` branches; the branch taken when every
    temperature is zero holds none of them and nothing vocabulary-wide but
    the argmax; the branch of a batch whose sampling rows do not filter
    draws the noise but holds no ``top_k`` / ``sort`` / ``cumsum``."""
    from omnia_tpu.ops import sampling as S

    B, V = 4, 1024
    closed = jax.make_jaxpr(S.sample_tokens_per_slot)(
        jnp.zeros((B, V), jnp.float32), jnp.zeros((B, 2), jnp.uint32),
        jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B, V), jnp.float32),
    )

    def heavy(names):
        return [
            (n, shapes) for n, shapes in names
            if n in ("top_k", "sort", "cumsum", "exp", "div")
            or n.startswith("cum")
            or (n in ("random_bits", "threefry2x32")
                and any(V in s for s in shapes))
        ]

    assert heavy(_eqn_names(closed.jaxpr, into_cond=False)) == []
    everything = {n for n, _ in _eqn_names(closed.jaxpr)}
    assert {"top_k", "sort", "cumsum", "random_bits"} <= everything

    (outer,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "cond"]
    # lax.cond(pred, true_fn, false_fn): branches[int(pred)].
    greedy, sample = outer.params["branches"]
    greedy_names = _eqn_names(greedy.jaxpr)
    assert heavy(greedy_names) == []
    assert "cond" not in {n for n, _ in greedy_names}
    assert {n for n, _ in greedy_names} <= {
        "argmax", "convert_element_type", "add", "pjit"}

    (inner,) = [e for e in sample.jaxpr.eqns if e.primitive.name == "cond"]
    outside_inner = {n for n, _ in _eqn_names(sample.jaxpr, into_cond=False)}
    assert not outside_inner & {"top_k", "sort", "cumsum"}
    assert "random_bits" in outside_inner
    open_thresh, thresholds = inner.params["branches"]
    assert heavy(_eqn_names(open_thresh.jaxpr)) == []
    assert {"top_k", "sort", "cumsum"} <= {
        n for n, _ in _eqn_names(thresholds.jaxpr)}

    # The single-key sampler goes through the same gates.
    closed1 = jax.make_jaxpr(S.sample_tokens)(
        jnp.zeros((B, V), jnp.float32), jax.random.key(0),
        jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32),
    )
    assert heavy(_eqn_names(closed1.jaxpr, into_cond=False)) == []
