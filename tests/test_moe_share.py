"""The dropless expert layer that holds a share of the experts
(ops/moe.py::moe_dropless): the shares add up to the uncut layer, nothing
drops however lopsided the routing, and the device counters equal a host
count."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.ops.moe import moe_dropless

_REFS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark", "reference")


def _reference(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(_REFS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)  # the benchmark's plain reference
    spec.loader.exec_module(mod)
    return mod


ref = _reference("mla_moe_ref")
# The router by which a family picks its experts, and the reference that states it:
# softmax over all of them (mla_moe_ref), or each expert's own sigmoid with a
# selection bias that picks and does not weigh (xing4_ref).
ROUTERS = {"softmax": ("softmax", False, ref), "sigmoid-with-a-selection-bias": (
    "sigmoid", True, _reference("xing4_ref"))}

D, F, E, K, N = 32, 48, 8, 2, 40


def layer_params(seed=0, experts=E, shared=True, bias=False):
    ks = jax.random.split(jax.random.key(seed), 8)
    p = {"router": jax.random.normal(ks[0], (D, experts)),
         "wg": jax.random.normal(ks[1], (experts, D, F)) * 0.2,
         "wu": jax.random.normal(ks[2], (experts, D, F)) * 0.2,
         "wd": jax.random.normal(ks[3], (experts, F, D)) * 0.2}
    if shared:
        p["shared"] = {"wg": jax.random.normal(ks[4], (D, F)) * 0.2,
                       "wu": jax.random.normal(ks[5], (D, F)) * 0.2,
                       "wd": jax.random.normal(ks[6], (F, D)) * 0.2}
    if bias:  # as large as the scores' own spread: it changes which experts are kept
        p["bias"] = jax.random.normal(ks[7], (experts,)) * 0.3
    return p


def share_of(p, rank, ranks):
    held = p["wg"].shape[0] // ranks
    cut = {k: p[k][rank * held:(rank + 1) * held] for k in ("wg", "wu", "wd")}
    whole = {k: p[k] for k in ("router", "bias") if k in p}  # the router keeps its width
    return {**whole, **cut}, rank * held


def uncut_reference(h, p, k=K, scaling=1.0, router="softmax"):
    """The whole layer by the benchmark's plain reference of that router:
    every expert held (rank 0 of 1), the shared expert once."""
    scoring, _, stated_by = ROUTERS[router]
    sizes = {"num_experts_per_tok": k,
             "config": {"expert_rank": 0, "routed_scaling_factor": scaling,
                        "scoring_func": scoring, "norm_topk_prob": True}}
    with jax.default_matmul_precision("highest"):
        return np.asarray(stated_by._experts(h, p, sizes, jnp.float32)[0])


def shared_expert(h, p):
    s = p["shared"]
    return (jax.nn.silu(h @ s["wg"]) * (h @ s["wu"])) @ s["wd"]


@pytest.mark.parametrize("ranks", [1, 2, 4])
@pytest.mark.parametrize("scaling", [1.0, 2.5])
@pytest.mark.parametrize("router", list(ROUTERS))
def test_the_ranks_parts_and_the_shared_expert_once_equal_the_uncut_layer(router, ranks, scaling):
    """The `model-configs` guide's test of the cut (section 4): what each
    rank computes for its own experts, summed over the ranks, plus what
    every chip computes alike counted once, is the uncut layer, under either
    router."""
    scoring, bias, _ = ROUTERS[router]
    p = layer_params(bias=bias)
    h = jax.random.normal(jax.random.key(9), (N, D))
    total = shared_expert(h, p)
    held_total = 0
    for rank in range(ranks):
        mine, first = share_of(p, rank, ranks)
        out, held, hit = moe_dropless(h, mine, K, first_expert=first,
                                      routed_scaling_factor=scaling, scoring=scoring)
        total = total + out
        held_total += int(held)
        assert 0 < int(hit) <= E // ranks
    assert held_total == N * K  # every assignment lands on exactly one rank
    np.testing.assert_allclose(np.asarray(total),
                               uncut_reference(h, p, scaling=scaling, router=router),
                               atol=2e-5, rtol=1e-4)


def test_the_selection_bias_picks_experts_and_does_not_weigh_them():
    """`top_k_weights`: with a bias the kept experts are those of score +
    bias, their weights the scores themselves renormalised; the bias changes
    the choice here, and a zero bias changes nothing."""
    from omnia_tpu.ops.moe import top_k_weights

    logits = jax.random.normal(jax.random.key(1), (N, E))
    bias = jax.random.normal(jax.random.key(2), (E,)) * 0.3
    scores = np.asarray(jax.nn.sigmoid(logits))
    top_w, top_i = top_k_weights(logits, K, "sigmoid", bias)
    want_i = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :K]
    np.testing.assert_array_equal(np.asarray(top_i), want_i)
    kept = np.take_along_axis(scores, want_i, axis=-1)
    np.testing.assert_allclose(np.asarray(top_w), kept / kept.sum(-1, keepdims=True), rtol=1e-6)
    plain_w, plain_i = top_k_weights(logits, K, "sigmoid")
    assert (np.asarray(plain_i) != want_i).any()
    zero_w, zero_i = top_k_weights(logits, K, "sigmoid", jnp.zeros((E,)))
    np.testing.assert_array_equal(np.asarray(zero_i), np.asarray(plain_i))
    np.testing.assert_allclose(np.asarray(zero_w), np.asarray(plain_w), rtol=1e-6)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        top_k_weights(logits, K, "tanh")


@pytest.mark.parametrize("tokens", [7, 64, 300])
def test_a_routing_in_which_every_token_picks_the_same_expert_loses_none(tokens):
    """Every token's first choice is expert 3: a capacity would drop most of
    them (moe_dispatch's is ceil(N·k/E · 2)). Held against every expert
    evaluated on every token."""
    p = layer_params(seed=2, shared=False)
    h = jnp.abs(jax.random.normal(jax.random.key(4), (tokens, D))) + 0.1
    p["router"] = p["router"].at[:, 3].set(4.0)   # h > 0: expert 3 leads everywhere
    out, held, hit = moe_dropless(h, p, K)
    logits = np.asarray(h @ p["router"])
    assert (logits.argmax(-1) == 3).all() and int(held) == tokens * K
    every = jax.vmap(lambda wg, wu, wd: (jax.nn.silu(h @ wg) * (h @ wu)) @ wd)(
        p["wg"], p["wu"], p["wd"])                                    # [E, N, D]
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(top_i, E) * top_w[..., None], axis=-2)
    want = jnp.einsum("ne,end->nd", combine, every)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=1e-4)
    assert int(hit) == len(np.unique(np.asarray(top_i)))


@pytest.mark.parametrize("rank,ranks", [(0, 1), (0, 4), (3, 4), (1, 2)])
def test_the_counters_equal_a_host_count(rank, ranks):
    p = layer_params(seed=5, shared=False)
    h = jax.random.normal(jax.random.key(6), (N, D))
    mine, first = share_of(p, rank, ranks)
    _, held, hit = moe_dropless(h, mine, K, first_expert=first)
    chosen = np.argsort(-np.asarray(h @ p["router"]), axis=-1)[:, :K]   # host top-k
    here = (chosen >= first) & (chosen < first + E // ranks)
    assert int(held) == here.sum()
    assert int(hit) == len(np.unique(chosen[here]))


def test_the_model_sums_the_counters_over_its_layers():
    from omnia_tpu.models import get_config, mla

    cfg = get_config("test-tiny-mla")
    params = mla.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    cache = mla.init_kv_cache(cfg, 3, 64, dtype=jnp.float32)
    tokens = jnp.asarray([[5], [9], [200]], jnp.int32)
    pos = jnp.zeros((3, 1), jnp.int32)
    logits, _, counts = mla.forward(params, cfg, tokens, pos, *cache, pos[:, 0], counters=True)
    held, hit = (int(c) for c in counts)
    assert mla.DECODE_COUNTERS == ("moe_assignments_held", "moe_experts_hit")
    assert 0 <= held <= cfg.num_layers * 3 * cfg.num_experts_per_tok
    assert 0 <= hit <= min(held, cfg.num_layers * cfg.experts_held)
    assert logits.shape == (3, 1, cfg.vocab_size)


@pytest.mark.parametrize("layer", [0, 2])
def test_a_layer_of_the_stacks_in_place_equals_the_layer_sliced_out(layer):
    """`layer=`: the experts' stacks over all layers go in whole and the
    layer's matrices are met where they lie (no slice, so no copy)."""
    per_layer = [layer_params(seed=10 + i, shared=False) for i in range(3)]
    stacks = {k: jnp.stack([p[k] for p in per_layer]) for k in ("wg", "wu", "wd")}
    h = jax.random.normal(jax.random.key(11), (N, D))
    mine = per_layer[layer]
    want = moe_dropless(h, mine, K)
    got = moe_dropless(h, {"router": mine["router"], **stacks}, K, layer=jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=1e-6)
    assert (int(got[1]), int(got[2])) == (int(want[1]), int(want[2]))
