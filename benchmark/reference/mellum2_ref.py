"""Plain reference forward of the Mellum 2 (`model_type` "mellum") model: a
period of window and full attention layers over grouped-query heads, a norm
on every query and key head, a rotary table a kind of attention layer (plain
RoPE on the window layers, YaRN with cos and sin scaled on the full ones),
and in every layer a softmax router's greedy top-k over all the experts,
every one of them held here, with no shared expert and no dense layer.

Straight `jax.numpy` in float32, `jax.default_matmul_precision("highest")`:
no cache, no ring, no kernel, the whole sequence at once, a layer at a time
in the model's order, every expert evaluated on every token and masked by
the top-k. Nothing is imported from the program. Weights arrive in the type
they are served in and are upcast a layer at a time, the routed experts an
expert at a time.

**The layer**, for input `x` [T, D], `eps` = `rms_norm_eps`, H query heads and
Hkv key/value heads of d = `head_dim`, layer l of attention kind
`layer_types[l]` (`sliding_attention`: window, `full_attention`: full):

- `h = rms(x; ln1)`; `q = h Wq` as [H, d], `k = h Wk`, `v = h Wv` as [Hkv, d].
- `q <- rms(q; qn)`, `k <- rms(k; kn)` over the d values of each head, one
  gain [d] for all heads, before rotation (`assumed.qk_norm`: the source has
  no key for it).
- Rotation by the group of `rope_parameters` that the layer's kind names,
  pairs as the two halves of a head (`assumed.rotary_pairs`). `rope_type`
  "default": angle = position x theta^(-2i/d). `rope_type` "yarn", from the
  published formula (Peng et al. 2023, as transformers'
  `_compute_yarn_parameters` reads these keys): pair i turns
  `r(i) = original_max_position_embeddings x theta^(-2i/d) / (2 pi)` times
  over the original context; with `low = floor(i at which r = beta_fast)`
  and `high = ceil(i at which r = beta_slow)` clipped to [0, d - 1], the
  ramp `g(i) = clip((i - low) / (high - low), 0, 1)` blends the kept
  frequency (g = 0) with the one divided by `factor` (g = 1); cos and sin are
  both multiplied by `attention_factor`, so q.k of a full layer carries its
  square (`assumed.yarn_convention`).
- Scores `q_i . k_j / sqrt(d)`, H / Hkv query heads to a key head; key j is
  visible to query i iff `j <= i` and, on a window layer, `i - j <
  sliding_window`, by position over the whole [T, T] score. Softmax;
  `x <- x + (softmax . v) Wo`.
- `h2 = rms(x; ln2)` (norms stand before each sublayer: `assumed.norm_placement`).
- `p = softmax(h2 Wr)` over all E = `num_experts`; the k = `num_experts_per_tok`
  largest; their weights divided by their sum (`norm_topk_prob`); no scaling
  factor, no bias, no shared expert: `x <- x + sum_{e in top-k} w_e E_e(h2)`,
  `E_e` a SwiGLU of width `moe_intermediate_size`.
- Final `rms`, untied head over the whole vocabulary.

Departures from the source, each kept out of the program alike: the
multi-token-prediction head that the model card mentions is not built
(next-token logits do not read it; `assumed.multi_token_prediction`); a
`mlp_layer_types` word other than "sparse" raises (the source has none).
A sequence longer than `QUERY_BLOCK` (512) has its scores computed that many
queries at a time against every key (a row of the score matrix is
independent of every other), so that the long comparison's three thousand
tokens do not need [H, T, T] at once.

`sizes` is `manifest.reference_sizes`: this module reads `num_heads`,
`num_kv_heads`, `head_dim`, `rms_norm_eps`, `num_experts_per_tok` and, under
`"config"`, the file's own keys (never the depth of the tree it is handed:
that is the tree's, and its order `sizes["layer_order"]` where
`harness/correct.py` has cut it, else the file's, `layer_order`). The
parameter tree is `omnia_tpu/models/llama.py::init_params`'s for a model of
several kinds: `layers` is a list of stacks, one for each kind of attention
layer the file's model has, window then full, each {ln1, ln2, attn/{wq, wk,
wv, wo, qn, kn}, mlp/{router [D, E], wg, wu [E, D, F], wd [E, F, D]}} led by
its own layer axis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
_NEG = -1e30
_KINDS = ("sliding_attention", "full_attention")


def _file_kinds(config: dict) -> list:
    run = config["num_hidden_layers"]
    if set(config["mlp_layer_types"][:run]) != {"sparse"}:
        raise NotImplementedError("a layer whose FFN is not sparse is not written here")
    return list(config["layer_types"][:run])


def stack_kinds(sizes: dict) -> tuple:
    """The attention kind of each stack of `params["layers"]`: those the
    file's model has a layer of, window first."""
    have = set(_file_kinds(sizes["config"]))
    return tuple(kind for kind in _KINDS if kind in have)


def layer_order(sizes: dict) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ..., from the file's
    `layer_types`: a layer lies in the stack of its kind, behind the earlier
    layers of that kind."""
    stacks = stack_kinds(sizes)
    seen = [0] * len(stacks)
    order = []
    for kind in _file_kinds(sizes["config"]):
        stack = stacks.index(kind)
        order.append((stack, seen[stack]))
        seen[stack] += 1
    return tuple(order)


def _rms_norm(x, w, eps):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(F32)).astype(x.dtype)


def rotary_table(group: dict, positions, d: int):
    """(cos, sin) [T, d / 2] of one group of `rope_parameters`."""
    half = d // 2
    i = jnp.arange(half, dtype=F32)
    theta = float(group["rope_theta"])
    freq = theta ** (-i / half)
    scale = 1.0
    if group["rope_type"] == "yarn":
        original = group["original_max_position_embeddings"]

        def pair_that_turns(rotations):  # r(i) = rotations, solved for i
            return d * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(pair_that_turns(group["beta_fast"])), 0)
        high = min(math.ceil(pair_that_turns(group["beta_slow"])), d - 1)
        if low == high:
            high += 0.001
        g = jnp.clip((i - low) / (high - low), 0.0, 1.0)
        freq = freq * (1.0 - g) + freq / group["factor"] * g
        scale = float(group["attention_factor"])
    elif group["rope_type"] != "default":
        raise NotImplementedError(f"rope_type {group['rope_type']!r} is not written here")
    angle = positions.astype(F32)[:, None] * freq[None, :]
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def _rotate(x, cos, sin):
    """x [T, heads, d]: pairs (i, i + d/2) turned by the table's row."""
    half = x.shape[-1] // 2
    cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _attention(h, p, sizes: dict, positions, kind: str):
    """h [T, D] -> [T, D] of a layer whose attention kind is `kind`."""
    config = sizes["config"]
    T = h.shape[0]
    H, Hkv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    q = (h @ p["wq"]).reshape(T, H, d)
    k = (h @ p["wk"]).reshape(T, Hkv, d)
    v = (h @ p["wv"]).reshape(T, Hkv, d)
    if config["assumed"]["qk_norm"]:
        q = _rms_norm(q, p["qn"], sizes["rms_norm_eps"])
        k = _rms_norm(k, p["kn"], sizes["rms_norm_eps"])
    cos, sin = rotary_table(config["rope_parameters"][kind], positions, d)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    q = q.reshape(T, Hkv, H // Hkv, d)
    window = config["sliding_window"] if kind == "sliding_attention" else 0
    out = []
    for lo in range(0, T, QUERY_BLOCK):  # a block of queries against every key
        qi = positions[lo:lo + QUERY_BLOCK]
        scores = jnp.einsum("thgd,shd->hgts", q[lo:lo + QUERY_BLOCK], k,
                            preferred_element_type=F32) * (d ** -0.5)
        seen = positions[None, :] <= qi[:, None]
        if window:
            seen &= qi[:, None] - positions[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, _NEG), axis=-1)
        out.append(jnp.einsum("hgts,shd->thgd", probs.astype(v.dtype), v))
    return jnp.concatenate(out, axis=0).reshape(T, H * d) @ p["wo"]


def _experts(h, p, sizes, compute):
    """Every expert evaluated on every token and weighted by the top-k mask;
    and the router's own account of each decision: the k-th minus the
    (k+1)-th router logit, and the standard deviation of the layer's."""
    k = sizes["num_experts_per_tok"]
    logits = jnp.dot(h, p["router"].astype(compute), preferred_element_type=F32)  # [T, E]
    ranked = jax.lax.top_k(logits, k + 1)[0]
    margin = ranked[:, k - 1] - ranked[:, k]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    if sizes["config"].get("norm_topk_prob", True):
        top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    E = logits.shape[-1]
    combine = jnp.sum(jax.nn.one_hot(top_i, E, dtype=F32) * top_w[..., None], axis=-2)
    combine = combine.astype(h.dtype)                                 # [T, E]

    def one(acc, expert):  # an expert at a time: never the layer whole in float32
        wg, wu, wd, w = expert
        y = _swiglu(h, wg.astype(compute), wu.astype(compute), wd.astype(compute))
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (p["wg"], p["wu"], p["wd"], combine.T))
    return out, margin, jnp.std(logits)


def forward(params, sizes: dict, tokens, compute=F32):
    """tokens int32 [T] -> logits float32 [T, V], whole sequence at once."""
    return _forward(params, sizes, tokens, compute)[0]


def forward_routed(params, sizes: dict, tokens):
    """(logits [T, V], margin [L, T], sigma [L], residual [L + 1, T, D]) over
    every model layer in the model's order."""
    logits, margin, sigma, residual = _forward(params, sizes, tokens, F32)
    return logits, jnp.stack(margin), jnp.stack(sigma), jnp.stack(residual)


def _forward(params, sizes: dict, tokens, compute):
    eps = sizes["rms_norm_eps"]
    stacks = stack_kinds(sizes)
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"][tokens].astype(compute)
        margins, sigmas, residual = [], [], []
        for stack, index in sizes.get("layer_order") or layer_order(sizes):
            p = jax.tree_util.tree_map(lambda a: a[index], params["layers"][stack])
            attn = jax.tree_util.tree_map(lambda a: a.astype(compute), p["attn"])
            residual.append(x)
            x = x + _attention(_rms_norm(x, p["ln1"], eps), attn, sizes, positions,
                               stacks[stack])
            y, margin, sigma = _experts(_rms_norm(x, p["ln2"], eps), p["mlp"], sizes, compute)
            x = x + y
            margins.append(margin)
            sigmas.append(sigma)
        residual.append(x)
        h = _rms_norm(x, params["final_norm"], eps)
        return (h @ params["lm_head"].astype(compute)).astype(F32), margins, sigmas, residual
