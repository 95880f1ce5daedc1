"""Plain reference forward of the K-EXAONE (`exaone_moe`) model: a period of
window and full attention layers over grouped-query heads with a norm on
every query and key head, no rotary position on the full layers, a leading
dense layer and then sparse ones whose sigmoid router picks with a selection
bias among all the experts, of which this chip holds a share beside the
shared expert.

Straight `jax.numpy` in float32, `jax.default_matmul_precision("highest")`:
no cache, no ring, no kernel, the whole sequence at once, a layer at a time
in the model's order, every held expert evaluated on every token and masked
by the top-k. Nothing is imported from the program. Weights arrive in the
type they are served in and are upcast a layer at a time, the routed experts
an expert at a time.

**The layer**, for input `x` [T, D], `eps` = `rms_norm_eps`, H query heads and
Hkv key/value heads of d = `head_dim`, layer l of attention kind
`layer_types[l]` (`sliding_attention`: window, `full_attention`: full) and FFN
kind `mlp_layer_types[l]`:

- `h = rms(x; ln1)`; `q = h Wq` as [H, d], `k = h Wk`, `v = h Wv` as [Hkv, d].
- `q <- rms(q; qn)`, `k <- rms(k; kn)` over the d values of each head, one
  gain [d] for all heads, before any rotation (`assumed.qk_norm`).
- A window layer rotates q and k by RoPE (`rope_parameters.rope_theta`, pairs
  as the two halves of a head: `rope_type` default). A full layer is rotated
  only where `assumed.rope_on_full_layers` says so: here it is not.
- Scores `q_i . k_j / sqrt(d)`, H / Hkv query heads to a key head; key j is
  visible to query i iff `j <= i` and, on a window layer, `i - j <
  sliding_window`. Softmax; `x <- x + (softmax . v) Wo`.
- `h2 = rms(x; ln2)` (norms stand before each sublayer: `assumed.norm_placement`).
- Dense: `x <- x + (silu(h2 Wg) * (h2 Wu)) Wd`.
- Sparse: `s = sigmoid(h2 Wr)` over all E = `num_experts_source` experts
  (`scoring_func`; softmax over all E otherwise); the k = `num_experts_per_tok`
  with the largest `s + b` (`mlp/bias`, the selection bias; `n_group` =
  `topk_group` = 1, so no group step); weights `s` at those k, never `s + b`,
  divided by their sum (`norm_topk_prob`) times `routed_scaling_factor`;
  `x <- x + sum_{e in top-k and held} w_e E_e(h2) + S(h2)`. Held are experts
  `expert_rank * num_experts ... + num_experts - 1` (`num_experts` of the file
  is how many this chip holds); what the absent ones would add is left out,
  here and in the program alike, and that partial stream goes on.
- Final `rms`, head over the held vocabulary slice. The multi-token-prediction
  module (`num_nextn_predict_layers`) is not built: next-token logits do not
  read it.

The scores of a long sequence are computed a block of `QUERY_BLOCK` (512) queries
at a time against every key (the same arithmetic, a row of the score matrix
being independent of every other), so that a sequence of eight thousand
tokens does not need its [H, T, T] scores at once.

`sizes` is `manifest.reference_sizes`: this module reads `num_heads`,
`num_kv_heads`, `head_dim`, `rms_norm_eps`, `num_experts_per_tok` and, under
`"config"`, the file's own keys, a switch the source lacks under its
`assumed` (never the depth of the tree it is handed: that is the tree's, and
its order `sizes["layer_order"]` where `harness/correct.py` has cut it, else
the file's, `layer_order`). The parameter tree is
`omnia_tpu/models/llama.py::init_params`'s for a model of several kinds:
`layers` is a list of stacks, one for each kind of layer the file's model
has, in the order (dense, window), (dense, full), (sparse, window), (sparse,
full), each {ln1, ln2, attn/{wq, wk, wv, wo, qn, kn}, mlp/{wg, wu, wd} or
mlp/{router [D, E], bias [E], wg, wu [held, D, F], wd [held, F, D],
shared/{wg, wu, wd}}} led by its own layer axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
_NEG = -1e30
_KINDS = (("dense", "sliding_attention"), ("dense", "full_attention"),
          ("sparse", "sliding_attention"), ("sparse", "full_attention"))


def _key(config: dict, key: str):
    """A key of the file, or of its `assumed` where the source lacks it."""
    return config[key] if key in config else config["assumed"][key]


def _file_kinds(config: dict) -> list:
    return list(zip(config["mlp_layer_types"], config["layer_types"]))[
        :config["num_hidden_layers"]]


def stack_kinds(sizes: dict) -> tuple:
    """(FFN kind, attention kind) of each stack of `params["layers"]`: those
    the file's model has a layer of, in the order of `_KINDS`."""
    have = set(_file_kinds(sizes["config"]))
    return tuple(kind for kind in _KINDS if kind in have)


def layer_order(sizes: dict) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ..., from the file's
    `mlp_layer_types` and `layer_types`: a layer lies in the stack of its
    kind, behind the earlier layers of that kind."""
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


def _rope(x, positions, theta: float):
    """x [T, heads, d]: pairs (i, i + d/2) turned by position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = positions.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _attention(h, p, sizes: dict, positions, window: int, rotate: bool):
    """h [T, D] -> [T, D]; `window` 0 is full attention."""
    config = sizes["config"]
    T = h.shape[0]
    H, Hkv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    q = (h @ p["wq"]).reshape(T, H, d)
    k = (h @ p["wk"]).reshape(T, Hkv, d)
    v = (h @ p["wv"]).reshape(T, Hkv, d)
    if _key(config, "qk_norm"):
        q = _rms_norm(q, p["qn"], sizes["rms_norm_eps"])
        k = _rms_norm(k, p["kn"], sizes["rms_norm_eps"])
    if rotate:
        theta = float(config["rope_parameters"]["rope_theta"])
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    q = q.reshape(T, Hkv, H // Hkv, d)
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
    """The held routed experts, each evaluated on every token and weighted
    by the top-k mask, and the shared expert once; and the router's own
    account of each decision: the k-th minus the (k+1)-th of what it selects
    by, and the standard deviation of that over the layer."""
    config = sizes["config"]
    k = sizes["num_experts_per_tok"]
    logits = jnp.dot(h, p["router"].astype(compute), preferred_element_type=F32)  # [T, E]
    scores = (jax.nn.sigmoid(logits) if config.get("scoring_func") == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    select = scores + p["bias"].astype(F32) if "bias" in p else scores
    ranked, top_i = jax.lax.top_k(select, k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    top_i = top_i[:, :k]
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if config.get("norm_topk_prob", True):
        top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    top_w = top_w * config.get("routed_scaling_factor", 1)
    E = scores.shape[-1]
    combine = jnp.sum(jax.nn.one_hot(top_i, E, dtype=F32) * top_w[..., None], axis=-2)
    held = p["wg"].shape[0]
    first = config.get("expert_rank", 0) * held
    combine = combine[:, first:first + held].astype(h.dtype)          # [T, held]

    def one(acc, expert):  # an expert at a time: never the layer whole in float32
        wg, wu, wd, w = expert
        y = _swiglu(h, wg.astype(compute), wu.astype(compute), wd.astype(compute))
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (p["wg"], p["wu"], p["wd"], combine.T))
    if "shared" in p:
        s = jax.tree_util.tree_map(lambda a: a.astype(compute), p["shared"])
        out = out + _swiglu(h, s["wg"], s["wu"], s["wd"])
    return out, margin, jnp.std(select)


def forward(params, sizes: dict, tokens, compute=F32):
    """tokens int32 [T] -> logits float32 [T, V], whole sequence at once."""
    return _forward(params, sizes, tokens, compute)[0]


def forward_routed(params, sizes: dict, tokens):
    """(logits [T, V], margin [L, T], sigma [L], residual [L + 1, T, D]) over
    every model layer in the model's order. A dense layer decides every
    position: margin inf, sigma 1."""
    logits, margin, sigma, residual = _forward(params, sizes, tokens, F32)
    return logits, jnp.stack(margin), jnp.stack(sigma), jnp.stack(residual)


def _forward(params, sizes: dict, tokens, compute):
    config, eps = sizes["config"], sizes["rms_norm_eps"]
    if (config.get("n_group", 1), config.get("topk_group", 1)) != (1, 1):
        raise NotImplementedError("grouped top-k (n_group, topk_group > 1) is not written here")
    stacks = stack_kinds(sizes)
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        positions = jnp.arange(T, dtype=jnp.int32)
        x = params["embed"][tokens].astype(compute)
        margins, sigmas, residual = [], [], []
        for stack, index in sizes.get("layer_order") or layer_order(sizes):
            ffn, attention = stacks[stack]
            window = config["sliding_window"] if attention == "sliding_attention" else 0
            p = jax.tree_util.tree_map(lambda a: a[index], params["layers"][stack])
            attn = jax.tree_util.tree_map(lambda a: a.astype(compute), p["attn"])
            residual.append(x)
            x = x + _attention(_rms_norm(x, p["ln1"], eps), attn, sizes, positions, window,
                               rotate=bool(window) or _key(config, "rope_on_full_layers"))
            h2 = _rms_norm(x, p["ln2"], eps)
            if ffn == "dense":
                mlp = jax.tree_util.tree_map(lambda a: a.astype(compute), p["mlp"])
                x = x + _swiglu(h2, mlp["wg"], mlp["wu"], mlp["wd"])
                margin, sigma = jnp.full((T,), jnp.inf, F32), jnp.ones((), F32)
            else:
                y, margin, sigma = _experts(h2, p["mlp"], sizes, compute)
                x = x + y
            margins.append(margin)
            sigmas.append(sigma)
        residual.append(x)
        h = _rms_norm(x, params["final_norm"], eps)
        return (h @ params["lm_head"].astype(compute)).astype(F32), margins, sigmas, residual
