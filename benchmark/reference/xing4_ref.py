"""Plain reference forward of the Xing4.0 (`xing4_0`) model: leading dense
layers and then sparse ones, latent attention (MLA), a residual stream of
`hc_mult` copies a token mixed around every sublayer by manifold-constrained
hyper-connections, and a sigmoid router with a selection bias beside a
shared expert.

Straight `jax.numpy` in float32, `jax.default_matmul_precision("highest")`:
no cache, no kernel, the whole sequence at once, a layer at a time in the
model's order, every routed expert evaluated on every token and masked by
the top-k. Nothing is imported from the program. The attention sublayer is
`mla_moe_ref._attention`, the file beside this one (the same block at other
ranks and head widths, with the conventions that file states for YaRN).

**The stream.** A token's residual is X [n, D], n = `hc_mult`, D =
`hidden_size`, carried as [T, n, D] and reported flat, [T, n * D] (copy i is
values i * D ... (i + 1) * D - 1). An embedding row is copied n times; a
table that is already n * D wide is the stream itself. After the last layer
the copies are summed, then `final_norm`, then `lm_head`.

**A sublayer f** (attention with `ln1`, the FFN or expert layer with `ln2`),
with its own `hc/<attn|mlp>/{phi [n * D, 2n + n^2] = [Phi_pre | Phi_post |
Phi_res], bias [2n + n^2] = [b_pre | b_post | vec(B_res)] (row-major), alpha
[3] = (alpha_pre, alpha_post, alpha_res)}`, in float32 whatever `compute`:

- `xbar = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)` (no gain);
- `a_pre = alpha_pre * (xbar Phi_pre) + b_pre`, `a_post` alike,
  `A_res = alpha_res * mat(xbar Phi_res) + B_res`;
- `h_pre = sigmoid(a_pre)`, `h_post = 2 sigmoid(a_post)`;
- `M = exp(clip(A_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))`, then
  `hc_sinkhorn_iters` times: every column divided by its sum + `hc_eps`, then
  every row by its; `H_res = M`;
- `u = sum_i h_pre[i] X[i]`, `y = f(rms(u; ln))`,
  `X'[i] = sum_j H_res[i, j] X[j] + h_post[i] y`.

**The router of a sparse layer**, in float32: `s = sigmoid(h2 W_r)` (E
scores; `scoring_func`), the k experts with the largest `s + b_sel`
(`mlp/bias`, the selection bias of `topk_method` noaux_tc; `n_group` =
`topk_group` = 1, so no group step), weights `s` at those k (never `s +
b_sel`) divided by their sum (`norm_topk_prob`) times `routed_scaling_factor`,
plus the shared expert once. A dense layer is one SwiGLU of
`intermediate_size`. The multi-token-prediction module
(`num_nextn_predict_layers`) is not built: next-token logits do not read it.

`sizes` is `manifest.reference_sizes`: this module reads `num_heads`,
`rms_norm_eps`, `num_experts_per_tok` and, under `"config"`, the file's own
keys (never its depth: that is the tree's, and its order `sizes["layer_order"]`
where `harness/correct.py` has cut it, else the file's, `layer_order`). The
parameter tree is `omnia_tpu/models/mla.py::init_params`'s: `layers` =
[dense stack, sparse stack], each led by its own layer axis.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_mla = _beside("mla_moe_ref")
_rms_norm, _swiglu = _mla._rms_norm, _mla._swiglu


def layer_order(sizes: dict) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ..., from the file:
    `first_k_dense_replace` dense layers (stack 0), then the sparse (stack 1)."""
    config = sizes["config"]
    dense = config["first_k_dense_replace"]
    return (tuple((0, i) for i in range(dense))
            + tuple((1, i) for i in range(config["num_hidden_layers"] - dense)))


def _attention_sizes(sizes: dict) -> dict:
    """`sizes` as `mla_moe_ref._attention` reads them: that file's model
    keeps its rotary constants in one group, `rope_parameters`; this one's
    file has `rope_scaling` and `rope_theta` beside it."""
    config = sizes["config"]
    rope = {**config["rope_scaling"], "rope_theta": config["rope_theta"]}
    interleave = config.get("rope_interleave", config.get("assumed", {}).get("rope_interleave"))
    return {**sizes, "config": {**config, "rope_parameters": rope, "rope_interleave": interleave}}


def _maps(x, hc, config: dict, eps: float):
    """x [T, n, D] -> (h_pre [T, n], h_post [T, n], H_res [T, n, n]), float32."""
    T, n, _ = x.shape
    flat = x.reshape(T, -1).astype(F32)
    xbar = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    phi, bias, alpha = (hc[k].astype(F32) for k in ("phi", "bias", "alpha"))
    a_pre = alpha[0] * (xbar @ phi[:, :n]) + bias[:n]
    a_post = alpha[1] * (xbar @ phi[:, n:2 * n]) + bias[n:2 * n]
    a_res = alpha[2] * (xbar @ phi[:, 2 * n:]).reshape(T, n, n) + bias[2 * n:].reshape(n, n)
    m = jnp.exp(jnp.clip(a_res, config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]))
    for _ in range(config["hc_sinkhorn_iters"]):
        m = m / (m.sum(axis=-2, keepdims=True) + config["hc_eps"])   # a column's sum: over rows
        m = m / (m.sum(axis=-1, keepdims=True) + config["hc_eps"])
    return jax.nn.sigmoid(a_pre), 2.0 * jax.nn.sigmoid(a_post), m


def _sublayer(x, hc, config: dict, eps: float, f):
    """x [T, n, D] -> (x' [T, n, D], whatever f returns beside y). `f` takes
    u [T, D] and gives (y [T, D], ...)."""
    h_pre, h_post, h_res = _maps(x, hc, config, eps)
    u = jnp.einsum("tn,tnd->td", h_pre, x.astype(F32)).astype(x.dtype)
    y, *rest = f(u)
    mixed = (jnp.einsum("tij,tjd->tid", h_res, x.astype(F32))
             + h_post[:, :, None] * y.astype(F32)[:, None, :])
    return (mixed.astype(x.dtype), *rest)


def _experts(h, p, sizes, compute):
    """The routed experts, each evaluated on every token and weighted by
    the top-k mask, and the shared expert once; and the router's own account
    of each decision: the k-th minus the (k+1)-th of what it selects by, and
    the standard deviation of that over the layer."""
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
    combine = combine.astype(h.dtype)                                 # [T, E]

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
    """(logits [T, V], margin [L, T], sigma [L], residual [L + 1, T, n * D])
    over every model layer in the model's order. A dense layer decides every
    position: margin inf, sigma 1."""
    logits, margin, sigma, residual = _forward(params, sizes, tokens, F32)
    return logits, jnp.stack(margin), jnp.stack(sigma), jnp.stack(residual)


def _forward(params, sizes: dict, tokens, compute):
    config, eps = sizes["config"], sizes["rms_norm_eps"]
    if (config.get("n_group", 1), config.get("topk_group", 1)) != (1, 1):
        raise NotImplementedError("grouped top-k (n_group, topk_group > 1) is not written here")
    n, D = config["hc_mult"], config["hidden_size"]
    attn_sizes = _attention_sizes(sizes)
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        positions = jnp.arange(T, dtype=jnp.int32)
        x = params["embed"][tokens].astype(compute)
        # A row of the model's width is copied n times; one n * D wide is the copies.
        x = jnp.repeat(x[:, None, :], n, axis=1) if x.shape[-1] == D else x.reshape(T, n, D)
        margins, sigmas, residual = [], [], []
        for stack, index in sizes.get("layer_order") or layer_order(sizes):
            p = jax.tree_util.tree_map(lambda a: a[index], params["layers"][stack])
            attn = jax.tree_util.tree_map(lambda a: a.astype(compute), p["attn"])
            residual.append(x.reshape(T, n * D))
            (x,) = _sublayer(x, p["hc"]["attn"], config, eps, lambda u: (
                _mla._attention(_rms_norm(u, p["ln1"], eps), attn, attn_sizes, positions),))
            if stack == 0:
                mlp = jax.tree_util.tree_map(lambda a: a.astype(compute), p["mlp"])
                (x,) = _sublayer(x, p["hc"]["mlp"], config, eps, lambda u: (
                    _swiglu(_rms_norm(u, p["ln2"], eps), mlp["wg"], mlp["wu"], mlp["wd"]),))
                margin, sigma = jnp.full((T,), jnp.inf, F32), jnp.ones((), F32)
            else:
                x, margin, sigma = _sublayer(x, p["hc"]["mlp"], config, eps, lambda u: _experts(
                    _rms_norm(u, p["ln2"], eps), p["mlp"], sizes, compute))
            margins.append(margin)
            sigmas.append(sigma)
        residual.append(x.reshape(T, n * D))
        h = _rms_norm(x.astype(F32).sum(axis=1).astype(compute), params["final_norm"], eps)
        return (h @ params["lm_head"].astype(compute)).astype(F32), margins, sigmas, residual
