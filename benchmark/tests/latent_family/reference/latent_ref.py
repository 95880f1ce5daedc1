"""Plain reference of the fixture family of `benchmark/tests/latent_family`
(its `model.py` says what the family is): float32, whole sequence at once,
no cache, nothing imported from the served side. The contract is
README's ("A configuration"): `forward`, and `forward_routed` for a router.

`sizes` is `manifest.reference_sizes(mc, config)`: of its eight fields this
module reads num_heads, rope_theta, rms_norm_eps, num_experts,
num_experts_per_tok and tie_embeddings; the latent width is the family's
own key, read from the configuration file (`sizes["config"]["latent_dim"]`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(F32)).astype(x.dtype)


def _rope(x, positions, theta):
    """x [T, ..., R]; rotate-half."""
    r = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    x1, x2 = x[..., : r // 2].astype(F32), x[..., r // 2:].astype(F32)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1).astype(x.dtype)


def _attention(h, p, sizes, positions):
    T, H, R = h.shape[0], sizes["num_heads"], sizes["config"]["latent_dim"]
    q = _rope((h @ p["wq"]).reshape(T, H, R), positions, sizes["rope_theta"])
    latent = _rope(h @ p["wc"], positions, sizes["rope_theta"])      # [T, R]: key and value
    scores = jnp.einsum("thr,sr->hts", q, latent, preferred_element_type=F32) * (R ** -0.5)
    causal = positions[None, :] <= positions[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,sr->thr", probs.astype(h.dtype), latent).reshape(T, H * R)
    return out @ p["wo"]


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _experts(h, p, sizes):
    k = sizes["num_experts_per_tok"]
    logits = (h @ p["router"]).astype(F32)                           # [T, E]
    ranked = jax.lax.top_k(logits, k + 1)[0]
    margin = ranked[:, k - 1] - ranked[:, k]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(top_i, probs.shape[-1], dtype=F32) * top_w[..., None],
                      axis=-2)
    every = jax.vmap(lambda wg, wu, wd: _swiglu(h, wg, wu, wd))(p["wg"], p["wu"], p["wd"])
    return jnp.einsum("te,etd->td", combine.astype(h.dtype), every), (margin, jnp.std(logits))


def forward(params, sizes: dict, tokens, compute=F32):
    return _forward(params, sizes, tokens, compute)[0]


def forward_routed(params, sizes: dict, tokens):
    logits, (margin, sigma, entered), left = _forward(params, sizes, tokens, F32)
    return logits, margin, sigma, jnp.concatenate([entered, left[None]], axis=0)


def _forward(params, sizes: dict, tokens, compute):
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"][tokens].astype(compute)

        def layer(x, p):
            x_in = x
            p = jax.tree_util.tree_map(lambda a: a.astype(compute), p)
            h = _rms_norm(x, p["ln1"], sizes["rms_norm_eps"])
            x = x + _attention(h, p["attn"], sizes, positions)
            h = _rms_norm(x, p["ln2"], sizes["rms_norm_eps"])
            if sizes["num_experts"]:
                y, routed = _experts(h, p["mlp"], sizes)
                return x + y, (*routed, x_in)
            m = p["mlp"]
            return x + _swiglu(h, m["wg"], m["wu"], m["wd"]), None

        x, routed = jax.lax.scan(layer, x, params["layers"])
        h = _rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
        head = params["embed"].T if sizes["tie_embeddings"] else params["lm_head"]
        return (h @ head.astype(compute)).astype(F32), routed, x
