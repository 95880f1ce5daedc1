"""Plain reference forward of the Llama/Mistral/Mixtral block.

Straight ``jax.numpy`` in float32: RMSNorm, grouped-query attention with
rotate-half RoPE, SwiGLU or softmax-top-k experts (every expert computed,
combined with the renormalised top-k router weights: no capacity, no
drops). No cache, no kernels, no batching tricks, and nothing imported
from the program. ``jax.default_matmul_precision("highest")`` because a
float32 matmul on a TPU otherwise runs in bf16 passes.

Weights arrive in the dtype they are served in and are upcast one layer
at a time inside the scan, so the reference fits beside the engine.

``sizes`` is a plain dict: num_heads, num_kv_heads, head_dim, rope_theta,
rms_norm_eps, num_experts (0 = dense), num_experts_per_tok, tie_embeddings.
The parameter tree is the one `models/llama.py::init_params` documents:
embed [V, D], layers/{ln1, ln2, attn/{wq, wk, wv, wo}, mlp/{wg, wu, wd
[, router]}} stacked on a leading layer axis, final_norm, lm_head [D, V].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x [T, H, D]; rotate-half convention (HF Llama/Mistral)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(h, p, sizes, positions):
    T = h.shape[0]
    H, Hkv, D = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    q = _rope((h @ p["wq"]).reshape(T, H, D), positions, sizes["rope_theta"])
    k = _rope((h @ p["wk"]).reshape(T, Hkv, D), positions, sizes["rope_theta"])
    v = (h @ p["wv"]).reshape(T, Hkv, D)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * (D ** -0.5)
    causal = positions[None, :] <= positions[:, None]  # [T(query), S(key)]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(T, H * D)
    return out @ p["wo"]


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _experts(h, p, sizes):
    """Mixtral: softmax over all experts, keep top-k, renormalise."""
    k = sizes["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ p["router"], axis=-1)           # [T, E]
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    E = probs.shape[-1]
    combine = jnp.sum(jax.nn.one_hot(top_i, E, dtype=F32) * top_w[..., None],
                      axis=-2)                                  # [T, E]
    every = jax.vmap(lambda wg, wu, wd: _swiglu(h, wg, wu, wd))(
        p["wg"], p["wu"], p["wd"])                              # [E, T, D]
    return jnp.einsum("te,etd->td", combine, every)


def forward(params, sizes: dict, tokens):
    """tokens int32 [T] -> logits float32 [T, V], whole sequence at once."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"][tokens].astype(F32)

        def layer(x, p):
            p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
            h = _rms_norm(x, p["ln1"], sizes["rms_norm_eps"])
            x = x + _attention(h, p["attn"], sizes, positions)
            h = _rms_norm(x, p["ln2"], sizes["rms_norm_eps"])
            if sizes["num_experts"]:
                x = x + _experts(h, p["mlp"], sizes)
            else:
                m = p["mlp"]
                x = x + _swiglu(h, m["wg"], m["wu"], m["wd"])
            return x, None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms_norm(x, params["final_norm"].astype(F32), sizes["rms_norm_eps"])
        if sizes["tie_embeddings"]:
            return x @ params["embed"].astype(F32).T
        return x @ params["lm_head"].astype(F32)
