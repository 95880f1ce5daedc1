"""Plain reference forward of the Llama/Mistral/Mixtral block.

Straight ``jax.numpy`` in float32: RMSNorm, grouped-query attention with
rotate-half RoPE, SwiGLU or softmax-top-k experts (every expert computed,
combined with the renormalised top-k router weights: no capacity, no
drops). No cache, no kernels, no batching tricks, and nothing imported
from the program. ``jax.default_matmul_precision("highest")`` because a
float32 matmul on a TPU otherwise runs in bf16 passes.

Weights arrive in the dtype they are served in and are upcast one layer
at a time inside the scan, so the reference fits beside the engine.

A configuration names its reference module (``"reference"`` in
``configs/<config>.json``, default this one); ``harness/correct.py`` calls
``forward`` for a dense model and ``forward_routed`` for one with a router.
``compute`` is the type the arithmetic runs in. float32 is the reference.
The served type (bfloat16) is the same plain code with weights as they
come, the stream and every matmul's result rounded to that type, and
norms, softmax and rotary angles worked in float32 inside: not a second
opinion on the result, but the measure of how far rounding alone takes a
sound evaluation from float32 (``harness/correct.py``, the noise ratio).

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
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(F32)).astype(x.dtype)


def _rope(x, positions, theta):
    """x [T, H, D]; rotate-half convention (HF Llama/Mistral)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2].astype(F32), x[..., d // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attention(h, p, sizes, positions):
    T = h.shape[0]
    H, Hkv, D = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    q = _rope((h @ p["wq"]).reshape(T, H, D), positions, sizes["rope_theta"])
    k = _rope((h @ p["wk"]).reshape(T, Hkv, D), positions, sizes["rope_theta"])
    v = (h @ p["wv"]).reshape(T, Hkv, D)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k, preferred_element_type=F32) * (D ** -0.5)
    causal = positions[None, :] <= positions[:, None]  # [T(query), S(key)]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(T, H * D)
    return out @ p["wo"]


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _experts(h, p, sizes):
    """Mixtral: softmax over all experts, keep top-k, renormalise. Also the
    router's own account of each decision: the margin between the k-th and
    the (k+1)-th router logit at every position [T], and the standard
    deviation of this layer's router logits."""
    k = sizes["num_experts_per_tok"]
    logits = (h @ p["router"]).astype(F32)                      # [T, E]
    ranked = jax.lax.top_k(logits, k + 1)[0]
    margin = ranked[:, k - 1] - ranked[:, k]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    E = probs.shape[-1]
    combine = jnp.sum(jax.nn.one_hot(top_i, E, dtype=F32) * top_w[..., None],
                      axis=-2)                                  # [T, E]
    every = jax.vmap(lambda wg, wu, wd: _swiglu(h, wg, wu, wd))(
        p["wg"], p["wu"], p["wd"])                              # [E, T, D]
    out = jnp.einsum("te,etd->td", combine.astype(h.dtype), every)
    return out, (margin, jnp.std(logits))


def forward(params, sizes: dict, tokens, compute=F32):
    """tokens int32 [T] -> logits float32 [T, V], whole sequence at once."""
    return _forward(params, sizes, tokens, compute)[0]


def forward_routed(params, sizes: dict, tokens):
    """For a model with a router: (logits [T, V], margin [L, T], sigma [L],
    residual [L + 1, T, D]). `margin` is the k-th minus the (k+1)-th router
    logit of every layer's decision at every position and `sigma` the
    standard deviation of that layer's router logits, both from this float32
    evaluation and nothing else: which decisions can flip under rounding is
    the reference's word. `residual[l]` is the stream that enters layer l
    and `residual[L]` what leaves the last: `harness/correct.py` hands the
    program one layer at a time with the reference's own input to it."""
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
            x = x + _swiglu(h, m["wg"], m["wu"], m["wd"])
            return x, None

        x, routed = jax.lax.scan(layer, x, params["layers"])
        h = _rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
        head = params["embed"].T if sizes["tie_embeddings"] else params["lm_head"]
        return (h @ head.astype(compute)).astype(F32), routed, x
