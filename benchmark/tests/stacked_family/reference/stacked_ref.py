"""Plain reference of the fixture family of `benchmark/tests/stacked_family`
(its `model.py` says what the family is): float32, whole sequence at once,
no cache, nothing imported from the served side. The contract is README's
("A configuration"): `forward`, `forward_routed`, and, because `layers` is
a sequence of stacks, `layer_order`.

Of `sizes` this module reads num_heads, rope_theta, rms_norm_eps,
num_experts_per_tok and tie_embeddings; the family's own keys it reads from
the configuration file (`sizes["config"]`: `latent_dim`, `hidden_size`,
`residual_copies`, `layer_kinds`). The order of the tree it is handed is
`sizes["layer_order"]` where `harness/correct.py` has cut it, else the
file's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
STACK_OF = {"dense": 0, "sparse": 1}


def layer_order(sizes: dict) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ..., from the file's
    `layer_kinds`."""
    seen, order = [0, 0], []
    for kind in sizes["config"]["layer_kinds"]:
        order.append((STACK_OF[kind], seen[STACK_OF[kind]]))
        seen[STACK_OF[kind]] += 1
    return tuple(order)


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


def _attention(h, p, rotate: bool, sizes, positions):
    T, H, R = h.shape[0], sizes["num_heads"], sizes["config"]["latent_dim"]
    q, latent = (h @ p["wq"]).reshape(T, H, R), h @ p["wc"]          # latent [T, R]: key and value
    if rotate:
        q, latent = _rope(q, positions, sizes["rope_theta"]), _rope(
            latent, positions, sizes["rope_theta"])
    scores = jnp.einsum("thr,sr->hts", q, latent, preferred_element_type=F32) * (R ** -0.5)
    causal = positions[None, :] <= positions[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,sr->thr", probs.astype(h.dtype), latent).reshape(T, H * R)
    return out @ p["wo"]


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]


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
    every = jax.vmap(lambda e: _swiglu(h, e))(p["experts"])          # [E, T, D]
    routed = jnp.einsum("te,etd->td", combine.astype(h.dtype), every)
    return routed + _swiglu(h, p["shared"]), margin, jnp.std(logits)


def _sub_block(x, hc, block):
    """x [T, n, D] -> x [T, n, D]: the copies folded into the block's input
    by `pre`, mixed by `mix`, the block's output added to each by `post`."""
    y, *rest = block(jnp.einsum("n,tnd->td", hc["pre"], x))
    return (jnp.einsum("mn,tnd->tmd", hc["mix"], x) + hc["post"][:, None] * y[:, None, :], *rest)


def forward(params, sizes: dict, tokens, compute=F32):
    return _forward(params, sizes, tokens, compute)[0]


def forward_routed(params, sizes: dict, tokens):
    """A layer without a router decides every position: margin inf, sigma 1."""
    logits, margin, sigma, residual = _forward(params, sizes, tokens, F32)
    return logits, jnp.stack(margin), jnp.stack(sigma), jnp.stack(residual)


def _forward(params, sizes: dict, tokens, compute):
    file = sizes["config"]
    n, D, eps = file["residual_copies"], file["hidden_size"], sizes["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        positions = jnp.arange(T, dtype=jnp.int32)
        x = params["embed"][tokens].astype(compute)
        # A table of the model's width is copied n times; one n * D wide is the copies.
        x = jnp.repeat(x[:, None, :], n, axis=1) if x.shape[-1] == D else x.reshape(T, n, D)
        margins, sigmas, residual = [], [], []
        for stack, index in sizes.get("layer_order") or layer_order(sizes):
            p = jax.tree_util.tree_map(lambda a: a[index].astype(compute),
                                       params["layers"][stack])
            residual.append(x.reshape(T, n * D))
            (x,) = _sub_block(x, p["hc"]["attn"], lambda h: (_attention(
                _rms_norm(h, p["ln1"], eps), p["attn"], stack == 0, sizes, positions),))
            if stack == 0:
                (x,) = _sub_block(x, p["hc"]["mlp"], lambda h: (
                    _swiglu(_rms_norm(h, p["ln2"], eps), p["mlp"]),))
                margin, sigma = jnp.full((T,), jnp.inf, F32), jnp.ones((), F32)
            else:
                x, margin, sigma = _sub_block(x, p["hc"]["mlp"], lambda h: _experts(
                    _rms_norm(h, p["ln2"], eps), p["mlp"], sizes))
            margins.append(margin)
            sigmas.append(sigma)
        residual.append(x.reshape(T, n * D))
        h = _rms_norm(x.sum(axis=1), params["final_norm"], eps)
        head = params["embed"].T if sizes["tie_embeddings"] else params["lm_head"]
        return (h @ head.astype(compute)).astype(F32), margins, sigmas, residual
