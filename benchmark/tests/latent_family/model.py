"""A model family that is not Llama's, for the harness's own tests: the
program's side of the contract in `harness/correct.py`'s docstring, at a
size the CPU holds. Never served, never in BENCHMARK.json.

Latent attention of the plainest kind: a layer projects each token to ONE
row of `head_dim` values, rotates it, and that row is both key and value
for all `num_heads` query heads. So the cache is one array [L, B, S, R],
not a (K, V) pair, and `forward` takes and returns one. The feed-forward is
SwiGLU, or with `num_experts` a router over experts (softmax over all, top-k,
renormalised; every expert evaluated, nothing dropped). It takes the
program's flat ModelConfig: `head_dim` is the latent width, `num_kv_heads`
is not read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

F32 = jnp.float32


def init_params(cfg, key, dtype=jnp.bfloat16):
    L, D, F, V = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden_size, cfg.vocab_size
    H, R, E = cfg.num_heads, cfg.head_dim, cfg.num_experts
    keys = iter(jax.random.split(key, 12))

    def normal(shape, std=0.02):
        return (jax.random.normal(next(keys), shape, dtype=F32) * std).astype(dtype)

    lead = (L, E) if E else (L,)
    mlp = {"wg": normal((*lead, D, F)), "wu": normal((*lead, D, F)),
           "wd": normal((*lead, F, D), std=0.02 / (2 * L) ** 0.5)}
    if E:
        mlp["router"] = normal((L, D, E))
    params = {
        "embed": normal((V, D)),
        "layers": {
            "ln1": jnp.ones((L, D), dtype), "ln2": jnp.ones((L, D), dtype),
            "attn": {"wq": normal((L, D, H * R)), "wc": normal((L, D, R)),
                     "wo": normal((L, H * R, D), std=0.02 / (2 * L) ** 0.5)},
            "mlp": mlp,
        },
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal((D, V))
    return params


def param_specs(cfg):
    """Everything replicated: the fixture is never sharded."""
    return jax.tree_util.tree_map(lambda _: P(), jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))


def init_kv_cache(cfg, batch: int, rows: int, dtype=jnp.bfloat16):
    return (jnp.zeros((cfg.num_layers, batch, rows, cfg.head_dim), dtype),)


def kv_cache_specs(kv_quant=None) -> tuple:
    return (P(),)


def _rms_norm(x, w, eps):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(F32)).astype(x.dtype)


def _rope(x, positions, theta):
    """x [B, T, ..., R], positions [B, T]; rotate-half."""
    r = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = positions.astype(F32)[..., None] * inv_freq
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    x1, x2 = x[..., : r // 2].astype(F32), x[..., r // 2:].astype(F32)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1).astype(x.dtype)


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _mlp(h, p, cfg):
    if not cfg.num_experts:
        return _swiglu(h, p["wg"], p["wu"], p["wd"])
    probs = jax.nn.softmax((h @ p["router"]).astype(F32), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(top_i, cfg.num_experts, dtype=F32) * top_w[..., None],
                      axis=-2)                                        # [B, T, E]
    every = jax.vmap(lambda wg, wu, wd: _swiglu(h, wg, wu, wd))(p["wg"], p["wu"], p["wd"])
    return jnp.einsum("bte,ebtd->btd", combine.astype(h.dtype), every)


def forward(params, cfg, tokens, positions, cache, start, mesh=None):
    """tokens, positions int32 [B, T]; cache [L, B, S, R]; start int32 [B],
    the row where this chunk's latents land. -> (logits f32 [B, T, V], cache)."""
    B, T = tokens.shape
    H, R, S = cfg.num_heads, cfg.head_dim, cache.shape[2]
    live = jnp.arange(S)[None, None, :] <= positions[:, :, None]      # [B, T, S]

    def layer(carry, scanned):
        x, cache = carry
        p, l = scanned
        h = _rms_norm(x, p["ln1"], cfg.rms_norm_eps)
        q = _rope((h @ p["attn"]["wq"]).reshape(B, T, H, R), positions, cfg.rope_theta)
        new = _rope(h @ p["attn"]["wc"], positions, cfg.rope_theta)   # [B, T, R]
        rows = jax.vmap(lambda c, n, s: jax.lax.dynamic_update_slice(c, n, (s, 0)))(
            cache[l], new, start)                                     # [B, S, R]
        cache = jax.lax.dynamic_update_index_in_dim(cache, rows, l, axis=0)
        scores = jnp.einsum("bthr,bsr->bhts", q, rows, preferred_element_type=F32) * R ** -0.5
        probs = jax.nn.softmax(jnp.where(live[:, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhts,bsr->bthr", probs.astype(x.dtype), rows).reshape(B, T, H * R)
        x = x + out @ p["attn"]["wo"]
        h = _rms_norm(x, p["ln2"], cfg.rms_norm_eps)
        return (x + _mlp(h, p["mlp"], cfg), cache), None

    (x, cache), _ = jax.lax.scan(
        layer, (params["embed"][tokens], cache),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    h = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (h @ head).astype(F32), cache
