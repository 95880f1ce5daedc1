"""A model family whose layers are not all alike, for the harness's own
tests: the program's side of the contract in `harness/correct.py`'s
docstring, with `layers` as stacks, at a size the CPU holds. Never in
BENCHMARK.json.

Two kinds of layer, a stack each (`KINDS`): `dense` (stack 0) rotates its
query and latent row by position and has a SwiGLU feed-forward; `sparse`
(stack 1) attends without positions and has a router over experts (softmax
over all, top-k, renormalised; every expert evaluated) beside a shared
expert that every token takes. Attention is `latent_family`'s: one row of
`head_dim` values a token a layer, key and value for every head, so the
cache is one array [L, B, S, R] over the model's layers in the order it
runs them. Only the sparse kind's attention goes by the decode kernel's
name the configuration files give (`program.decode_kernel_layers`).

The residual is `n` copies a token (a hyper-connection path): before a
sub-block a learned map `pre` [n] folds the copies into its input, after it
`mix` [n, n] mixes the copies and `post` [n] adds the sub-block's output to
each. So the stream is [.., n * D], `embed` [V, D] is copied n times to
start it, and a table that is already n * D wide is the stream itself.

The program's flat ModelConfig has no field for a list of kinds or a number
of copies, so the fixture borrows two it does not use otherwise:
`rope_scaling` holds the kinds of the model's layers in the order it runs
them (the file's `layer_kinds`), `num_kv_heads` the copies
(`residual_copies`). A family the program serves has fields of its own,
which its `model_config` PR adds.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

F32 = jnp.float32
KINDS = ("dense", "sparse")  # stack k holds the layers of KINDS[k]


def layer_order(cfg) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ...: a stack's layers in
    the order of its axis."""
    seen = [0] * len(KINDS)
    order = []
    for kind in cfg.rope_scaling:
        stack = KINDS.index(kind)
        order.append((stack, seen[stack]))
        seen[stack] += 1
    return tuple(order)


def with_layer_order(cfg, order):
    """The ModelConfig of the same model with the layers `order` names."""
    return dataclasses.replace(cfg, num_layers=len(order),
                               rope_scaling=tuple(KINDS[s] for s, _ in order))


def _schedule(cfg) -> tuple:
    """(stack, index, cache layer) of each model layer, as `forward` runs them."""
    return tuple((s, i, l) for l, (s, i) in enumerate(layer_order(cfg)))


def init_params(cfg, key, dtype=jnp.bfloat16):
    D, V, H, R, n = cfg.hidden_size, cfg.vocab_size, cfg.num_heads, cfg.head_dim, cfg.num_kv_heads
    F, Fm, E = cfg.ffn_hidden_size, cfg.moe_ffn_hidden_size, cfg.num_experts
    L = cfg.num_layers
    counts = [sum(s == k for s, _ in layer_order(cfg)) for k in range(len(KINDS))]
    keys = iter(jax.random.split(key, 64))

    def normal(shape, std=0.02, mean=0.0):
        return (mean + jax.random.normal(next(keys), shape, dtype=F32) * std).astype(dtype)

    def shared(c):  # what every kind of layer has
        def hyper():
            return {"pre": normal((c, n), 0.1, 1.0 / n), "post": normal((c, n), 0.1, 1.0),
                    "mix": normal((c, n, n), 0.1) + jnp.eye(n, dtype=dtype)}
        return {"ln1": jnp.ones((c, D), dtype), "ln2": jnp.ones((c, D), dtype),
                "hc": {"attn": hyper(), "mlp": hyper()},
                "attn": {"wq": normal((c, D, H * R)), "wc": normal((c, D, R)),
                         "wo": normal((c, H * R, D), std=0.02 / (2 * L) ** 0.5)}}

    def swiglu(lead, width):
        return {"wg": normal((*lead, D, width)), "wu": normal((*lead, D, width)),
                "wd": normal((*lead, width, D), std=0.02 / (2 * L) ** 0.5)}

    dense, sparse = counts
    params = {
        "embed": normal((V, D)),
        "layers": [
            {**shared(dense), "mlp": swiglu((dense,), F)},
            {**shared(sparse), "mlp": {"router": normal((sparse, D, E)),
                                       "experts": swiglu((sparse, E), Fm),
                                       "shared": swiglu((sparse,), Fm)}},
        ],
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


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]


def _experts(h, p, cfg):
    probs = jax.nn.softmax((h @ p["router"]).astype(F32), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(top_i, cfg.num_experts, dtype=F32) * top_w[..., None],
                      axis=-2)                                        # [B, T, E]
    every = jax.vmap(lambda e: _swiglu(h, e))(p["experts"])          # [E, B, T, D]
    return jnp.einsum("bte,ebtd->btd", combine.astype(h.dtype), every) + _swiglu(h, p["shared"])


@jax.named_scope("hc.mix")
def _fold(x, hc):
    """The sub-block's input: the copies [B, T, n, D] folded by `pre`."""
    return jnp.einsum("n,btnd->btd", hc["pre"], x)


@jax.named_scope("hc.mix")
def _unfold(x, y, hc):
    """The copies mixed by `mix`, the sub-block's output added by `post`."""
    return jnp.einsum("mn,btnd->btmd", hc["mix"], x) + hc["post"][:, None] * y[:, :, None, :]


def _expand(x, cfg):
    """The stream as copies [B, T, n, D]: a table of the model's width is
    copied n times, one that is n * D wide is the copies themselves."""
    n, D = cfg.num_kv_heads, cfg.hidden_size
    if x.shape[-1] == D:
        return jnp.repeat(x[:, :, None, :], n, axis=2)
    return x.reshape(*x.shape[:2], n, D)


def _attention(h, p, kind, positions, rows_of, cfg):
    """-> (output [B, T, H * R], the layer's rows [B, S, R] with this chunk's)."""
    B, T = h.shape[:2]
    H, R = cfg.num_heads, cfg.head_dim
    q, new = (h @ p["wq"]).reshape(B, T, H, R), h @ p["wc"]
    if kind == "dense":
        q, new = _rope(q, positions, cfg.rope_theta), _rope(new, positions, cfg.rope_theta)
    rows = rows_of(new)
    live = jnp.arange(rows.shape[1])[None, None, :] <= positions[:, :, None]      # [B, T, S]
    scores = jnp.einsum("bthr,bsr->bhts", q, rows, preferred_element_type=F32) * R ** -0.5
    probs = jax.nn.softmax(jnp.where(live[:, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bsr->bthr", probs.astype(h.dtype), rows).reshape(B, T, H * R), rows


def forward(params, cfg, tokens, positions, cache, start, mesh=None):
    """tokens, positions int32 [B, T]; cache [L, B, S, R]; start int32 [B],
    the row where this chunk's latents land. -> (logits f32 [B, T, V], cache)."""
    x = _expand(params["embed"][tokens], cfg)
    for stack, index, at in _schedule(cfg):
        p = jax.tree_util.tree_map(lambda a: a[index], params["layers"][stack])
        kind = KINDS[stack]

        def rows_of(new, at=at):
            return jax.vmap(lambda c, n, s: jax.lax.dynamic_update_slice(c, n, (s, 0)))(
                cache[at], new, start)

        h = _rms_norm(_fold(x, p["hc"]["attn"]), p["ln1"], cfg.rms_norm_eps)
        out, rows = _attention(h, p["attn"], kind, positions, rows_of, cfg)
        cache = cache.at[at].set(rows)
        x = _unfold(x, out @ p["attn"]["wo"], p["hc"]["attn"])
        h = _rms_norm(_fold(x, p["hc"]["mlp"]), p["ln2"], cfg.rms_norm_eps)
        y = _swiglu(h, p["mlp"]) if kind == "dense" else _experts(h, p["mlp"], cfg)
        x = _unfold(x, y, p["hc"]["mlp"])
    h = _rms_norm(x.sum(axis=2), params["final_norm"], cfg.rms_norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (h @ head).astype(F32), cache
