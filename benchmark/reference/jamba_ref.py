"""Plain reference forward of the Jamba (`jamba`) model as AI21-Jamba2-3B
configures it: Mamba-1 selective-state-space layers beside softmax attention
without any positional encoding, pre-norm blocks, a dense SwiGLU in every
layer (`num_experts` 1), the head tied to the embedding table.

Straight `jax.numpy` in float32, `jax.default_matmul_precision("highest")`:
no cache, no chunks, no kernel, no batching, the whole sequence at once, a
layer at a time in the model's order, the state-space layer as its per-token
recurrence exactly as written below (a `lax.scan` a token), the convolution as
a sum over its taps. Nothing is imported from the program. Weights arrive in
the type they are served in and are upcast a layer at a time.

**Which layer is what.** Layer l is attention where `l mod attn_layer_period
== attn_layer_offset` and Mamba else (`JambaConfig.layers_block_type`); the
file states the same list under `assumed.layer_types` ("full_attention" /
"mamba"), which is what is read here, and `benchmark/tests/test_jamba2.py`
holds the list equal to the two keys. The depth is the tree's, never the
file's.

**A Mamba layer**, for its normed input `h` [T, D], E = `mamba_expand` x D
channels, N = `mamba_d_state`, R = `mamba_dt_rank`, K = `mamba_d_conv`:

- `[u | z] = h W_in`, [T, E | E], no bias (`mamba_proj_bias` false).
- `u'_t = silu(sum_{j=0..K-1} conv[j] * u_{t-(K-1)+j} + conv_b)`: depthwise and
  causal, rows before position 0 are zero, a bias a channel
  (`mamba_conv_bias`).
- `[d | B | C] = u' W_x`, [T, R | N | N], no bias; each RMS-normed over its own
  width with a gain of its own and `rms_norm_eps` (`assumed.mamba_inner_norms`:
  Jamba's dt_layernorm, b_layernorm, c_layernorm).
- `D_t = softplus(d W_dt + dt_bias)` [T, E]; `A = -exp(A_log)`.
- **The selective scan**, S in R^{N x E} float32, S_0 = 0:
  `S_t[n, c] = exp(D_t[c] A[n, c]) S_{t-1}[n, c] + D_t[c] B_t[n] u'_t[c]`;
  `y_t[c] = sum_n S_t[n, c] C_t[n] + d_skip[c] u'_t[c]`.
- the sublayer's output is `(y * silu(z)) W_out`, no bias.

**An attention layer**: `q = h Wq` (`num_attention_heads` heads of `head_dim`),
`k = h Wk`, `v = h Wv` (`num_key_value_heads` heads, each shared by a group of
query heads), no bias, **no rotation** (Jamba has no positional encoding:
position comes from the Mamba layers), scores `q . k head_dim^-0.5`, causal
softmax, `Wo`.

**The block**: `x <- x + mixer(rms(x; ln1))`; `x <- x + (silu(h Wg) * (h Wu))
Wd` with `h = rms(x; ln2)`. Final `rms`, and the head is the table transposed
(`tie_word_embeddings`) unless `sizes["tie_embeddings"]` is false (a cut
model's: `harness/correct.py` names the head then).

**Departures from the published description.** One of layout only: the
program's tree holds `A_log` as [N, E] (the state's own order, the channels
along the lanes) where the published checkpoint holds [E, N]; it is read here
as handed. What `config.json` does not settle is each under the file's
`assumed` and followed here: the order of the layers, the three inner norms,
`head_dim`, no rotation, the state in float32. `compute` other than float32
rounds the stream and every matmul's result to it; the convolution, the
norms, the softplus, the exponent and the recurrence stay in float32, as the
configuration states.

The attention layers' scores of a long sequence are computed a block of
`QUERY_BLOCK` queries at a time against every key, and the recurrence is a
`lax.scan` a token, so some thousands of tokens need neither [H, T, T] nor T
states at once.

`sizes` is `manifest.reference_sizes`: this module reads `rms_norm_eps`,
`tie_embeddings` and, under `"config"`, the file's own keys (never the depth
of the tree it is handed, and its order `sizes["layer_order"]` where
`harness/correct.py` has cut it, else the file's, `layer_order`). The
parameter tree is `omnia_tpu/models/stacks.py::_init_stacks`'s: `layers` is a
list of stacks, the attention layers' before the Mamba layers', each {ln1,
ln2, attn/{wq, wk, wv, wo} or attn/{win, conv [K, E], conv_b [E], wx, dtn, bn,
cn, wdt, dt_bias [E], a_log [N, E], d [E], wo}, mlp/{wg, wu, wd}} led by its own
layer axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
_NEG = -1e30
_KINDS = ("full_attention", "mamba")  # the stacks' order


def _key(config: dict, key: str):
    """A key of the file, or of its `assumed` where the source lacks it."""
    return config[key] if key in config else config["assumed"][key]


def _file_kinds(config: dict) -> list:
    return list(_key(config, "layer_types")[:config["num_hidden_layers"]])


def stack_kinds(sizes: dict) -> tuple:
    """The kind of each stack of `params["layers"]`: those the file's model
    has a layer of, in the order of `_KINDS`."""
    have = set(_file_kinds(sizes["config"]))
    return tuple(kind for kind in _KINDS if kind in have)


def layer_order(sizes: dict) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ...: a layer lies in the
    stack of its kind, behind the earlier layers of that kind."""
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


def _mamba(h, p, sizes: dict):
    """h [T, D] (the compute type, normed) -> the sublayer's output [T, D]. `p`
    holds the compute type's matrices; the taps, their bias, the norms' gains,
    `dt_bias`, `a_log` and `d` are read in float32."""
    config, eps = sizes["config"], sizes["rms_norm_eps"]
    if config["mamba_proj_bias"] or not config["mamba_conv_bias"]:
        raise NotImplementedError("this reference has a bias on the convolution and "
                                  "none on the projections")
    N, R, K = config["mamba_d_state"], config["mamba_dt_rank"], config["mamba_d_conv"]
    T = h.shape[0]
    u, z = jnp.split(h @ p["win"], 2, axis=-1)                         # [T, E] each
    rows = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u.astype(F32)], axis=0)
    u = jax.nn.silu(sum(p["conv"][j] * rows[j:j + T] for j in range(K)) + p["conv_b"])
    dbc = (u.astype(h.dtype) @ p["wx"]).astype(F32)                    # [T, R + 2 N]
    d, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    if _key(config, "mamba_inner_norms"):
        d, B, C = (_rms_norm(t, p[gain], eps) for t, gain in ((d, "dtn"), (B, "bn"), (C, "cn")))
    delta = jax.nn.softplus((d.astype(h.dtype) @ p["wdt"]).astype(F32) + p["dt_bias"])
    A = -jnp.exp(p["a_log"])                                           # [N, E]

    def token(S, x):  # S [N, E]
        u, delta, B, C = x
        S = jnp.exp(delta[None, :] * A) * S + B[:, None] * (delta * u)[None, :]
        return S, jnp.sum(S * C[:, None], axis=0) + p["d"] * u

    _, y = jax.lax.scan(token, jnp.zeros(A.shape, F32), (u, delta, B, C))
    return (y * jax.nn.silu(z.astype(F32))).astype(h.dtype) @ p["wo"]


def _attention(h, p, sizes: dict, positions):
    """h [T, D] -> [T, D]: softmax attention over every earlier row, a KV head
    shared by its group of query heads, no rotation."""
    config = sizes["config"]
    T, H, Hkv = h.shape[0], config["num_attention_heads"], config["num_key_value_heads"]
    d = _key(config, "head_dim")
    if _key(config, "rope_on_full_layers"):
        raise NotImplementedError("an attention layer with rotary position is not written here")
    q = (h @ p["wq"]).reshape(T, Hkv, H // Hkv, d)
    k = (h @ p["wk"]).reshape(T, Hkv, d)
    v = (h @ p["wv"]).reshape(T, Hkv, d)
    out = []
    for lo in range(0, T, QUERY_BLOCK):  # a block of queries against every key
        scores = jnp.einsum("tkgd,skd->kgts", q[lo:lo + QUERY_BLOCK], k,
                            preferred_element_type=F32) * d ** -0.5
        seen = positions[None, :] <= positions[lo:lo + QUERY_BLOCK, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, _NEG), axis=-1)
        out.append(jnp.einsum("kgts,skd->tkgd", probs.astype(v.dtype), v))
    return jnp.concatenate(out, axis=0).reshape(T, H * d) @ p["wo"]


_FLOAT32_LEAVES = ("conv", "conv_b", "dtn", "bn", "cn", "dt_bias", "a_log", "d")


def forward(params, sizes: dict, tokens, compute=F32):
    """tokens int32 [T] -> logits float32 [T, V], whole sequence at once."""
    eps = sizes["rms_norm_eps"]
    stacks = stack_kinds(sizes)
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"][tokens].astype(compute)
        for stack, index in sizes.get("layer_order") or layer_order(sizes):
            p = jax.tree_util.tree_map(lambda a: a[index], params["layers"][stack])
            attn = {name: a.astype(F32 if name in _FLOAT32_LEAVES else compute)
                    for name, a in p["attn"].items()}
            h = _rms_norm(x, p["ln1"], eps)
            x = x + (_mamba(h, attn, sizes) if stacks[stack] == "mamba"
                     else _attention(h, attn, sizes, positions))
            mlp = jax.tree_util.tree_map(lambda a: a.astype(compute), p["mlp"])
            h = _rms_norm(x, p["ln2"], eps)
            x = x + (jax.nn.silu(h @ mlp["wg"]) * (h @ mlp["wu"])) @ mlp["wd"]
        h = _rms_norm(x, params["final_norm"], eps)
        head = params["embed"].T if sizes.get("tie_embeddings") else params["lm_head"]
        return (h @ head.astype(compute)).astype(F32)
