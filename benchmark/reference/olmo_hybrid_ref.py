"""Plain reference forward of the Olmo-Hybrid (`olmo_hybrid`) model: layers
of gated delta-rule linear attention with one decay a head beside full
softmax attention without rotary position, every block's two RMSNorms on
its sublayers' outputs, a dense SwiGLU in every layer.

Straight `jax.numpy` in float32, `jax.default_matmul_precision("highest")`:
no cache, no chunks, no kernel, no batching, the whole sequence at once, a
layer at a time in the model's order, the linear attention as its per-token
recurrence exactly as written below, the convolution as a sum over its
taps. Nothing is imported from the program. Weights arrive in the type they
are served in and are upcast a layer at a time.

**Which layer is what.** `layer_types[l]` is "linear_attention" or
"full_attention" (the source's own list, kept whole; the first layers of the
tree's depth are run). The depth is the tree's, never the file's.

**A linear layer**, for its input `x` [T, D] (the stream itself: nothing is
normed in front), H = `linear_num_key_heads` = `linear_num_value_heads`
heads whose keys are dk = `linear_key_head_dim` and values dv =
`linear_value_head_dim` wide, K = `linear_conv_kernel_dim` taps:

- `[q~ | k~ | v~] = x Wqkv`, [T, H dk | H dk | H dv].
- A depthwise causal convolution over time on each column, then silu:
  `u'_t = silu(sum_{j=0..K-1} conv[j] * u~_{t-(K-1)+j})`, rows before position
  0 are zero, no bias (`assumed.conv_bias`).
- `q_t = l2norm(q'_t) dk^-0.5`, `k_t = l2norm(k'_t)` a head, `l2norm(x) = x /
  sqrt(sum x^2 + 1e-6)` (`assumed.l2norm_eps`); `v_t = v'_t`.
- One log-decay a head, `g_t = -exp(A_log) softplus(x Wa + dt_bias)` [H] in
  float32, `alpha_t = exp(g_t)`; `beta_t = 2 sigmoid(x Wb)` [H], in (0, 2): the
  factor 2 is `linear_allow_neg_eigval` (without it 1).
- **The gated delta rule**, a head, S in R^{dk x dv} float32, S_0 = 0:
  `S' = alpha_t S_{t-1}`; `S_t = S' + beta_t k_t (v_t - S'^T k_t)^T`;
  `o_t = S_t^T q_t`. The decay comes before the update.
- `y_t = rms(o_t; on) * silu(x Wg)` a head (`on` [dv]; `Wg` [D, H dv] of full
  rank; the activation is `hidden_act`); the sublayer's output is `y Wo`.

**A full layer** (H = `num_attention_heads` = `num_key_value_heads` heads of
`head_dim` = `hidden_size` / H): `q = rms(x Wq; qn)`, `k = rms(x Wk; kn)` over the
WHOLE projected width (gains [H head_dim]) before the heads are split
(`assumed.qk_norm`), `v = x Wv`; **no rotary position** (`rope_parameters.
rope_theta` null); scores `q . k head_dim^-0.5`, causal softmax; the
sublayer's output is `(softmax . v) Wo`.

**The block** (`assumed.norm_placement`): `x <- x + rms(mixer(x); ln1)`; `x <- x +
rms((silu(x Wg) * (x Wu)) Wd; ln2)`; nothing is normed in front of either.
Final `rms` and the untied head over the whole vocabulary.

**Departures from the published description**: none that is known. What
`config.json` does not settle is each under the file's `assumed` and followed
here: the norms' placement and the whole-width QK-norm (the OLMo 2/3 block),
`A_log` and `dt_bias` a head, no bias on the convolutions, the SiLU output
gate, the l2norm's epsilon inside the root, the state in float32, the
final norm. `compute` other than float32 rounds the stream and every
matmul's result to it; the convolution, the norms, the gates and the
recurrence stay in float32, as the configuration states.

The full layers' scores of a long sequence are computed a block of
`QUERY_BLOCK` queries at a time against every key, and the recurrence is a
`lax.scan` a token, so some thousands of tokens need neither [H, T, T] nor T
states at once.

`sizes` is `manifest.reference_sizes`: this module reads `rms_norm_eps` and,
under `"config"`, the file's own keys (never the depth of the tree it is
handed, and its order `sizes["layer_order"]` where `harness/correct.py` has
cut it, else the file's, `layer_order`). The parameter tree is
`omnia_tpu/models/stacks.py::_init_stacks`'s: `layers` is a list of stacks,
one for each kind of layer the file's model has, full layers' before linear
ones', each {ln1, ln2, attn/{wq, wk, wv, qn, kn, wo} or attn/{wqkv, conv [K, 2
H dk + H dv], wa, wb, dt_bias, a_log, wg, on, wo}, mlp/{wg, wu, wd}} led by its
own layer axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
_NEG = -1e30
_KINDS = ("full_attention", "linear_attention")  # the stacks' order


def _key(config: dict, key: str):
    """A key of the file, or of its `assumed` where the source lacks it."""
    return config[key] if key in config else config["assumed"][key]


def _file_kinds(config: dict) -> list:
    return list(config["layer_types"][:config["num_hidden_layers"]])


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


def _l2norm(x, eps):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _linear(x, p, sizes: dict):
    """x [T, D] (the compute type) -> the sublayer's output [T, D]. `p` holds
    the compute type's matrices; `conv`, `dt_bias`, `a_log` and `on` are read
    in float32."""
    config = sizes["config"]
    H, dk, dv = (config["linear_num_key_heads"], config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    if config["linear_num_value_heads"] != H:
        raise NotImplementedError("more value heads than key heads is not written here")
    K, T = config["linear_conv_kernel_dim"], x.shape[0]
    pre = (x @ p["wqkv"]).astype(F32)                                  # [T, 2 H dk + H dv]
    rows = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1]), F32), pre], axis=0)
    conv = p["conv"].astype(F32)
    u = jax.nn.silu(sum(conv[j] * rows[j:j + T] for j in range(K)))
    q = u[:, :H * dk].reshape(T, H, dk)
    k = u[:, H * dk:2 * H * dk].reshape(T, H, dk)
    v = u[:, 2 * H * dk:].reshape(T, H, dv)
    eps = float(_key(config, "l2norm_eps"))
    q, k = _l2norm(q, eps) * dk ** -0.5, _l2norm(k, eps)
    g = -jnp.exp(p["a_log"].astype(F32)) * jax.nn.softplus(
        (x @ p["wa"]).astype(F32) + p["dt_bias"].astype(F32))          # [T, H]
    beta = jax.nn.sigmoid((x @ p["wb"]).astype(F32))
    if config["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    gate = jax.nn.silu((x @ p["wg"]).astype(F32)).reshape(T, H, dv)

    def token(S, x):  # S [H, dk, dv]
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, None, None] * S
        r = jnp.einsum("hkv,hk->hv", S, k)
        S = S + k[:, :, None] * (beta[:, None] * (v - r))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), F32), (q, k, v, g, beta))
    y = _rms_norm(o, p["on"], sizes["rms_norm_eps"]) * gate
    return y.reshape(T, H * dv).astype(x.dtype) @ p["wo"]


def _full(x, p, sizes: dict, positions):
    """x [T, D] -> [T, D]: softmax attention over every earlier row, q and k
    RMS-normed over their whole width, no rotary position."""
    config = sizes["config"]
    if config["rope_parameters"].get("rope_theta") is not None:
        raise NotImplementedError("a full layer with rotary position is not written here")
    T, H = x.shape[0], config["num_attention_heads"]
    if config["num_key_value_heads"] != H:
        raise NotImplementedError("grouped key-value heads are not written here")
    d = _key(config, "head_dim")
    eps = sizes["rms_norm_eps"]
    q = _rms_norm(x @ p["wq"], p["qn"], eps).reshape(T, H, d)
    k = _rms_norm(x @ p["wk"], p["kn"], eps).reshape(T, H, d)
    v = (x @ p["wv"]).reshape(T, H, d)
    out = []
    for lo in range(0, T, QUERY_BLOCK):  # a block of queries against every key
        scores = jnp.einsum("thd,shd->hts", q[lo:lo + QUERY_BLOCK], k,
                            preferred_element_type=F32) * d ** -0.5
        seen = positions[None, :] <= positions[lo:lo + QUERY_BLOCK, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, _NEG), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", probs.astype(v.dtype), v))
    return jnp.concatenate(out, axis=0).reshape(T, H * d) @ p["wo"]


_FLOAT32_LEAVES = ("conv", "dt_bias", "a_log", "on")


def forward(params, sizes: dict, tokens, compute=F32):
    """tokens int32 [T] -> logits float32 [T, V], whole sequence at once."""
    config, eps = sizes["config"], sizes["rms_norm_eps"]
    if _key(config, "norm_placement") != "post" or _key(config, "qk_norm_whole") is not True:
        raise NotImplementedError("this reference is the block with its norms on the "
                                  "sublayers' outputs and a whole-width QK-norm")
    stacks = stack_kinds(sizes)
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"][tokens].astype(compute)
        for stack, index in sizes.get("layer_order") or layer_order(sizes):
            p = jax.tree_util.tree_map(lambda a: a[index], params["layers"][stack])
            attn = {name: a.astype(F32 if name in _FLOAT32_LEAVES else compute)
                    for name, a in p["attn"].items()}
            y = (_linear(x, attn, sizes) if stacks[stack] == "linear_attention"
                 else _full(x, attn, sizes, positions))
            x = x + _rms_norm(y, p["ln1"], eps)
            mlp = jax.tree_util.tree_map(lambda a: a.astype(compute), p["mlp"])
            y = (jax.nn.silu(x @ mlp["wg"]) * (x @ mlp["wu"])) @ mlp["wd"]
            x = x + _rms_norm(y, p["ln2"], eps)
        h = _rms_norm(x, params["final_norm"], eps)
        head = params["embed"].T if sizes.get("tie_embeddings") else params["lm_head"]
        return (h @ head.astype(compute)).astype(F32)
