"""Plain reference forward of the Kimi-Linear (`kimi_linear`) model: layers
of gated delta-rule linear attention with a channel-wise decay (KDA) beside
latent-attention (MLA) layers without rotary position, a leading dense layer
and then sparse ones whose sigmoid router picks with a selection bias among
all the experts, of which this chip holds a share beside the shared expert.

Straight `jax.numpy` in float32, `jax.default_matmul_precision("highest")`:
no cache, no chunks, no kernel, the whole sequence at once, a layer at a time
in the model's order, the linear attention as its per-token recurrence
exactly as written below, the latent layer expanded, every held expert
evaluated on every token and masked by the top-k. Nothing is imported from
the program. Weights arrive in the type they are served in and are upcast a
layer at a time, the routed experts an expert at a time.

**Which layer is what.** `linear_attn_config.kda_layers` and
`.full_attn_layers` count layers from 1: model layer l (from 0) is KDA iff
l + 1 is in `kda_layers`. The first `first_k_dense_replace` layers have a
dense SwiGLU, the rest the experts. The depth is the tree's, never the
file's.

**A KDA layer**, for input `x` [T, D], `eps` = `rms_norm_eps`, H =
`linear_attn_config.num_heads` heads whose keys and values are both d =
`linear_attn_config.head_dim` wide, K = `short_conv_kernel_size` taps:

- `h = rms(x; ln1)`; `[q~ | k~ | v~] = h Wqkv`, each [T, H d].
- A depthwise causal convolution over time on each, then silu:
  `u'_t = silu(sum_{j=0..K-1} conv[j] * u~_{t-(K-1)+j})`, rows before position
  0 are zero, no bias (`assumed.conv_bias`).
- `q_t = l2norm(q'_t) d^-0.5`, `k_t = l2norm(k'_t)` a head, `l2norm(x) = x /
  sqrt(sum x^2 + 1e-6)` (`assumed.l2norm_eps`); `v_t = v'_t`.
- The log-decay a channel `g_t = -exp(A_log[head]) softplus((h Wfa) Wfb +
  dt_bias)` [H, d] in float32, `alpha_t = exp(g_t)`; `beta_t = sigmoid(h Wb)`
  [H].
- **The gated delta rule**, a head, S in R^{d x d} float32, S_0 = 0:
  `S' = Diag(alpha_t) S_{t-1}`; `S_t = S' + beta_t k_t (v_t - S'^T k_t)^T`;
  `o_t = S_t^T q_t`. The decay comes before the update.
- `y_t = rms(o_t; on) * sigmoid((h Wga) Wgb)` a head (`on` [d], the sigmoid
  output gate: `assumed.output_gate`); `x <- x + y Wo`.

**A latent layer** (H = `num_attention_heads`, dn = `qk_nope_head_dim`, dr =
`qk_rope_head_dim`, dv = `v_head_dim`, R = `kv_lora_rank`): `h = rms(x; ln1)`;
`q = h Wq` as [H, dn + dr] (`q_lora_rank` null: no query rank); `[ckv | kr] = h
Wkva`, `c = rms(ckv; kvn)`; `[k_nope | v] = c Wkvb` as [H, dn | dv]; **no rotary
position** (`mla_use_nope`: `q_rope` and `kr` are used as projected, `kr` one
head shared by all); scores `(q_nope . k_nope + q_rope . kr) (dn + dr)^-0.5`,
causal softmax, `x <- x + (softmax . v) Wo`.

**The FFN.** `h2 = rms(x; ln2)`. Dense: `x <- x + (silu(h2 Wg) * (h2 Wu)) Wd`.
Sparse: `s = sigmoid(h2 Wr)` over all E = `num_experts_source` experts
(`moe_router_activation_func`); the k = `num_experts_per_token` with the
largest `s + b` (`mlp/bias`, the selection bias: `assumed.topk_method`;
`num_expert_group` = `topk_group` = 1, so no group step); weights `s` at those
k, never `s + b`, divided by their sum (`moe_renormalize`) times
`routed_scaling_factor`; `x <- x + sum_{e in top-k and held} w_e E_e(h2) +
S(h2)`. Held are experts `expert_rank * num_experts ... + num_experts - 1`
(`num_experts` of the file is how many this chip holds); what the absent
ones would add is left out, here and in the program alike. Final `rms`, head
over the held vocabulary slice.

**Departures from the published description**, each because `config.json` does
not settle it and each under the file's `assumed`: the ranks of `Wfa` / `Wga`
(the head's 128), `A_log` a head and `dt_bias` a channel, no bias on the
convolutions, the sigmoid output gate, the l2norm's epsilon inside the root,
the selection bias, the state in float32. `compute` other than float32 rounds
the stream and every matmul's result to it; the convolution, the norms, the
gates and the recurrence stay in float32, as the configuration states.

The latent scores of a long sequence are computed a block of `QUERY_BLOCK`
queries at a time against every key, and the recurrence is a `lax.scan` a
token, so eight thousand tokens need neither [H, T, T] nor T states at once.

`sizes` is `manifest.reference_sizes`: this module reads `rms_norm_eps`,
`num_experts_per_tok` and, under `"config"`, the file's own keys (never the
depth of the tree it is handed, and its order `sizes["layer_order"]` where
`harness/correct.py` has cut it, else the file's, `layer_order`). The
parameter tree is `omnia_tpu/models/mla.py::_init_kinds`'s: `layers` is a list
of stacks, one for each kind of layer the file's model has, in the order
(dense, kda), (dense, mla), (sparse, kda), (sparse, mla), each {ln1, ln2,
attn/{wqkv, conv [K, 3 H d], wfa, wfb, dt_bias, a_log, wb, wga, wgb, on, wo} or
attn/{wq, wkva, kvn, wkvb, wo}, mlp/{wg, wu, wd} or mlp/{router [D, E], bias
[E], wg, wu [held, D, F], wd [held, F, D], shared/{wg, wu, wd}}} led by its own
layer axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
_NEG = -1e30
_KINDS = (("dense", "kda"), ("dense", "mla"), ("sparse", "kda"), ("sparse", "mla"))


def _key(config: dict, key: str):
    """A key of the file, or of its `assumed` where the source lacks it."""
    return config[key] if key in config else config["assumed"][key]


def _file_kinds(config: dict) -> list:
    """(FFN kind, attention kind) of model layer 0, 1, ... of the file's
    depth, from the 1-indexed lists."""
    linear = config["linear_attn_config"]
    kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
    kinds = []
    for l in range(config["num_hidden_layers"]):
        if (l + 1 in kda) == (l + 1 in full):
            raise ValueError(f"layer {l + 1} is in both or neither of kda_layers, full_attn_layers")
        kinds.append(("dense" if l < config["first_k_dense_replace"] else "sparse",
                      "kda" if l + 1 in kda else "mla"))
    return kinds


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


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _l2norm(x, eps):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _kda(h, p, sizes: dict):
    """h [T, D] (the compute type) -> [T, D]. `p` holds the compute type's
    matrices; `conv`, `dt_bias`, `a_log` and `on` are read in float32."""
    config = sizes["config"]
    linear = config["linear_attn_config"]
    H, d, K = linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]
    T = h.shape[0]
    pre = (h @ p["wqkv"]).astype(F32)                                  # [T, 3 H d]
    rows = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1]), F32), pre], axis=0)
    conv = p["conv"].astype(F32)
    u = jax.nn.silu(sum(conv[j] * rows[j:j + T] for j in range(K)))
    q, k, v = (t.reshape(T, H, d) for t in jnp.split(u, 3, axis=-1))
    eps = float(_key(config, "l2norm_eps"))
    q, k = _l2norm(q, eps) * d ** -0.5, _l2norm(k, eps)
    decay = ((h @ p["wfa"]) @ p["wfb"]).astype(F32) + p["dt_bias"].astype(F32)
    g = -jnp.exp(p["a_log"].astype(F32))[:, None] * jax.nn.softplus(decay.reshape(T, H, d))
    beta = jax.nn.sigmoid((h @ p["wb"]).astype(F32))                   # [T, H]
    gate = jax.nn.sigmoid(((h @ p["wga"]) @ p["wgb"]).astype(F32)).reshape(T, H, d)

    def token(S, x):  # S [H, d(k), d(v)]
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, :, None] * S
        r = jnp.einsum("hkv,hk->hv", S, k)
        S = S + k[:, :, None] * (beta[:, None] * (v - r))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), F32), (q, k, v, g, beta))
    y = _rms_norm(o, p["on"], sizes["rms_norm_eps"]) * gate
    return y.reshape(T, H * d).astype(h.dtype) @ p["wo"]


def _mla(h, p, sizes: dict, positions):
    """h [T, D] -> [T, D]: latent attention expanded, no rotary position."""
    config = sizes["config"]
    if not config.get("mla_use_nope") or config.get("q_lora_rank"):
        raise NotImplementedError("a latent layer with rotary position or a query rank "
                                  "is not written here")
    T, H = h.shape[0], config["num_attention_heads"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    R = config["kv_lora_rank"]
    q = (h @ p["wq"]).reshape(T, H, dn + dr)
    kva = h @ p["wkva"]
    c = _rms_norm(kva[:, :R], p["kvn"], sizes["rms_norm_eps"])
    kr = kva[:, R:]                                                    # [T, dr], one head
    kv = (c @ p["wkvb"]).reshape(T, H, dn + dv)
    out = []
    for lo in range(0, T, QUERY_BLOCK):  # a block of queries against every key
        qb = q[lo:lo + QUERY_BLOCK]
        scores = jnp.einsum("thd,shd->hts", qb[..., :dn], kv[..., :dn],
                            preferred_element_type=F32)
        scores += jnp.einsum("thd,sd->hts", qb[..., dn:], kr, preferred_element_type=F32)
        scores *= (dn + dr) ** -0.5
        seen = positions[None, :] <= positions[lo:lo + QUERY_BLOCK, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, _NEG), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", probs.astype(kv.dtype), kv[..., dn:]))
    return jnp.concatenate(out, axis=0).reshape(T, H * dv) @ p["wo"]


def _experts(h, p, sizes, compute):
    """The held routed experts, each evaluated on every token and weighted
    by the top-k mask, and the shared expert once; and the router's own
    account of each decision: the k-th minus the (k+1)-th of what it selects
    by, and the standard deviation of that over the layer."""
    config = sizes["config"]
    k = sizes["num_experts_per_tok"]
    logits = jnp.dot(h, p["router"].astype(compute), preferred_element_type=F32)  # [T, E]
    scores = (jax.nn.sigmoid(logits) if config["moe_router_activation_func"] == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    select = scores + p["bias"].astype(F32) if "bias" in p else scores
    ranked, top_i = jax.lax.top_k(select, k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    top_i = top_i[:, :k]
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if config.get("moe_renormalize", True):
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


_FLOAT32_LEAVES = ("conv", "dt_bias", "a_log", "on")


def _forward(params, sizes: dict, tokens, compute):
    config, eps = sizes["config"], sizes["rms_norm_eps"]
    if (config.get("num_expert_group", 1), config.get("topk_group", 1)) != (1, 1):
        raise NotImplementedError("grouped top-k (num_expert_group, topk_group > 1) is not "
                                  "written here")
    stacks = stack_kinds(sizes)
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        positions = jnp.arange(T, dtype=jnp.int32)
        x = params["embed"][tokens].astype(compute)
        margins, sigmas, residual = [], [], []
        for stack, index in sizes.get("layer_order") or layer_order(sizes):
            ffn, attention = stacks[stack]
            p = jax.tree_util.tree_map(lambda a: a[index], params["layers"][stack])
            attn = {name: a.astype(F32 if name in _FLOAT32_LEAVES else compute)
                    for name, a in p["attn"].items()}
            residual.append(x)
            h = _rms_norm(x, p["ln1"], eps)
            x = x + (_kda(h, attn, sizes) if attention == "kda"
                     else _mla(h, attn, sizes, positions))
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
