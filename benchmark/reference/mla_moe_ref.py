"""Plain reference forward of the Mistral-Small-4 (`mistral4`) block: latent
attention (MLA) and a routed expert layer beside a shared expert, of which
this chip holds a share.

Straight ``jax.numpy`` in float32, ``jax.default_matmul_precision("highest")``:
no cache, no kernel, expanded attention only, every expert of the held
share evaluated on every token and masked by the top-k. Nothing is imported
from the program. Weights arrive in the type they are served in and are
upcast a layer at a time, the routed experts an expert at a time, so that
one layer never stands whole in float32.

**The layer**, for input ``x`` [T, D], ``eps`` = ``rms_norm_eps``, H heads,
``dn`` = ``qk_nope_head_dim``, ``dr`` = ``qk_rope_head_dim``, ``dv`` =
``v_head_dim``, R = ``kv_lora_rank``:

- ``h = rms(x; ln1)``; ``cq = rms(h·Wqa; qn)`` [``q_lora_rank``]; ``q = cq·Wqb`` →
  [H, dn + dr], split ``q_nope`` [dn] | ``q_rope`` [dr].
- ``[ckv | kr] = h·Wkva`` → [R | dr]; ``c = rms(ckv; kvn)``; ``k_rope = rope(kr)``,
  one head shared by all H; ``q_rope = rope(q_rope)``. Rotary pairs are
  interleaved (``rope_interleave``: dims 2i, 2i+1), frequencies by YaRN from
  ``rope_parameters`` (``rope_theta``, ``factor``,
  ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``).
- ``[k_nope | v] = c·Wkvb`` → [H, dn | dv]. ``s = (q_nope·k_nope + q_rope·k_rope)·σ``,
  causal softmax, ``o = Σ p·v`` → [H, dv]; ``x ← x + o·Wo``.
- ``h2 = rms(x; ln2)``; ``r = h2·Wr`` [E] in float32; ``p = softmax(r)``; the
  ``num_experts_per_tok`` largest, renormalised to sum 1 (``norm_topk_prob``),
  × ``routed_scaling_factor``.
  ``x ← x + Σ_{e ∈ top-k ∩ held} w_e·ffn_e(h2) + ffn_shared(h2)``,
  ``ffn(h) = (silu(h·Wg) ⊙ h·Wu)·Wd``. ``held`` = experts ``held·rank …
  held·rank + held − 1`` (``n_routed_experts`` of the file is how many are
  held, ``expert_rank`` which share; the router's width E is the published
  count). What the absent experts would add is left out, here and in the
  program alike, and that partial stream goes on to the next layer.
- Final ``rms``, head over the held vocabulary slice.

Three readings that the source's ``config.json`` does not settle (the
configuration file lists them under ``assumed``):

- the router scores by softmax over all E (the family's convention, as
  Mixtral; the config has no ``scoring_func``);
- ``σ = (dn + dr)^-0.5 · m²`` with ``m = 0.1·mscale_all_dim·ln(factor) + 1``,
  and cos/sin scaled by ``m(mscale) / m(mscale_all_dim)`` (the convention of
  the DeepSeek-V3 code that ``rope_type`` yarn with ``mscale_all_dim`` comes
  from);
- ``llama_4_scaling_beta`` β as ``q ← q·(1 + β·ln(1 + ⌊pos / original context⌋))``.

``sizes`` is ``manifest.reference_sizes``: this module reads ``num_heads``,
``rms_norm_eps``, ``num_experts_per_tok`` and, under ``"config"``, the
configuration file's own keys. Its depth is the tree's. The parameter
tree is the one ``omnia_tpu/models/mla.py::init_params`` documents: embed
[V, D], layers/{ln1, ln2, attn/{wqa, qn, wqb, wkva, kvn, wkvb, wo},
mlp/{router [D, E], wg, wu [held, D, F], wd [held, F, D], shared/{wg, wu,
wd}}} stacked on a leading layer axis, final_norm, lm_head [D, V].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(F32)).astype(x.dtype)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn_inv_freq(dim: int, rp: dict):
    """[dim // 2] float32. A pair that turns more than ``beta_fast`` times
    over the original context keeps its frequency, one that turns fewer
    than ``beta_slow`` times is divided by ``factor``, a linear ramp over
    the pair's index between."""
    base, factor = float(rp["rope_theta"]), float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def pair(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair(rp["beta_fast"])), 0)
    high = min(math.ceil(pair(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def _rope(x, positions, config: dict):
    """x [T, H, dr]; pairs (2i, 2i+1) when ``rope_interleave``, else the
    two halves."""
    rp = config["rope_parameters"]
    d = x.shape[-1]
    ratio = (_mscale(rp["factor"], rp.get("mscale", 1))
             / _mscale(rp["factor"], rp.get("mscale_all_dim", 0) or 1))
    ang = positions.astype(F32)[:, None] * _yarn_inv_freq(d, rp)[None, :]
    cos, sin = (jnp.cos(ang) * ratio)[:, None, :], (jnp.sin(ang) * ratio)[:, None, :]
    xf = x.astype(F32)
    if config.get("rope_interleave"):
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)
    else:
        x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _attention(h, p, sizes, positions):
    config = sizes["config"]
    rp = config["rope_parameters"]
    T, H = h.shape[0], sizes["num_heads"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    R, eps = config["kv_lora_rank"], sizes["rms_norm_eps"]
    q = (_rms_norm(h @ p["wqa"], p["qn"], eps) @ p["wqb"]).reshape(T, H, dn + dr)
    beta = rp.get("llama_4_scaling_beta", 0)
    if beta:
        grown = 1.0 + beta * jnp.log1p(
            (positions // int(rp["original_max_position_embeddings"])).astype(F32))
        q = (q * grown[:, None, None]).astype(q.dtype)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], positions, config)
    kva = h @ p["wkva"]
    c = _rms_norm(kva[:, :R], p["kvn"], eps)
    k_rope = _rope(kva[:, None, R:], positions, config)[:, 0]        # [T, dr]
    kv = (c @ p["wkvb"]).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    sigma = (dn + dr) ** -0.5
    if rp.get("mscale_all_dim", 0):
        sigma *= _mscale(rp["factor"], rp["mscale_all_dim"]) ** 2
    scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope, preferred_element_type=F32)
              + jnp.einsum("thd,sd->hts", q_rope, k_rope, preferred_element_type=F32)) * sigma
    causal = positions[None, :] <= positions[:, None]  # [T(query), S(key)]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
    return jnp.einsum("hts,shd->thd", probs, v).reshape(T, H * dv) @ p["wo"]


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _experts(h, p, sizes, compute):
    """The held share of the routed experts, each evaluated on every token
    and weighted by the top-k mask, and the shared expert once. Also the
    router's own account of each decision (``llama_ref._experts``)."""
    config = sizes["config"]
    k = sizes["num_experts_per_tok"]
    logits = jnp.dot(h, p["router"].astype(compute), preferred_element_type=F32)  # [T, E]
    ranked = jax.lax.top_k(logits, k + 1)[0]
    margin = ranked[:, k - 1] - ranked[:, k]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    if config.get("norm_topk_prob", True):
        top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    top_w = top_w * config.get("routed_scaling_factor", 1)
    E = probs.shape[-1]
    combine = jnp.sum(jax.nn.one_hot(top_i, E, dtype=F32) * top_w[..., None], axis=-2)  # [T, E]
    held = p["wg"].shape[0]
    first = int(config.get("expert_rank", 0)) * held
    mine = jax.lax.dynamic_slice_in_dim(combine, first, held, axis=1).astype(h.dtype)  # [T, held]

    def one(acc, expert):  # an expert at a time: never the layer whole in float32
        wg, wu, wd, w = expert
        y = _swiglu(h, wg.astype(compute), wu.astype(compute), wd.astype(compute))
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (p["wg"], p["wu"], p["wd"], mine.T))
    if "shared" in p:
        s = jax.tree_util.tree_map(lambda a: a.astype(compute), p["shared"])
        out = out + _swiglu(h, s["wg"], s["wu"], s["wd"])
    return out, (margin, jnp.std(logits))


def forward(params, sizes: dict, tokens, compute=F32):
    """tokens int32 [T] -> logits float32 [T, V], whole sequence at once."""
    return _forward(params, sizes, tokens, compute)[0]


def forward_routed(params, sizes: dict, tokens):
    """(logits [T, V], margin [L, T], sigma [L], residual [L + 1, T, D]),
    as ``llama_ref.forward_routed``: the k-th minus the (k+1)-th router
    logit at every layer and position, the standard deviation of each
    layer's router logits, and the stream that enters each layer (and
    leaves the last), all from this float32 evaluation."""
    logits, (margin, sigma, entered), left = _forward(params, sizes, tokens, F32)
    return logits, margin, sigma, jnp.concatenate([entered, left[None]], axis=0)


def _forward(params, sizes: dict, tokens, compute):
    eps = sizes["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"][tokens].astype(compute)

        def layer(x, p):
            x_in = x
            attn = jax.tree_util.tree_map(lambda a: a.astype(compute), p["attn"])
            h = _rms_norm(x, p["ln1"], eps)
            x = x + _attention(h, attn, sizes, positions)
            h = _rms_norm(x, p["ln2"], eps)
            y, routed = _experts(h, p["mlp"], sizes, compute)
            return x + y, (*routed, x_in)

        x, routed = jax.lax.scan(layer, x, params["layers"])
        h = _rms_norm(x, params["final_norm"], eps)
        return (h @ params["lm_head"].astype(compute)).astype(F32), routed, x
