"""Latent-attention (MLA) transformer with a dropless expert layer that
holds a share of the routed experts beside a shared expert, functional JAX;
optionally with leading dense layers (two stacks), a residual stream of
several copies mixed by hyper-connections, and a sigmoid router with a
selection bias.

The block, for a layer with input ``x`` [T, D] (benchmark/reference/
mla_moe_ref.py is the same mathematics in plain float32):

- ``h = rms(x; ln1)``; ``cq = rms(h·Wqa; qn)``; ``q = cq·Wqb`` → [H, dn + dr],
  split ``q_nope | q_rope``.
- ``[ckv | kr] = h·Wkva``; ``c = rms(ckv; kvn)`` [R]; ``k_rope = rope(kr)``, one
  head shared by all H; ``q_rope = rope(q_rope)``. Rotary pairs are (2i, 2i+1)
  when ``rope_interleave``, frequencies YaRN's (ops/rope.py).
- ``[k_nope | v] = c·Wkvb`` → [H, dn | dv]; ``s = (q_nope·k_nope + q_rope·k_rope)·σ``,
  causal softmax, ``o = Σ p·v``; ``x ← x + o·Wo``.
- ``h2 = rms(x; ln2)``; router logits in float32, softmax over all E, the k
  largest renormalised; ``x ← x + Σ_{e ∈ top-k ∩ held} w_e·ffn_e(h2) + ffn_shared(h2)``.

Three things a model of this family may have besides, each off by default
and then tracing none of its code (benchmark/reference/xing4_ref.py is their
mathematics in plain float32):

- **Stacks of unlike layers** (``cfg.num_dense_layers`` > 0): the leading
  layers have a dense SwiGLU of ``ffn_hidden_size`` where the rest have the
  router and the experts. ``params["layers"]`` is then the sequence ``[dense,
  sparse]``, a tree each with its own leading layer axis, and ``layer_order`` /
  ``with_layer_order`` state and cut the order as benchmark/README.md ("`layers`:
  one tree, or stacks") sets out. A forward pass is two scans in that order
  under the scopes ``stack.dense`` and ``stack.sparse`` (one tree: one scan under
  ``layers``), the cache carried through both; a sparse layer's cache index is
  its index in the stack plus the dense count, its experts' index the stack's
  own. A stack that a cut model is left with none of is skipped.
- **A stream of n copies** (``cfg.residual_copies`` = n > 1): a token's residual
  is ``X`` [n, D], carried as ``[B, T, n·D]``. An embedding row is copied n times
  (a table already n·D wide is the stream itself), each sublayer f sits
  between ``hc.pre`` and ``hc.post`` (ops/hyper_connections.py: ``u = Σ h_pre[i]·X[i]``,
  ``X'[i] = Σ_j H_res[i, j]·X[j] + h_post[i]·f(rms(u))`` with ``H_res`` made doubly
  stochastic by Sinkhorn) instead of ``x + f(rms(x))``, and after the last layer
  the copies are summed before the final norm. Scopes ``hc.mix``, ``hc.maps``,
  ``hc.sinkhorn``.
- **A router that scores by sigmoid and picks with a bias**
  (``cfg.router_scoring``, ``cfg.router_topk_method`` "noaux_tc"): ``s = σ(h2·Wr)``,
  the k experts with the largest ``s + b_sel`` (``mlp/bias`` [E], float32), weights
  ``s`` at those k renormalised, times ``routed_scaling_factor``
  (ops/moe.py::top_k_weights).

**The cache is one array** ``[L, B, S, W]``: a token's row is ``[c | k_rope |
0]``, W the next multiple of 128 (ops/decode_mla_attention.py says why).
``forward`` takes and returns it as a tuple of one, the contract of
benchmark/README.md ("The model module"). It rides the layer scan whole, as
the carry, and a layer writes its B×T rows in place, as models/llama.py.

**Two attention paths, one mathematics.** T > 1 (prefill) expands the rows:
``c·Wkvb`` gives every head its keys and values and the einsums are the
ordinary ones. T == 1 (decode) absorbs ``Wkvb`` into the query and the
output instead (``q̃[h] = q_nope[h]·Wkvb_k[h]ᵀ``, ``o[h] = (Σ p·c)·Wkvb_v[h]``),
so a step reads each cached row once, for scores and values alike: the
Pallas kernel on a TPU, the same two einsums elsewhere.

**Scopes.** Every op sits in a named scope (metadata only). The outer
ones are those models/llama.py has, so the trace reduction that knows them
(benchmark/harness/spans.py) reads this family too: ``attn.qkv`` (inside it
``attn.q_lora`` and ``attn.kv_latent``), ``kv.update``, ``attn.decode`` (inside
it ``attn.absorb`` around the two absorbing einsums; the kernel is
``decode_mla_attention``) or ``attn.prefill``, ``attn.out``, ``mlp`` (inside it
``moe.route``, ``moe.sort``, ``moe.experts``, ``moe.combine``, ``moe.shared``).

**The share.** ``cfg.num_experts`` is the router's width; this chip holds
``cfg.experts_held`` of them, experts ``expert_rank·held …``, and computes
their part of the result. What the absent experts would add is left out,
and that partial stream goes on to the next layer: on one chip the layer
runs without its exchange (the `model-configs` guide, section 4). Not
ported to this family, and refused by name at engine construction:
kv_quant, kv_pages, sessions and the prefix pool, spec_decode, the mixed
step, sp, tp/dp > 1.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from omnia_tpu.models.config import ModelConfig
from omnia_tpu.ops import hyper_connections as hc
from omnia_tpu.ops.attention import _kernel_on, _pallas_decode_mode
from omnia_tpu.ops.decode_mla_attention import block_rows, decode_mla_attention
from omnia_tpu.ops.moe import EXPERT_COUNTERS
from omnia_tpu.ops.moe import expert_ffn as _experts
from omnia_tpu.ops.moe import unstack_experts as _unstack_experts
from omnia_tpu.ops.norms import rms_norm
from omnia_tpu.ops.rope import (
    apply_rope,
    apply_rope_interleaved,
    rope_cos_sin,
    yarn_cos_sin,
    yarn_softmax_scale,
)

_NEG_INF = -1e30

#: Counters a decode step sums on the device over its layers, in the
#: order ``forward(..., counters=True)`` returns them (engine.metrics keys).
DECODE_COUNTERS = EXPERT_COUNTERS


#: Cache rows in one block of this family's decode kernel, by cache length:
#: what the engine's ``decode_kv_blocks`` counts in (scheduler._live_kv_blocks).
decode_block_rows = block_rows


def row_width(cfg: ModelConfig) -> int:
    """Lanes of one cached row: [c | k_rope] padded to the 128-lane tile."""
    return -(-(cfg.kv_rank + cfg.qk_rope_head_dim) // 128) * 128


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def layer_order(cfg: ModelConfig) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ... of a model whose
    ``params["layers"]`` is two stacks: the leading dense layers are stack 0,
    the sparse ones behind them stack 1 (benchmark/README.md, "`layers`: one
    tree, or stacks")."""
    dense = cfg.num_dense_layers
    return (tuple((0, i) for i in range(dense))
            + tuple((1, i) for i in range(cfg.num_layers - dense)))


def with_layer_order(cfg: ModelConfig, order) -> ModelConfig:
    """The same model with the layers ``order`` names: its own order over
    the cut stacks, either of which may be left with none."""
    dense = sum(stack == 0 for stack, _ in order)
    cut = dataclasses.replace(cfg, num_layers=len(order), num_dense_layers=dense)
    if tuple(map(tuple, order)) != layer_order(cut):
        raise ValueError(f"{order}: this family runs its dense layers first, then its sparse")
    return cut


def _hc_constants(cfg: ModelConfig) -> dict:
    return {"iters": cfg.hc_sinkhorn_iters, "eps": cfg.hc_eps,
            "clamp": (cfg.hc_res_clamp_min, cfg.hc_res_clamp_max),
            "norm_eps": cfg.rms_norm_eps}


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16):
    """Random-initialized parameter pytree (layers stacked on axis 0); of
    the routed experts only the held share exists. ``layers`` is one tree
    of sparse layers, or with leading dense layers the two stacks ``[dense,
    sparse]`` (``layer_order``). A model with a selection bias has it as
    ``mlp/bias`` [E] float32 beside the router; one with several residual
    copies has ``hc/{attn, mlp}/{phi, bias, alpha}`` in every layer
    (ops/hyper_connections.py): Φ ~ N(0, 1/(n·D)), so that x̄·Φ has unit
    variance at any width, α_pre = α_post = 0.5, α_res = 0.3, b_pre, b_post
    ~ N(0, 0.5), B_res = 1.5·I + N(0, 0.3): H_res leans to the identity
    (diagonal about 0.6 of 4 copies) without being it, and 20 Sinkhorn
    iterations bring its columns within 1e-4 of 1 (a sharper diagonal does
    not converge that far)."""
    L, D, V, H = cfg.num_layers, cfg.hidden_size, cfg.vocab_size, cfg.num_heads
    R, Rq = cfg.kv_rank, cfg.q_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Eh, n = cfg.moe_ffn_hidden_size, cfg.experts_held, cfg.residual_copies
    Fs = cfg.num_shared_experts * F
    dense, sparse = cfg.num_dense_layers, cfg.num_layers - cfg.num_dense_layers
    keys = iter(jax.random.split(key, 16))
    # What a model of one tree, one copy and no bias has no use for draws
    # from keys of its own, so that model's weights are what they were.
    more = iter(jax.random.split(jax.random.fold_in(key, 1), 24))
    out_std = 0.02 / (2 * L) ** 0.5

    def normal(shape, std=0.02, keys=keys, dtype=dtype, mean=0.0):
        return (mean + jax.random.normal(next(keys), shape, dtype=jnp.float32) * std).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype=dtype)

    def swiglu(lead, width, keys):
        return {
            "wg": normal((*lead, D, width), keys=keys),
            "wu": normal((*lead, D, width), keys=keys),
            "wd": normal((*lead, width, D), std=out_std, keys=keys),
        }

    def attention(c, keys):
        return {
            "wqa": normal((c, D, Rq), keys=keys),
            "qn": ones(c, Rq),
            "wqb": normal((c, Rq, H * (dn + dr)), keys=keys),
            "wkva": normal((c, D, R + dr), keys=keys),
            "kvn": ones(c, R),
            "wkvb": normal((c, R, H * (dn + dv)), keys=keys),
            "wo": normal((c, H * dv, D), std=out_std, keys=keys),
        }

    def hyper(c):
        if n == 1:
            return {}

        def maps():
            f32 = {"keys": more, "dtype": jnp.float32}
            b_res = 1.5 * jnp.eye(n).reshape(n * n) + normal((c, n * n), 0.3, **f32)
            return {"phi": normal((c, n * D, 2 * n + n * n), (n * D) ** -0.5, keys=more),
                    "bias": jnp.concatenate([normal((c, 2 * n), 0.5, **f32), b_res], axis=-1),
                    "alpha": jnp.tile(jnp.asarray([0.5, 0.5, 0.3], jnp.float32), (c, 1))}

        return {"hc": {"attn": maps(), "mlp": maps()}}

    mlp = {"router": normal((sparse, D, cfg.num_experts)), **swiglu((sparse, Eh), F, keys)}
    if Fs:
        mlp["shared"] = swiglu((sparse,), Fs, keys)
    embed = normal((V, D))
    stack = {"ln1": ones(sparse, D), "ln2": ones(sparse, D), "attn": attention(sparse, keys),
             "mlp": mlp}
    lm_head = normal((D, V))
    if cfg.router_bias:
        mlp["bias"] = normal((sparse, cfg.num_experts), 0.05, keys=more, dtype=jnp.float32)
    stack.update(hyper(sparse))
    if dense:
        stack = [{"ln1": ones(dense, D), "ln2": ones(dense, D), "attn": attention(dense, more),
                  "mlp": swiglu((dense,), cfg.ffn_hidden_size, more), **hyper(dense)}, stack]
    return {"embed": embed, "layers": stack, "final_norm": ones(D), "lm_head": lm_head}


def param_specs(cfg: ModelConfig):
    """Everything replicated: this family runs on one chip a replica (the
    engine refuses tp/dp/sp > 1 for it)."""
    return jax.tree_util.tree_map(
        lambda _: P(), jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    )


def init_kv_cache(cfg: ModelConfig, batch: int, seq: int, dtype=jnp.bfloat16,
                  kv_quant=None):
    """The zeroed latent cache, a tuple of one array [L, B, S, W]."""
    if kv_quant:
        raise NotImplementedError("kv_quant is not ported to the latent cache")
    return (jnp.zeros((cfg.num_layers, batch, seq, row_width(cfg)), dtype=dtype),)


def kv_cache_specs(kv_quant=None) -> tuple:
    return (P(),)


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------


def _rotary(cfg: ModelConfig, q_positions):
    if cfg.rope_yarn is None:
        return rope_cos_sin(q_positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    return yarn_cos_sin(q_positions, cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_yarn)


def _rope(cfg: ModelConfig, x, cos, sin):
    return (apply_rope_interleaved if cfg.rope_interleave else apply_rope)(x, cos, sin)


def _queries_and_row(h, p, cfg: ModelConfig, cos, sin, q_scale):
    """h [B, T, D] → (q_nope [B, T, H, dn], q_rope [B, T, H, dr], row
    [B, T, W]): the queries of every head and the row the cache keeps."""
    B, T, _ = h.shape
    dn, dr, R = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_rank
    with jax.named_scope("attn.q_lora"):
        cq = rms_norm(jnp.dot(h, p["wqa"]), p["qn"], cfg.rms_norm_eps)
        q = jnp.dot(cq, p["wqb"]).reshape(B, T, cfg.num_heads, dn + dr)
        if q_scale is not None:
            q = (q * q_scale[:, :, None, None]).astype(q.dtype)
        q_nope, q_rope = q[..., :dn], _rope(cfg, q[..., dn:], cos, sin)
    with jax.named_scope("attn.kv_latent"):
        kva = jnp.dot(h, p["wkva"])
        c = rms_norm(kva[..., :R], p["kvn"], cfg.rms_norm_eps)
        k_rope = _rope(cfg, kva[..., None, R:], cos, sin)[..., 0, :]
        pad = jnp.zeros((B, T, row_width(cfg) - R - dr), c.dtype)
        row = jnp.concatenate([c, k_rope, pad], axis=-1)
    return q_nope, q_rope, row


def _write_rows(cache, row, start, layer):
    """Whole cache [L, B, S, W] ← row [B, T, W] at ``[layer, b, start[b] :
    start[b] + T]``, in place: one update a slot (models/llama.py::_write_kv)."""
    for b in range(row.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, row[b][None, None].astype(cache.dtype), (layer, b, start[b], 0)
        )
    return cache


def _expanded_attention(q_nope, q_rope, rows, wkvb, cfg: ModelConfig, q_positions):
    """Prefill (T > 1): every head's keys and values from the rows. rows
    [B, S, W] lie at positions 0 … S-1. → [B, T, H·dv]."""
    B, T, H, dn = q_nope.shape
    R, dr, dv = cfg.kv_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    S = rows.shape[1]
    kv = jnp.dot(rows[..., :R], wkvb).reshape(B, S, H, dn + dv)
    scores = jnp.einsum("bthd,bshd->bhts", q_nope, kv[..., :dn],
                        preferred_element_type=jnp.float32)
    scores += jnp.einsum("bthd,bsd->bhts", q_rope, rows[..., R:R + dr],
                         preferred_element_type=jnp.float32)
    scores *= yarn_softmax_scale(dn + dr, cfg.rope_yarn)
    mask = jnp.arange(S, dtype=jnp.int32)[None, None, :] <= q_positions[:, :, None]
    scores = jnp.where(mask[:, None], scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = (probs / probs.sum(axis=-1, keepdims=True)).astype(rows.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, kv[..., dn:]).reshape(B, T, H * dv)


def _absorbed_attention(q_nope, q_rope, cache, wkvb, cfg: ModelConfig, q_positions,
                        layer, live):
    """Decode (T == 1) over layer ``layer`` of the whole cache [L, B, S, W]:
    Wkvb goes into the query and the output, the rows are read as they lie.
    → [B, 1, H·dv]."""
    B, _, H, dn = q_nope.shape
    R, dr, dv = cfg.kv_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    S, W = cache.shape[2:]
    scale = yarn_softmax_scale(dn + dr, cfg.rope_yarn)
    wkvb = wkvb.reshape(R, H, dn + dv)
    with jax.named_scope("attn.absorb"):
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wkvb[..., :dn])
        q_cat = jnp.concatenate(
            [q_lat, q_rope[:, 0], jnp.zeros((B, H, W - R - dr), q_lat.dtype)], axis=-1)
    if _kernel_on():
        o_lat = decode_mla_attention(
            q_cat, cache, q_positions[:, 0], jnp.asarray(layer, jnp.int32), live,
            rank=R, scale=scale, interpret=_pallas_decode_mode() == "interpret",
        )
    else:
        rows = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
        scores = jnp.einsum("bhw,bsw->bhs", q_cat, rows,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(S, dtype=jnp.int32)[None, :] <= q_positions  # [B, S]
        scores = jnp.where(mask[:, None], scores, _NEG_INF)
        probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        probs = (probs / probs.sum(axis=-1, keepdims=True)).astype(rows.dtype)
        o_lat = jnp.einsum("bhs,bsr->bhr", probs, rows[..., :R])
    with jax.named_scope("attn.absorb"):
        out = jnp.einsum("bhr,rhd->bhd", o_lat, wkvb[..., dn:])
    return out.reshape(B, 1, H * dv)


def _layer(x, p, experts, at, cfg: ModelConfig, cos, sin, q_scale, q_positions,
           cache, write_start, live=None, first=0):
    """One block, ``at`` its index in its stack and ``first + at`` in the
    model and the cache (one tree: the same). With a cache, ``cache`` is
    the WHOLE [L, B, S, W]: the new rows are written in place and attention
    reads that layer where it lies. Without one (fresh prefill) attention
    runs over the chunk's own rows, which are returned. ``experts`` are the
    routed experts' stacks over the stack's layers (``_unstack_experts``),
    None for a dense layer. With several residual copies x is the stream
    [B, T, n·D], and each sublayer sits between ``hc.pre`` and ``hc.post``
    instead of ``x + f(x)``."""
    B, T, _ = x.shape
    n = cfg.residual_copies
    layer = first + at if first else at
    u, mixes = hc.pre(x, p["hc"]["attn"], n, **_hc_constants(cfg)) if n > 1 else (x, None)
    with jax.named_scope("attn.qkv"):  # attn.q_lora and attn.kv_latent inside
        h = rms_norm(u, p["ln1"], cfg.rms_norm_eps)
        q_nope, q_rope, row = _queries_and_row(h, p["attn"], cfg, cos, sin, q_scale)
    if cache is None:
        rows, kept = row, row
    else:
        with jax.named_scope("kv.update"):
            cache = kept = _write_rows(cache, row, write_start, layer)
    if T == 1 and cache is not None:
        with jax.named_scope("attn.decode"):  # attn.absorb inside
            attn = _absorbed_attention(q_nope, q_rope, cache, p["attn"]["wkvb"], cfg,
                                       q_positions, layer, live)
    else:
        with jax.named_scope("attn.prefill"):
            if cache is not None:
                rows = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
            attn = _expanded_attention(q_nope, q_rope, rows, p["attn"]["wkvb"], cfg,
                                       q_positions)
    with jax.named_scope("attn.out"):
        out = jnp.dot(attn, p["attn"]["wo"])
        x = x + out if mixes is None else hc.post(x, out, mixes)
    u, mixes = hc.pre(x, p["hc"]["mlp"], n, **_hc_constants(cfg)) if n > 1 else (x, None)
    with jax.named_scope("mlp"):  # moe.route/sort/experts/combine/shared inside
        h2 = rms_norm(u, p["ln2"], cfg.rms_norm_eps)
        y, counts = _experts(h2, p["mlp"], experts, at, cfg)
    return (x + y if mixes is None else hc.post(x, y, mixes)), kept, counts


def _embed(params, cfg: ModelConfig, tokens, q_positions):
    """Token embeddings (with several residual copies: the stream they
    start, ``hc.expand``), the rotary tables of their positions and the
    position-dependent query scale (None where the model has none)."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        if cfg.residual_copies > 1:
            x = hc.expand(x, cfg.residual_copies, cfg.hidden_size)
        cos, sin = _rotary(cfg, q_positions)
        q_scale = None
        if cfg.q_scaling_beta:
            # 1 below the original context (rope_yarn's), growing with
            # the logarithm of how many of them the position lies past.
            q_scale = 1.0 + cfg.q_scaling_beta * jnp.log1p(
                (q_positions // int(cfg.rope_yarn[1])).astype(jnp.float32))
    return x, cos, sin, q_scale


def _logits(params, cfg: ModelConfig, x):
    with jax.named_scope("lm_head"):
        if cfg.residual_copies > 1:
            x = hc.fold(x, cfg.residual_copies)
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return jnp.dot(x, params["lm_head"]).astype(jnp.float32)


def _logits_at(params, cfg: ModelConfig, x, row):
    """The head over the rows its caller reads (models/llama.py::_logits_at):
    every row of the stream x [B, T, n·D] (``row`` None → [B, T, V]), or row
    ``row`` alone (→ [B, V]), taken BEFORE the fold, the norm and the head."""
    if row is None:
        return _logits(params, cfg, x)
    with jax.named_scope("lm_head"):
        x = jax.lax.dynamic_slice_in_dim(x, row, 1, axis=1)
    return _logits(params, cfg, x)[:, 0]


def _scans(params, cfg: ModelConfig):
    """The layer scans a forward pass makes, in the model's order: (scope,
    what the scan runs over: the layers' parameters, sliced a layer at a time,
    and their indices in the stack; the routed experts' stacks or None; the
    stack's first layer in the model). One tree is one scan under ``layers``;
    two stacks are ``stack.dense`` and ``stack.sparse``, and a stack a cut
    model is left with none of is skipped."""
    def scan(scope, scanned, experts, first):
        count = scanned["ln1"].shape[0]
        return scope, (scanned, jnp.arange(count, dtype=jnp.int32)), experts, first

    layers = params["layers"]
    if not isinstance(layers, (list, tuple)):
        return [scan("layers", *_unstack_experts(layers), 0)]
    dense, sparse = layers
    scans = [scan("stack.dense", dense, None, 0)] if cfg.num_dense_layers else []
    if cfg.num_layers > cfg.num_dense_layers:
        scans.append(scan("stack.sparse", *_unstack_experts(sparse), cfg.num_dense_layers))
    return scans


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def forward_prefill(params, cfg: ModelConfig, tokens, q_positions, row=None):
    """Fresh-sequence prefill: attention over the chunk's own rows, which
    come back for the engine to place into a cache slot.

    tokens, q_positions: int32 [B, T]. Returns (logits [B, T, V] f32,
    chunk [L, B, T, W]); with ``row`` (int32 scalar) the logits are that
    row's alone, [B, V]: the head runs over one row (``_logits_at``)."""
    x, cos, sin, q_scale = _embed(params, cfg, tokens, q_positions)
    chunks = []
    for scope, layers, experts, first in _scans(params, cfg):

        def body(x, scanned, experts=experts, first=first):
            p, at = scanned
            x, row, _ = _layer(x, p, experts, at, cfg, cos, sin, q_scale, q_positions,
                               None, None, first=first)
            return x, row

        with jax.named_scope(scope):
            x, chunk = jax.lax.scan(body, x, layers)
        chunks.append(chunk)
    chunk = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=0)
    return _logits_at(params, cfg, x, row), chunk


def forward(params, cfg: ModelConfig, tokens, q_positions, cache, write_start,
            mesh=None, live=None, counters=False, row=None):
    """Serving forward (prefill or decode: same code, different T).

    tokens, q_positions: int32 [B, T]; cache: [L, B, S, W]; write_start:
    int32 [B], the row where this chunk's rows land. ``live``: bool [B]
    or None, the slots whose logits the caller will use; the decode
    kernel skips the others. ``row``: int32 scalar or None, the one row of
    the T whose logits the caller will use; the logits are then [B, V].
    Returns (logits [B, T, V] f32, cache), and with ``counters`` a third:
    int32 [len(DECODE_COUNTERS)], summed over the layers that have a router.
    """
    del mesh  # one chip a replica: nothing here is sharded
    x, cos, sin, q_scale = _embed(params, cfg, tokens, q_positions)
    scans = _scans(params, cfg)
    counts = jnp.zeros((len(DECODE_COUNTERS),), jnp.int32)
    for scope, layers, experts, first in scans:

        def body(carry, scanned, experts=experts, first=first):
            x, cache, counts = carry
            p, at = scanned
            x, cache, c = _layer(x, p, experts, at, cfg, cos, sin, q_scale, q_positions,
                                 cache, write_start, live=live, first=first)
            return (x, cache, counts + c), None

        with jax.named_scope(scope):
            (x, cache, counts), _ = jax.lax.scan(body, (x, cache, counts), layers)
    logits = _logits_at(params, cfg, x, row)
    return (logits, cache, counts) if counters else (logits, cache)
