"""Latent-attention (MLA) transformer with a dropless expert layer that
holds a share of the routed experts beside a shared expert, functional JAX;
optionally with leading dense layers (two stacks), a residual stream of
several copies mixed by hyper-connections, a sigmoid router with a
selection bias, and layers of linear attention (the gated delta rule with
a channel-wise decay, KDA) beside the latent ones in any stated order.

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

A fourth, for a model that states an attention kind a layer (``cfg.layer_types``;
``has_kinds``; benchmark/reference/kimi_linear_ref.py is its mathematics):

- **Layers of several kinds, in any order.** A layer is *latent* ("full_attention":
  the block above; ``q = h·Wq`` straight to the heads where ``cfg.q_rank`` is 0; no
  rotary position where ``cfg.rope_on_full_layers`` is false) or *linear*
  ("linear_attention": ``_kda_layer``, ops/kda.py). ``params["layers"]`` is a stack for
  each kind the model has, FFN kind × attention kind (``stack_kinds``), in any
  stated order (models/kinds.py, which models/stacks.py goes through too), a scan
  a run of layers of one kind under ``stack.<kind>``. The cache is then THREE
  arrays: the latent layers' rows, the linear layers' float32 states ``[Lk, B, H,
  dk, dv]`` and their convolutions' tails; a layer's index into them is its count
  among the layers of its attention kind. Scopes ``attn.kda`` (inside it
  ``kda.conv``, ``kda.gates``, ``kda.chunk``, ``kda.state``, ``kda.out``); counter
  ``decode_kda_slots``.

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
step, sp, tp/dp > 1 (engine/family.py says why a recurrent state cannot
have its rows offloaded, seeded, paged or rolled back).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from omnia_tpu.models import kinds
from omnia_tpu.models.config import ModelConfig
from omnia_tpu.ops import hyper_connections as hc
from omnia_tpu.ops.attention import _kernel_on, _pallas_decode_mode, prefill_kernel_on
from omnia_tpu.ops.decode_mla_attention import block_rows, decode_mla_attention
from omnia_tpu.ops.kda import decode_kda_state, kda_chunked
from omnia_tpu.ops.moe import EXPERT_COUNTERS, init_ffn
from omnia_tpu.ops.moe import expert_ffn as _experts, unstack_experts as _unstack_experts
from omnia_tpu.ops.norms import rms_norm
from omnia_tpu.ops.prefill_attention import latent_prefill_attention
from omnia_tpu.ops.rope import (apply_rope, apply_rope_interleaved, rope_cos_sin, yarn_cos_sin,
                                yarn_softmax_scale)

_NEG_INF = -1e30

#: Counters a decode step sums on the device over its layers, in the
#: order ``forward(..., counters=True)`` returns them (engine.metrics keys).
DECODE_COUNTERS = EXPERT_COUNTERS

#: Every kind a layer of this family can be (models/kinds.py), in its stacks'
#: order, and this family's name for a full-attention layer: the latent one.
_KINDS = ("dense_kda", "dense_mla", "sparse_kda", "sparse_mla")
_NAMES = {"full": "mla"}


def has_kinds(cfg: ModelConfig) -> bool:
    """Whether the model states an attention kind a layer (``layer_types``, or the
    stacks of one cut out of such a model): ``params["layers"]`` is a stack a kind."""
    return cfg.layer_types is not None or cfg.layer_stacks is not None


def stack_kinds(cfg: ModelConfig) -> tuple:
    """The kind of each stack of a model that ``has_kinds``."""
    return kinds.stack_kinds(cfg, _KINDS, _NAMES)


def decode_counters(cfg: ModelConfig) -> tuple:
    """``DECODE_COUNTERS`` and, for a model with linear-attention layers, the states a
    decode step updates (live slots, a layer), as ``forward`` returns them."""
    return EXPERT_COUNTERS + (("decode_kda_slots",) if cfg.has_state_layers else ())


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
    tree, or stacks"). A model that ``has_kinds``: a layer lies in the stack of
    its kind, in whatever order the model states (models/kinds.py)."""
    if has_kinds(cfg):
        return kinds.layer_order(cfg, _KINDS, _NAMES)
    dense = cfg.num_dense_layers
    return (tuple((0, i) for i in range(dense))
            + tuple((1, i) for i in range(cfg.num_layers - dense)))


def with_layer_order(cfg: ModelConfig, order) -> ModelConfig:
    """The same model with the layers ``order`` names: its own order over
    the cut stacks, any of which may be left with none."""
    if has_kinds(cfg):
        return kinds.with_layer_order(cfg, order, _KINDS, _NAMES)
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
    not converge that far). A model that ``has_kinds``: ``_init_kinds``."""
    if has_kinds(cfg):
        return _init_kinds(cfg, key, dtype)
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


def _init_kinds(cfg: ModelConfig, key: jax.Array, dtype):
    """``init_params`` of a model that ``has_kinds``: ``layers`` is a list, a stack
    for each of ``stack_kinds(cfg)`` with its layers on axis 0 (a cut model's may
    have none). A linear-attention layer: ``wqkv`` (q | k | v before the
    convolution), ``conv`` [taps, 3·H·dk], the decay's ``wfa``, ``wfb``, ``dt_bias``, ``a_log``
    (float32), ``wb``, the output gate's ``wga``, ``wgb``, the head norm's ``on``, ``wo``.
    ``a_log`` = log U(1, 16) a head, ``dt_bias`` the inverse softplus of a step
    log-uniform in 1e-3 … 0.1 a channel: a token's decay then lies in about 0.2 …
    0.999 as a trained model's does (near 0 it would empty the state every token)."""
    if cfg.residual_copies != 1:
        raise ValueError("a model with layer_types in the latent family has one residual copy")
    D, V, L, H = cfg.hidden_size, cfg.vocab_size, cfg.num_layers, cfg.num_heads
    R, Rq, dn, dr, dv = (cfg.kv_rank, cfg.q_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    Hk, dk, r, taps = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_gate_rank, cfg.kda_conv_kernel
    out_std = 0.02 / (2 * max(L, 1)) ** 0.5

    def stack_of(kind, c, key):
        keys = iter(jax.random.split(key, 32))

        def normal(shape, std=0.02, dtype=dtype):
            return (jax.random.normal(next(keys), shape, dtype=jnp.float32) * std).astype(dtype)

        def log_uniform(shape, lo, hi):
            return jnp.exp(jax.random.uniform(next(keys), shape, jnp.float32,
                                              jnp.log(lo), jnp.log(hi)))

        if kind.endswith("kda"):
            step = log_uniform((c, Hk * dk), 1e-3, 0.1)
            attn = {"wqkv": normal((c, D, 3 * Hk * dk)),
                    "conv": normal((c, taps, 3 * Hk * dk), std=taps ** -0.5),
                    "wfa": normal((c, D, r)), "wfb": normal((c, r, Hk * dk)),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "a_log": jnp.log(log_uniform((c, Hk), 1.0, 16.0)),
                    "wb": normal((c, D, Hk)),
                    "wga": normal((c, D, r)), "wgb": normal((c, r, Hk * dk)),
                    "on": jnp.ones((c, dk), dtype),
                    "wo": normal((c, Hk * dk, D), std=out_std)}
        else:
            query = ({"wqa": normal((c, D, Rq)), "qn": jnp.ones((c, Rq), dtype),
                      "wqb": normal((c, Rq, H * (dn + dr)))} if Rq
                     else {"wq": normal((c, D, H * (dn + dr)))})
            attn = {**query, "wkva": normal((c, D, R + dr)), "kvn": jnp.ones((c, R), dtype),
                    "wkvb": normal((c, R, H * (dn + dv))),
                    "wo": normal((c, H * dv, D), std=out_std)}
        mlp = init_ffn(cfg, c, kind.startswith("sparse"), normal, out_std, lambda: next(keys))
        return {"ln1": jnp.ones((c, D), dtype), "ln2": jnp.ones((c, D), dtype),
                "attn": attn, "mlp": mlp}

    k_embed, k_head, k_layers = jax.random.split(key, 3)
    return {
        "embed": (jax.random.normal(k_embed, (V, D), jnp.float32) * 0.02).astype(dtype),
        "layers": [stack_of(kind, c, jax.random.fold_in(k_layers, _KINDS.index(kind)))
                   for kind, c in zip(stack_kinds(cfg), kinds.stack_counts(cfg, _KINDS, _NAMES))],
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": (jax.random.normal(k_head, (D, V), jnp.float32) * 0.02).astype(dtype),
    }


def param_specs(cfg: ModelConfig):
    """Everything replicated: this family runs on one chip a replica (the
    engine refuses tp/dp/sp > 1 for it)."""
    return jax.tree_util.tree_map(
        lambda _: P(), jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    )


def init_kv_cache(cfg: ModelConfig, batch: int, seq: int, dtype=jnp.bfloat16,
                  kv_quant=None):
    """The zeroed latent cache, a tuple of one array [L, B, S, W]. A model that
    ``has_kinds``: the latent layers' rows [Lm, B, S, W], the linear-attention layers'
    states [Lk, B, H, dk, dv] (float32 whatever ``dtype``) and their tails [Lk, B, taps -
    1, 3·H·dk]; a layer's index is its count among the layers of its attention kind."""
    if kv_quant:
        raise NotImplementedError("kv_quant is not ported to the latent cache")
    if not has_kinds(cfg):
        return (jnp.zeros((cfg.num_layers, batch, seq, row_width(cfg)), dtype=dtype),)
    Lm, Lk = (cfg.attention_kinds.count(kind) for kind in ("full", "kda"))
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    return (jnp.zeros((Lm, batch, seq, row_width(cfg)), dtype=dtype),
            jnp.zeros((Lk, batch, H, d, d), dtype=jnp.float32),
            jnp.zeros((Lk, batch, cfg.kda_conv_kernel - 1, 3 * H * d), dtype=dtype))


def kv_cache_specs(kv_quant=None) -> tuple:
    """(One chip a replica, no mesh: nobody lays these over a cache of three arrays.)"""
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
        if cfg.q_rank:
            cq = rms_norm(jnp.dot(h, p["wqa"]), p["qn"], cfg.rms_norm_eps)
            q = jnp.dot(cq, p["wqb"]).reshape(B, T, cfg.num_heads, dn + dr)
        else:  # no query rank: straight to the heads
            q = jnp.dot(h, p["wq"]).reshape(B, T, cfg.num_heads, dn + dr)
        if q_scale is not None:
            q = (q * q_scale[:, :, None, None]).astype(q.dtype)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        if cos is not None:  # else no rotary position: q_rope as projected
            q_rope = _rope(cfg, q_rope, cos, sin)
    with jax.named_scope("attn.kv_latent"):
        kva = jnp.dot(h, p["wkva"])
        c = rms_norm(kva[..., :R], p["kvn"], cfg.rms_norm_eps)
        if cos is not None:
            k_rope = _rope(cfg, kva[..., None, R:], cos, sin)[..., 0, :]
        else:  # no rotary position: k_rope as projected
            k_rope = kva[..., R:]
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
    """Prefill (T > 1): every head's keys and values from the rows [B, S, W] at positions
    0 … S-1 → [B, T, H·dv], by the einsums below or the blocked kernel where routed on."""
    B, T, H, dn = q_nope.shape
    R, dr, dv = cfg.kv_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    S = rows.shape[1]
    if prefill_kernel_on(T, S, cfg.attn_value_width):
        return latent_prefill_attention(
            q_nope, q_rope, rows, wkvb, q_positions,
            scale=yarn_softmax_scale(dn + dr, cfg.rope_yarn),
            interpret=_pallas_decode_mode() == "interpret")
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
           cache, write_start, live=None, first=0, cache_layer=None):
    """One block, ``at`` its index in its stack and ``first + at`` in the
    model and the cache (one tree: the same; a model that ``has_kinds``
    names the cache's layer itself, ``cache_layer``). With a cache, ``cache`` is
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
    if cache_layer is not None:
        layer = cache_layer
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
        cos, sin = _rotary(cfg, q_positions) if cfg.rope_on_full_layers else (None, None)
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


#: The ε inside the square root of a KDA head's key and query norms.
_L2_EPS = 1e-6


def _fresh(write_start):
    """bool [B]: the slots whose chunk starts at position 0, a new tenant's first."""
    return write_start == 0


def _kda_layer(x, p, experts, at, cfg: ModelConfig, cache, cache_layer, write_start,
               n_real, live):
    """One block whose attention is the gated delta rule (ops/kda.py), ``at`` its
    index in its stack (its experts' too), ``cache_layer`` its index among the
    linear-attention layers. ``cache``: (states [Lk, B, H, dk, dv] float32, tails [Lk,
    B, taps - 1, 3·H·dk]) whole, or None for a fresh chunk, which starts from zero and
    gets its (state, tail) back instead. A state has no position to mask by
    afterwards, so which rows and steps may touch it is said here alone:
    - of a chunk's T rows the first ``n_real`` [B] count; the pad behind them gets
      β = 0 and g = 0, which leaves S as it is, and the tail kept is the last REAL row's;
    - a chunk (T > 1) at position 0 is a new tenant's first: it starts from S = 0
      and a zero tail whatever the slot holds;
    - a decode step (T == 1) leaves a slot that is not ``live`` as it is.

    → (x, cache or (state, tail), counts int32 [3]: EXPERT_COUNTERS, states updated)."""
    B, T, _ = x.shape
    H, d, taps = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_kernel
    f32, a = jnp.float32, p["attn"]
    step = T == 1 and cache is not None
    with jax.named_scope("attn.kda"):  # kda.conv/gates/chunk/state/out inside
        h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
        pre = jnp.dot(h, a["wqkv"])                                # q | k | v  [B, T, 3·H·d]
        with jax.named_scope("kda.conv"):
            if cache is None:
                tail = jnp.zeros((B, taps - 1, pre.shape[-1]), pre.dtype)
            else:
                states, tails = cache
                tail = jax.lax.dynamic_index_in_dim(tails, cache_layer, 0, keepdims=False)
                if not step:
                    fresh = _fresh(write_start)
                    tail = jnp.where(fresh[:, None, None], 0, tail)
            rows = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
            u = sum(a["conv"][j].astype(f32) * rows[:, j:j + T].astype(f32)
                    for j in range(taps))
            q, k, v = (t.reshape(B, T, H, d) for t in jnp.split(jax.nn.silu(u), 3, axis=-1))
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + _L2_EPS) * d ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + _L2_EPS)
            # What the next chunk's first taps see: the rows before the last
            # real one, never the pad's.
            kept = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(r, n, taps - 1, 0))(
                rows, n_real)
            if step and live is not None:
                kept = jnp.where(live[:, None, None], kept, tail.astype(kept.dtype))
        with jax.named_scope("kda.gates"):
            decay = jnp.dot(jnp.dot(h, a["wfa"]), a["wfb"], preferred_element_type=f32)
            g = -jnp.exp(a["a_log"].astype(f32))[:, None] * jax.nn.softplus(
                (decay + a["dt_bias"].astype(f32)).reshape(B, T, H, d))
            beta = jax.nn.sigmoid(jnp.dot(h, a["wb"], preferred_element_type=f32))
            gate = jax.nn.sigmoid(jnp.dot(jnp.dot(h, a["wga"]), a["wgb"],
                                          preferred_element_type=f32)).reshape(B, T, H, d)
            real = jnp.arange(T, dtype=jnp.int32)[None, :] < n_real[:, None]
            g = jnp.where(real[:, :, None, None], g, 0.0)
            beta = jnp.where(real[:, :, None], beta, 0.0)
        if step:
            with jax.named_scope("kda.state"):
                o, states = decode_kda_state(
                    states, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], cache_layer, live,
                    kernel=_kernel_on(), interpret=_pallas_decode_mode() == "interpret")
                o = o[:, None]
            updated = (jnp.sum(live, dtype=jnp.int32) if live is not None
                       else jnp.int32(B))
        else:
            with jax.named_scope("kda.chunk"):
                S = jnp.zeros((B, H, d, d), f32)
                if cache is not None:
                    S = jax.lax.dynamic_index_in_dim(states, cache_layer, 0, keepdims=False)
                    S = jnp.where(fresh[:, None, None, None], 0.0, S)
                o, S = kda_chunked(q, k, v, g, beta, S)
                if cache is not None:
                    states = jax.lax.dynamic_update_slice_in_dim(states, S[None], cache_layer, 0)
            updated = jnp.int32(0)
        if cache is not None:
            with jax.named_scope("kda.conv"):
                tails = jax.lax.dynamic_update_slice_in_dim(
                    tails, kept.astype(tails.dtype)[None], cache_layer, 0)
        with jax.named_scope("kda.out"):
            y = rms_norm(o, a["on"], cfg.rms_norm_eps) * gate
            x = x + jnp.dot(y.astype(x.dtype).reshape(B, T, H * d), a["wo"])
    with jax.named_scope("mlp"):  # moe.route/sort/experts/combine/shared inside
        y, counts = _experts(rms_norm(x, p["ln2"], cfg.rms_norm_eps), p["mlp"], experts, at, cfg)
    counts = jnp.concatenate([counts, updated[None]])
    return x + y, ((states, tails) if cache is not None else (S, kept)), counts


def _run_kinds(params, cfg: ModelConfig, x, cos, sin, q_scale, q_positions, cache,
               write_start, row, live):
    """Every layer of a model that ``has_kinds``, a scan a run of consecutive layers
    of one kind (models/kinds.py) under ``stack.<kind>``: the stack's leaves are read
    a layer at a time where they lie, the routed experts' never sliced. With a
    cache (rows, states, tails) it is the carry and comes back; without one the
    chunk's own come back, an array for each. → (x, cache or chunks, counts)."""
    B, T, _ = x.shape
    n_real = jnp.broadcast_to(T if row is None else row + 1, (B,)).astype(jnp.int32)
    n_counts = len(decode_counters(cfg))
    counts = jnp.zeros((n_counts,), jnp.int32)
    chunks = {"mla": [], "kda": []}
    for stack, kind, first, length, cache_first in kinds.runs(cfg, _KINDS, _NAMES):
        layers = params["layers"][stack]
        scanned, experts = (_unstack_experts(layers) if kind.startswith("sparse")
                            else (layers, None))
        latent = kind.endswith("mla")

        def body(carry, i, scanned=scanned, experts=experts, latent=latent, first=first,
                 cache_first=cache_first):
            x, cache, counts = carry
            p = jax.tree_util.tree_map(
                lambda leaf: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False), scanned)
            at = cache_first + i - first
            if latent:
                x, kept, c = _layer(x, p, experts, i, cfg, cos, sin, q_scale, q_positions,
                                    None if cache is None else cache[0], write_start,
                                    live=live, cache_layer=at)
                c = jnp.pad(c, (0, n_counts - c.shape[0]))
                if cache is not None:
                    kept = (kept, *cache[1:])
            else:
                x, kept, c = _kda_layer(x, p, experts, i, cfg,
                                        None if cache is None else cache[1:], at,
                                        write_start, n_real, live)
                if cache is not None:
                    kept = (cache[0], *kept)
            return ((x, kept, counts + c), None) if cache is not None else (
                (x, None, counts + c), kept)

        with jax.named_scope(f"stack.{kind}"):
            (x, cache, counts), kept = jax.lax.scan(
                body, (x, cache, counts), first + jnp.arange(length, dtype=jnp.int32))
        if kept is not None:
            chunks[kind.split("_")[1]].append(kept)
    if cache is not None:
        return x, cache, counts
    def whole(kind):  # the runs' chunks, in the order of the kind's cache arrays
        found = [each if isinstance(each, tuple) else (each,) for each in chunks[kind]]
        if found:
            return tuple(each[0] if len(each) == 1 else jnp.concatenate(each, axis=0)
                         for each in zip(*found))
        if kind == "mla":  # a cut model without a layer of the kind: arrays of no layers
            return (jnp.zeros((0, B, T, row_width(cfg)), x.dtype),)
        H, d = cfg.kda_num_heads, cfg.kda_head_dim
        return (jnp.zeros((0, B, H, d, d), jnp.float32),
                jnp.zeros((0, B, cfg.kda_conv_kernel - 1, 3 * H * d), x.dtype))

    return x, (*whole("mla"), *whole("kda")), counts


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def forward_prefill(params, cfg: ModelConfig, tokens, q_positions, row=None, mesh=None):
    """Fresh-sequence prefill: attention over the chunk's own rows, which
    come back for the engine to place into a cache slot (``mesh``: unused, as ``forward``'s).

    tokens, q_positions: int32 [B, T]. Returns (logits [B, T, V] f32,
    chunk [L, B, T, W]); with ``row`` (int32 scalar) the logits are that
    row's alone, [B, V]: the head runs over one row (``_logits_at``). A model that
    ``has_kinds`` returns what ``init_kv_cache`` makes, a slot's worth: (logits, rows,
    states, tails); rows past ``row`` are pad and touch neither state nor tail."""
    x, cos, sin, q_scale = _embed(params, cfg, tokens, q_positions)
    if has_kinds(cfg):
        x, chunks, _ = _run_kinds(params, cfg, x, cos, sin, q_scale, q_positions, None,
                                  None, row, None)
        return (_logits_at(params, cfg, x, row), *chunks)
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


def forward(params, cfg: ModelConfig, tokens, q_positions, *cache, mesh=None, live=None,
            counters=False, row=None):
    """Serving forward (prefill or decode: same code, different T).

    tokens, q_positions: int32 [B, T]; ``*cache``: the cache's arrays (``init_kv_cache``)
    and behind them write_start, int32 [B], the row where this chunk's rows land.
    ``live``: bool [B] or None, the slots whose logits the caller will use; the decode
    kernels skip the others, and a linear-attention layer leaves their state and
    tail as they are. ``row``: int32 scalar or None, the one row of the T whose logits
    the caller will use: the logits are then [B, V], the rows behind it pad.
    Returns (logits [B, T, V] f32, *cache), and with ``counters`` one more: int32
    [len(decode_counters(cfg))], summed over the layers."""
    del mesh  # one chip a replica: nothing here is sharded
    *cache, write_start = cache
    x, cos, sin, q_scale = _embed(params, cfg, tokens, q_positions)
    if has_kinds(cfg):
        x, cache, counts = _run_kinds(params, cfg, x, cos, sin, q_scale, q_positions,
                                      tuple(cache), write_start, row, live)
        logits = _logits_at(params, cfg, x, row)
        return (logits, *cache, counts) if counters else (logits, *cache)
    cache, = cache
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
