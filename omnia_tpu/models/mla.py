"""Latent-attention (MLA) transformer with a dropless expert layer that
holds a share of the routed experts beside a shared expert, functional JAX.

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

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from omnia_tpu.models.config import ModelConfig
from omnia_tpu.ops.attention import _kernel_on, _pallas_decode_mode
from omnia_tpu.ops.decode_mla_attention import block_rows, decode_mla_attention
from omnia_tpu.ops.moe import moe_dropless
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
DECODE_COUNTERS = ("moe_assignments_held", "moe_experts_hit")


#: Cache rows in one block of this family's decode kernel, by cache length:
#: what the engine's ``decode_kv_blocks`` counts in (scheduler._live_kv_blocks).
decode_block_rows = block_rows


def row_width(cfg: ModelConfig) -> int:
    """Lanes of one cached row: [c | k_rope] padded to the 128-lane tile."""
    return -(-(cfg.kv_rank + cfg.qk_rope_head_dim) // 128) * 128


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16):
    """Random-initialized parameter pytree (layers stacked on axis 0); of
    the routed experts only the held share exists."""
    L, D, V, H = cfg.num_layers, cfg.hidden_size, cfg.vocab_size, cfg.num_heads
    R, Rq = cfg.kv_rank, cfg.q_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Eh = cfg.moe_ffn_hidden_size, cfg.experts_held
    Fs = cfg.num_shared_experts * F
    keys = iter(jax.random.split(key, 16))
    out_std = 0.02 / (2 * L) ** 0.5

    def normal(shape, std=0.02):
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32) * std).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype=dtype)

    mlp = {
        "router": normal((L, D, cfg.num_experts)),
        "wg": normal((L, Eh, D, F)),
        "wu": normal((L, Eh, D, F)),
        "wd": normal((L, Eh, F, D), std=out_std),
    }
    if Fs:
        mlp["shared"] = {
            "wg": normal((L, D, Fs)),
            "wu": normal((L, D, Fs)),
            "wd": normal((L, Fs, D), std=out_std),
        }
    return {
        "embed": normal((V, D)),
        "layers": {
            "ln1": ones(L, D),
            "ln2": ones(L, D),
            "attn": {
                "wqa": normal((L, D, Rq)),
                "qn": ones(L, Rq),
                "wqb": normal((L, Rq, H * (dn + dr))),
                "wkva": normal((L, D, R + dr)),
                "kvn": ones(L, R),
                "wkvb": normal((L, R, H * (dn + dv))),
                "wo": normal((L, H * dv, D), std=out_std),
            },
            "mlp": mlp,
        },
        "final_norm": ones(D),
        "lm_head": normal((D, V)),
    }


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


_EXPERT_STACKS = ("wg", "wu", "wd")


def _unstack_experts(layers):
    """(what the layer scan slices a layer at a time, the routed experts'
    three stacks [L, Eh, …] whole): the experts are nine tenths of a
    layer's bytes and a step needs only those a token chose, so the scan
    must not slice a layer's out (ops/moe.py::_grouped_matmul)."""
    mlp = layers["mlp"]
    scanned = {**layers, "mlp": {k: v for k, v in mlp.items() if k not in _EXPERT_STACKS}}
    return scanned, {k: mlp[k] for k in _EXPERT_STACKS}


def _experts(h2, p, experts, layer, cfg: ModelConfig):
    """The routed experts held here (``experts``: their stacks over all
    layers, of which ``layer``'s are used) and the shared expert. h2
    [B, T, D] → (y [B, T, D], counts int32 [2] as DECODE_COUNTERS)."""
    B, T, D = h2.shape
    y, held, hit = moe_dropless(
        h2.reshape(B * T, D), {"router": p["router"], **experts},
        cfg.num_experts_per_tok,
        first_expert=cfg.expert_rank * cfg.experts_held,
        routed_scaling_factor=cfg.routed_scaling_factor, layer=layer,
    )
    y = y.reshape(B, T, D)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            s = p["shared"]
            y = y + jnp.dot(jax.nn.silu(jnp.dot(h2, s["wg"])) * jnp.dot(h2, s["wu"]), s["wd"])
    return y, jnp.stack([held, hit])


def _layer(x, p, experts, layer, cfg: ModelConfig, cos, sin, q_scale, q_positions,
           cache, write_start, live=None):
    """One block, ``layer`` its index. With a cache, ``cache`` is the
    WHOLE [L, B, S, W]: the new rows are written in place and attention
    reads that layer where it lies. Without one (fresh prefill) attention
    runs over the chunk's own rows, which are returned. ``experts`` are
    the routed experts' stacks over all layers (``_unstack_experts``)."""
    B, T, _ = x.shape
    with jax.named_scope("attn.qkv"):  # attn.q_lora and attn.kv_latent inside
        h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
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
        x = x + jnp.dot(attn, p["attn"]["wo"])
    with jax.named_scope("mlp"):  # moe.route/sort/experts/combine/shared inside
        h2 = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
        y, counts = _experts(h2, p["mlp"], experts, layer, cfg)
    return x + y, kept, counts


def _embed(params, cfg: ModelConfig, tokens, q_positions):
    """Token embeddings, the rotary tables of their positions and the
    position-dependent query scale (None where the model has none)."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
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
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return jnp.dot(x, params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def forward_prefill(params, cfg: ModelConfig, tokens, q_positions):
    """Fresh-sequence prefill: attention over the chunk's own rows, which
    come back for the engine to place into a cache slot.

    tokens, q_positions: int32 [B, T]. Returns (logits [B, T, V] f32,
    chunk [L, B, T, W])."""
    x, cos, sin, q_scale = _embed(params, cfg, tokens, q_positions)
    scanned, experts = _unstack_experts(params["layers"])

    def body(x, scanned):
        p, layer = scanned
        x, row, _ = _layer(x, p, experts, layer, cfg, cos, sin, q_scale, q_positions,
                           None, None)
        return x, row

    layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    with jax.named_scope("layers"):
        x, chunk = jax.lax.scan(body, x, (scanned, layers))
    return _logits(params, cfg, x), chunk


def forward(params, cfg: ModelConfig, tokens, q_positions, cache, write_start,
            mesh=None, live=None, counters=False):
    """Serving forward (prefill or decode: same code, different T).

    tokens, q_positions: int32 [B, T]; cache: [L, B, S, W]; write_start:
    int32 [B], the row where this chunk's rows land. ``live``: bool [B]
    or None, the slots whose logits the caller will use; the decode
    kernel skips the others. Returns (logits [B, T, V] f32, cache), and
    with ``counters`` a third: int32 [len(DECODE_COUNTERS)], summed over
    the layers.
    """
    del mesh  # one chip a replica: nothing here is sharded
    x, cos, sin, q_scale = _embed(params, cfg, tokens, q_positions)
    scanned, experts = _unstack_experts(params["layers"])

    def body(carry, scanned):
        x, cache, counts = carry
        p, layer = scanned
        x, cache, c = _layer(x, p, experts, layer, cfg, cos, sin, q_scale, q_positions,
                             cache, write_start, live=live)
        return (x, cache, counts + c), None

    layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    zero = jnp.zeros((len(DECODE_COUNTERS),), jnp.int32)
    with jax.named_scope("layers"):
        (x, cache, counts), _ = jax.lax.scan(
            body, (x, cache, zero), (scanned, layers)
        )
    logits = _logits(params, cfg, x)
    return (logits, cache, counts) if counters else (logits, cache)
