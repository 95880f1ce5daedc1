"""Llama-family transformer (dense MLP or Mixtral-style MoE), functional JAX.

Design notes (TPU-first, not a port — the reference platform executes no
models; see SURVEY.md §0):

- **Params are a plain pytree** with all layers stacked on a leading [L] axis
  and the forward pass runs ``lax.scan`` over layers. One traced layer body
  instead of L inlined copies → ~L× faster XLA compiles and an HLO whose
  while-loop body XLA tiles once for the MXU.
- **One forward for prefill AND decode.** The KV cache is slot-contiguous
  (row s = absolute position s), writes land via per-batch
  ``dynamic_update_slice`` at ``write_start``, and causality is just
  ``key_index <= query_position`` (ops/attention.py). The cache stays in
  its buffer through the layer scan: it is the scan's carry, each layer
  writes its new rows at ``[layer, b, write_start[b]]`` and attends over
  layer ``layer`` of the whole array (the decode kernel takes the index
  in its block map), so no layer of K or V is copied out or back.
  Multi-turn incremental prefill falls out for free: pass write_start =
  current length.
- **Sharding by annotation**: ``param_specs`` returns a PartitionSpec pytree
  (megatron-style tensor parallel over the "tp" mesh axis: attention heads,
  FFN hidden dim, expert dim, vocab). Activations shard batch over "dp". XLA
  GSPMD inserts the collectives; there are no explicit psums here.
- Compute dtype bf16 (MXU native), logits and softmax statistics f32.

**Layers of several kinds** (``is_stacked(cfg)``: window layers,
linear-attention layers, state-space layers, a share of the routed experts,
leading dense layers, norms on the sublayers' outputs) are models/stacks.py's,
which this module dispatches to at the top of each entry point:
``params["layers"]`` is
then a sequence of stacks, the cache of a model with window layers four
arrays (whole contexts beside rings), that of a model with linear-attention
or state-space layers four too (whole contexts, float32 states, convolution
tails), and
``layer_order`` /
``with_layer_order`` state and cut the order. Everything above is the model
whose layers are all alike, which traces none of that and compiles to the
programs it always had.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from omnia_tpu.models.config import ModelConfig
from omnia_tpu.models.kv_quant import (
    QuantKV,
    is_quant_kv,
    kv_map,
    quantize_rows,
    validate_kv_quant,
)
from omnia_tpu.models.paged_kv import PagedKV, is_paged, write_rows
from omnia_tpu.models.quant import qdot
from omnia_tpu.models.stacks import (  # noqa: F401  (the module contract's names)
    _init_stacks,
    _run_stacks,
    cache_kv_heads,
    conv_width,
    decode_counters,
    decode_window_rows,
    is_stacked,
    layer_order,
    ring_rows,
    rope_tables,
    stack_kinds,
    state_shape,
    tail_shape,
    with_layer_order,
)
from omnia_tpu.ops.attention import einsum_attention, gqa_attention
from omnia_tpu.ops.moe import moe_mlp
from omnia_tpu.ops.norms import rms_norm
from omnia_tpu.ops.rope import apply_rope, rope_cos_sin


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16):
    """Random-initialized parameter pytree (layers stacked on axis 0; a
    model of several kinds of layers: a stack each, ``_init_stacks``)."""
    if is_stacked(cfg):
        return _init_stacks(cfg, key, dtype)
    L, D, F, V = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden_size, cfg.vocab_size
    keys = iter(jax.random.split(key, 16))

    def normal(key, shape, std=0.02):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * std).astype(dtype)

    attn = {
        "wq": normal(next(keys), (L, D, cfg.q_dim)),
        "wk": normal(next(keys), (L, D, cfg.kv_dim)),
        "wv": normal(next(keys), (L, D, cfg.kv_dim)),
        "wo": normal(next(keys), (L, cfg.q_dim, D), std=0.02 / (2 * L) ** 0.5),
    }
    if cfg.is_moe:
        E = cfg.num_experts
        mlp = {
            "router": normal(next(keys), (L, D, E)),
            "wg": normal(next(keys), (L, E, D, F)),
            "wu": normal(next(keys), (L, E, D, F)),
            "wd": normal(next(keys), (L, E, F, D), std=0.02 / (2 * L) ** 0.5),
        }
    else:
        mlp = {
            "wg": normal(next(keys), (L, D, F)),
            "wu": normal(next(keys), (L, D, F)),
            "wd": normal(next(keys), (L, F, D), std=0.02 / (2 * L) ** 0.5),
        }
    params = {
        "embed": normal(next(keys), (V, D)),
        "layers": {
            "ln1": jnp.ones((L, D), dtype=dtype),
            "ln2": jnp.ones((L, D), dtype=dtype),
            "attn": attn,
            "mlp": mlp,
        },
        "final_norm": jnp.ones((D,), dtype=dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(next(keys), (D, V))
    return params


def param_specs(cfg: ModelConfig):
    """PartitionSpec pytree matching init_params (tensor parallel on "tp");
    a model of several kinds of layers is replicated whole (the engine
    refuses tp/dp/sp > 1 for it)."""
    if is_stacked(cfg):
        return jax.tree_util.tree_map(
            lambda _: P(), jax.eval_shape(lambda: init_params(cfg, jax.random.key(0))))
    attn = {
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
    }
    if cfg.is_moe:
        # Expert parallelism: experts sharded over the same ICI axis.
        mlp = {
            "router": P(None, None, None),
            "wg": P(None, "tp", None, None),
            "wu": P(None, "tp", None, None),
            "wd": P(None, "tp", None, None),
        }
    else:
        mlp = {
            "wg": P(None, None, "tp"),
            "wu": P(None, None, "tp"),
            "wd": P(None, "tp", None),
        }
    specs = {
        "embed": P("tp", None),
        "layers": {
            "ln1": P(None, None),
            "ln2": P(None, None),
            "attn": attn,
            "mlp": mlp,
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def param_specs_pp(cfg: ModelConfig):
    """``param_specs`` with the stacked layer axis sharded over "pp"
    (parallel/pipeline.py): each pipeline stage holds its contiguous
    L/pp layer shard; tp sharding within a layer is unchanged, so pp
    composes with tensor parallelism. Non-layer params (embed, final
    norm, lm_head) stay replicated across pp — at 70B the embedding is
    ~2% of weights, a fair price for keeping the first/last stage
    symmetric and the checkpoint layout identical to the dense specs."""
    specs = param_specs(cfg)
    specs["layers"] = jax.tree.map(
        lambda s: P("pp", *s[1:]),
        specs["layers"],
        is_leaf=lambda x: isinstance(x, P),
    )
    return specs


def kv_cache_specs(kv_quant=None) -> tuple:
    """(k, v) PartitionSpecs for [L, B, S, Hkv, D] caches: batch over "dp",
    KV heads over "tp". With kv_quant the spec tree mirrors the QuantKV
    pytree (the scale drops the trailing head-dim axis but keeps the
    "tp"-sharded head axis). (A model with window layers has four arrays
    and runs unsharded: nobody lays these over its cache.)"""
    spec = P(None, "dp", None, "tp", None)
    if validate_kv_quant(kv_quant):
        qspec = QuantKV(spec, P(None, "dp", None, "tp"))
        return qspec, qspec
    return spec, spec


def paged_kv_specs(kv_quant=None) -> tuple:
    """(k, v) PartitionSpecs for PagedKV caches: the pool's page axis
    shards over "dp" (the axis the slot-batch left), KV heads over
    "tp"; the page table is tiny and replicated."""
    kspec, vspec = kv_cache_specs(kv_quant)
    tspec = P(None, None)
    return PagedKV(kspec, tspec), PagedKV(vspec, tspec)


def init_kv_cache(cfg: ModelConfig, batch: int, seq: int, dtype=jnp.bfloat16,
                  kv_quant=None):
    """Zeroed (k, v) caches: plain [L, B, S, Hkv, D] arrays, or QuantKV
    pairs (int8 rows + per-row-per-head f32 scales) when kv_quant is
    set. kv_quant=None allocates no scale tensors at all. A model with
    window layers: (k, v) of its full layers at ``seq`` rows, then (k, v) of
    its window layers at the ring's (``ring_rows``), whatever ``seq``. A
    model with linear-attention layers: (k, v) of its full layers, then the
    delta layers' states [Ld, B, H/p, dk, p·dv] (float32 whatever ``dtype``;
    ``stacks.state_heads_a_row`` heads side by side along the lanes) and
    their convolutions' tails [Ld, B, taps - 1, 2·H·dk + H·dv]; with
    state-space layers in their place the Mamba layers' states [Lm, B, N, E]
    float32 and tails [Lm, B, (K - 1)·E] (``stacks.state_shape``,
    ``stacks.tail_shape``). Either
    model's rows hold ``cache_kv_heads`` heads (more than ``num_kv_heads``
    where that is over eight and no whole number of eights)."""
    shape = (cfg.num_layers, batch, seq, cfg.num_kv_heads, cfg.head_dim)
    if cfg.has_state_layers:
        if kv_quant or cfg.has_window_layers:
            raise NotImplementedError("kv_quant and window layers are not ported to a "
                                      "cache with recurrent states")
        kinds = cfg.attention_kinds
        full = (kinds.count("full"), batch, seq, cache_kv_heads(cfg), cfg.head_dim)
        Ld = kinds.count("delta") + kinds.count("mamba")
        return (jnp.zeros(full, dtype=dtype), jnp.zeros(full, dtype=dtype),
                jnp.zeros((Ld, batch, *state_shape(cfg)), dtype=jnp.float32),
                jnp.zeros((Ld, batch, *tail_shape(cfg)), dtype=dtype))
    if cfg.has_window_layers:
        if kv_quant:
            raise NotImplementedError("kv_quant is not ported to a cache with rings")
        kinds = cfg.attention_kinds
        heads = (cache_kv_heads(cfg), cfg.head_dim)
        full = (kinds.count("full"), batch, seq, *heads)
        ring = (kinds.count("window"), batch, ring_rows(cfg), *heads)
        return tuple(jnp.zeros(shape, dtype=dtype) for shape in (full, full, ring, ring))
    if validate_kv_quant(kv_quant):
        def one():
            return QuantKV(
                jnp.zeros(shape, dtype=jnp.int8),
                jnp.zeros(shape[:-1], dtype=jnp.float32),
            )

        return one(), one()
    return jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype)


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------


def _dense_mlp(h, p):
    gate = qdot(h, p["wg"])
    up = qdot(h, p["wu"])
    return qdot(jax.nn.silu(gate) * up, p["wd"])


def _moe_mlp(h, p, cfg: ModelConfig):
    """Mixtral MoE — routing + dispatch live in ops/moe.py. Decode-sized
    token counts take the exact all-expert path; prefill/train token counts
    take GShard-style capacity dispatch (experts sharded over "tp")."""
    with jax.named_scope("moe.experts"):  # the router inside is moe.route
        return moe_mlp(h, p, cfg.num_experts_per_tok)


def _write_kv(cache, new, start, layer, slots_sharded=False):
    """Whole cache [L,B,S,Hkv,D] ← new [B,T,Hkv,D] at rows
    ``[layer, b, start[b] : start[b]+T]``, in place: the cache is the
    layer scan's carry, so only these B×T rows move. A window that would
    run past the cache end is shifted back to fit
    (``dynamic_update_slice``'s clamp), never split.

    A quantized cache quantizes the NEW rows here — the single producer
    seam for every serving write path (prefill chunk placement goes
    through kv_quant.cache_put with the same quantizer, so both paths
    store bit-identical int8 rows for the same values)."""
    if is_paged(cache):
        # Paged pool (EngineConfig.kv_pages): rows scatter through the
        # page table; quantization runs through the same quantize_rows
        # seam, so stored values are bit-identical across layouts.
        return write_rows(cache, new, start, layer)
    if is_quant_kv(cache):
        new = quantize_rows(new)

    def put(c, n):  # c [L, B, S, ...] ← n [B, T, ...]
        # One in-place update a slot. The v5e runs the B of them in a
        # quarter of the time its scatter loop takes for the same rows
        # (0.75 against 3.4 ms a decode step at 32 slots × 14 layers).
        n = n.astype(c.dtype)
        tail = (0,) * (n.ndim - 2)
        for b in range(n.shape[0]):
            c = jax.lax.dynamic_update_slice(
                c, n[b][None, None], (layer, b, start[b]) + tail
            )
        return c

    def put_sharded(c, n):
        # Slots sharded over "dp": a slot picked by number would be sent
        # to its owner one collective at a time. As a BATCH axis of one
        # scatter (not moved to the front, as vmap would) each shard
        # writes the slots it holds, with no collective at all.
        at = jnp.stack([jnp.full_like(start, layer), start], axis=-1)  # [B, 2]
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(1, n.ndim)),
            inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0, 2),
            operand_batching_dims=(1,),
            scatter_indices_batching_dims=(0,),
        )
        return jax.lax.scatter(
            c, at, n.astype(c.dtype), dnums, indices_are_sorted=True,
            unique_indices=True, mode=jax.lax.GatherScatterMode.CLIP,
        )

    return kv_map(put_sharded if slots_sharded else put, cache, new)


def _layer(x, p, cfg: ModelConfig, cos, sin, q_positions, ck, cv, write_start,
           attn_fn=None, mesh=None, layer=None, live=None):
    """One block. With a cache, ``ck``/``cv`` are the WHOLE caches
    [L, B, S, Hkv, D] (or paged pools) and ``layer`` this block's index
    into them: the new rows are written into layer ``layer`` in place,
    attention reads that layer where it lies, and the whole caches are
    returned — no layer is ever sliced out or stacked back.

    Every op sits in a named scope (metadata only), so a device trace
    names it whatever number XLA gives its fusion; what the layer scan
    itself emits (its slice of this layer's weights out of the stacked
    arrays) carries only the scan's own ``layers``."""
    B, T, D = x.shape
    with jax.named_scope("attn.qkv"):
        h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
        q = qdot(h, p["attn"]["wq"]).reshape(B, T, cfg.num_heads, cfg.head_dim)
        k = qdot(h, p["attn"]["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
        v = qdot(h, p["attn"]["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    with jax.named_scope("attn.rope"):
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    if ck is None:
        # Self-contained path (training, or fresh prefill): attend over this
        # chunk's own keys; the caller receives the k/v chunk to place into
        # a cache slot if it wants one.
        ck, cv = k, v
    else:
        dp = mesh.shape["dp"] if mesh is not None else 1
        sharded = dp > 1 and B % dp == 0  # as _decode_path shards them
        with jax.named_scope("kv.update"):
            ck = _write_kv(ck, k, write_start, layer, slots_sharded=sharded)
            cv = _write_kv(cv, v, write_start, layer, slots_sharded=sharded)

    with jax.named_scope("attn.decode" if T == 1 else "attn.prefill"):
        if attn_fn is not None:
            attn = attn_fn(q, ck, cv, q_positions)
        else:
            attn = gqa_attention(q, ck, cv, q_positions, mesh=mesh, layer=layer,
                                 live=live)
    with jax.named_scope("attn.out"):
        x = x + qdot(attn.reshape(B, T, -1), p["attn"]["wo"])

    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
        if cfg.is_moe:
            x = x + _moe_mlp(h2, p["mlp"], cfg)
        else:
            x = x + _dense_mlp(h2, p["mlp"])
    return x, ck, cv


def _embed(params, cfg: ModelConfig, tokens, q_positions):
    """Token embeddings and the rotary tables of their positions, made once
    a program: → (x, rope), ``rope`` the pair (cos, sin), or for a model of
    several kinds of layers a pair for each attention kind that rotates
    (``rope_tables``)."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        if is_stacked(cfg):
            return x, rope_tables(cfg, q_positions)
        cos, sin = rope_cos_sin(
            q_positions, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
        )
    return x, (cos, sin)


def forward_prefill(params, cfg: ModelConfig, tokens, q_positions, attn_fn=None,
                    row=None, mesh=None):
    """Fresh-sequence prefill: self-contained attention over the chunk,
    returning the per-layer KV chunk for the engine to place into a cache
    slot (so prefill never reads or writes other slots' cache).

    tokens, q_positions: int32 [B, T]
    Returns (logits [B, T, V] f32, k_chunk, v_chunk [L, B, T, Hkv, D]);
    with ``row`` (int32 scalar) the logits are that row's alone, [B, V]:
    the final norm and the head run over one row (``_logits_at``).
    attn_fn overrides the attention op (the ring-prefill path); ``mesh`` as
    ``forward`` takes it: a chunk whose heads a mesh shards keeps the einsums
    (ops/attention.py::prefill_kernel_on). A model
    with window layers returns four chunks, the window layers' already in
    the ring's shape [Lw, B, R, Hkv, D]: the last R real rows where the
    ring holds them.
    """
    x, rope = _embed(params, cfg, tokens, q_positions)
    if is_stacked(cfg):
        x, chunks, _ = _run_stacks(params, cfg, x, rope, q_positions, None, None,
                                   row, mesh, None)
        return (_logits_at(params, cfg, x, row), *chunks)
    cos, sin = rope

    def body(x, p):
        x, k, v = _layer(
            x, p, cfg, cos, sin, q_positions, None, None, None, attn_fn=attn_fn,
            mesh=mesh,
        )
        return x, (k, v)

    with jax.named_scope("layers"):
        x, (k_chunk, v_chunk) = jax.lax.scan(body, x, params["layers"])
    return _logits_at(params, cfg, x, row), k_chunk, v_chunk


def forward_prefill_ring(params, cfg: ModelConfig, tokens, q_positions, mesh,
                         row=None):
    """Long-context prefill: identical contract to `forward_prefill`, but
    attention runs as causal ring attention with q/k/v sequence-sharded
    over the mesh's "sp" axis (parallel/ring_attention.py), so the O(T²)
    attention FLOPs of a long prompt split across the ring instead of
    serializing on one device. The returned KV chunk is the full
    [L, B, T, Hkv, D] (GSPMD gathers shards on insert), so the serving
    cache layout is unchanged — sp accelerates prefill, decode still
    reads the resident rows.

    Requires T divisible by mesh.shape["sp"]; positions must be the
    fresh-sequence arange (ring blocks derive causality from global row
    index)."""
    from omnia_tpu.parallel.ring_attention import ring_attention

    def ring(q, k, v, _q_positions):
        return ring_attention(q, k, v, mesh)

    return forward_prefill(params, cfg, tokens, q_positions, attn_fn=ring, row=row)



# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _logits(params, cfg: ModelConfig, x):
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        if cfg.tie_embeddings:
            return jnp.dot(x, params["embed"].T).astype(jnp.float32)
        return qdot(x, params["lm_head"]).astype(jnp.float32)


def _logits_at(params, cfg: ModelConfig, x, row):
    """The head over the rows its caller reads: every row of x [B, T, D]
    (``row`` None → [B, T, V]), or row ``row`` alone (→ [B, V]), taken out
    of x BEFORE the final norm and the head. A placement samples one row of
    its prompt; the other T - 1 are the head's width in work nobody reads."""
    if row is None:
        return _logits(params, cfg, x)
    with jax.named_scope("lm_head"):
        x = jax.lax.dynamic_slice_in_dim(x, row, 1, axis=1)
    return _logits(params, cfg, x)[:, 0]


def forward(params, cfg: ModelConfig, tokens, q_positions, *cache_and_start,
            mesh=None, live=None, row=None, counters=False):
    """Serving forward (prefill or decode — same code, different T).

    tokens, q_positions: int32 [B, T]; then the cache's arrays (cache_k/v:
    [L, B, S, Hkv, D]; four for a model with window layers, the module
    docstring) and last write_start:
    int32 [B] row offset where this chunk's KV lands.
    mesh: the mesh params and caches are sharded over, if any — the
    decode kernel needs it named (ops/attention.py).
    live: bool [B], or None for "every slot live": the slots whose
    logits the caller will use. The decode kernel skips the others
    (no cache read, zero attention output); their rows are still
    written and their logits are garbage, as the caller expects.
    row: int32 scalar, or None for "every row": the one row of the T
    whose logits the caller will use (a placement's last prompt row).
    Returns (logits [B, T, V] f32, new_cache_k, new_cache_v); with ``row``
    the logits are [B, V]. Rows of the T past ``row`` are pad: a ring is
    not written by them. With ``counters`` (a model that names
    ``decode_counters``) a last result more: int32 [len(decode_counters(cfg))].

    The caches (plain, QuantKV or PagedKV alike) ride the layer scan
    whole, as its carry beside ``x``; what is scanned is each layer's
    weights and its index. A layer writes its B×T new rows into the
    carried buffer and reads its own layer of it, so with the caches
    donated the returned ones are the same buffers and the bytes that
    move are the new rows.
    """
    *cache, write_start = cache_and_start
    x, rope = _embed(params, cfg, tokens, q_positions)  # x [B,T,D]
    if is_stacked(cfg):
        x, cache, counts = _run_stacks(params, cfg, x, rope, q_positions,
                                       tuple(cache), write_start, row, mesh, live)
        out = (_logits_at(params, cfg, x, row), *cache)
        # (a model without experts counts nothing for them: the counters it
        # names are the last of ``_run_stacks``', none of them if it names none)
        named = len(decode_counters(cfg))
        return (*out, counts[counts.shape[0] - named:]) if counters else out
    cache_k, cache_v = cache
    cos, sin = rope

    def body(carry, scanned):
        x, ck, cv = carry
        p, layer = scanned
        return _layer(
            x, p, cfg, cos, sin, q_positions, ck, cv, write_start,
            mesh=mesh, layer=layer, live=live,
        ), None

    layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    with jax.named_scope("layers"):
        (x, new_k, new_v), _ = jax.lax.scan(
            body, (x, cache_k, cache_v), (params["layers"], layers)
        )
    return _logits_at(params, cfg, x, row), new_k, new_v


def forward_embed(params, cfg: ModelConfig, tokens, mask, mesh=None):
    """Embedding-role forward (reference Provider role `embedding`,
    provider_types.go:40-63 — served remotely there, on-device here):
    masked mean-pool of the final hidden states, L2-normalized f32 [B, D].

    tokens: int32 [B, T]; mask: [B, T] (1 = real token, 0 = pad); ``mesh``:
    the one the params are sharded over, if any (as ``forward`` takes it).
    """
    B, T = tokens.shape
    q_positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    x, rope = _embed(params, cfg, tokens, q_positions)

    def body(x, p):
        x, _, _ = _layer(x, p, cfg, *rope, q_positions, None, None, None, mesh=mesh)
        return x, None

    if is_stacked(cfg):
        x = _run_stacks(params, cfg, x, rope, q_positions, None, None, None,
                        mesh, None)[0]
    else:
        x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(jnp.float32)
    m = mask.astype(jnp.float32)[:, :, None]
    pooled = (x * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def forward_train(params, cfg: ModelConfig, tokens):
    """Full causal forward with no cache (training / scoring).

    tokens: int32 [B, T] → logits [B, T, V] f32.
    """
    B, T = tokens.shape
    q_positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    x, rope = _embed(params, cfg, tokens, q_positions)
    # Differentiated through: the einsums whatever the route (the blocked
    # kernel of ops/prefill_attention.py is a Pallas call, which has no VJP).
    if is_stacked(cfg):
        return _logits(params, cfg, _run_stacks(
            params, cfg, x, rope, q_positions, None, None, None, None, None,
            attn_fn=einsum_attention)[0])

    def body(x, p):
        x, _, _ = _layer(x, p, cfg, *rope, q_positions, None, None, None,
                         attn_fn=einsum_attention)
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _logits(params, cfg, x)
