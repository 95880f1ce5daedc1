"""Layers of several kinds in the pair family (models/llama.py dispatches
here for a model with window layers, experts of ``moe_ffn_hidden_size`` or
leading dense layers): stacks, runs and rings.

A layer has an attention kind, *window*
(``cfg.layer_types`` "sliding_attention": a query sees its own row and the
``sliding_window`` - 1 before it) or *full*, and an FFN kind, *dense* (SwiGLU
of ``ffn_hidden_size``) or *sparse* (``ops/moe.py::expert_ffn``: the sigmoid
or softmax router over all experts, the experts held here, all of them or a
rank's share, through ``moe_dropless``, and the shared expert where the model
has one, as models/mla.py runs it). ``params["layers"]`` is a
sequence of stacks, one for each kind the model has (``stack_kinds``): a
model whose every layer is sparse has sparse stacks only, and nothing here
asks for a dense one. ``layer_order`` / ``with_layer_order`` state and cut
the order as benchmark/README.md sets out (models/kinds.py, which the latent
family's kinds go through too). A forward pass is one ``lax.scan`` for each run
of consecutive layers of one kind, under the scope ``stack.<kind>``, over the
run's indices into its stack: the stack's leaves are read a layer at a time
where they lie, the routed experts' never sliced at all. With ``cfg.qk_norm``
every query and key head is RMS-normed (one gain ``[head_dim]``) before any
rotation (``attn.qk_norm``).

*A rotary table a kind of attention layer* (``rope_tables``, made once a
program under ``rope.tables``; a layer turns its q and k by its kind's under
``attn.rope``): window layers by plain RoPE at ``rope_theta``; full layers by
the same table, by one of their own where ``cfg.rope_full_yarn`` states it
(YaRN's blended frequencies, cos and sin times the attention factor), or,
``rope_on_full_layers`` false, not at all.

*The cache of a model with window layers is four arrays*: K and V of the
full layers ``[Lf, B, S, Hkv, D]`` (row s = position s, as models/llama.py has it) and K and V
of the window layers ``[Lw, B, R, Hkv, D]``, rings of R = ``ring_rows(cfg)``
rows, the window rounded up to a power of two: ring row r of a slot holds
the newest position p ≡ r (mod R) that the slot has written. The engine and
the harness hand the tuple back whole; a slot's view of an array is the
array's own row axis whole (``[L, 1, S or R, ...]``, engine/programs.py's
seam), and which rows of it mean what is this module's word alone:

- *Every write into a ring writes real rows only.* A chunk of T rows of
  which ``n`` are real (``row + 1`` where the caller names the last real row,
  else T) writes the last min(n, R) of them, each to ``(start + j) mod R``;
  the pad behind them, which in a whole-context array lands past the
  frontier, would here wrap onto rows still inside the window. A decode
  step writes a slot's row only where the slot is live.
- *A chunk attends before it writes*: over ``[the window - 1 rows before it,
  out of the ring | its own k, v]`` (``ops/attention.py::window_attention``:
  the blocked kernel of ops/prefill_attention.py with a lower bound on the
  key blocks where the route takes it, the einsums of ``band_attention``
  else, and in training).
  The ring therefore never has to hold a chunk and the window before it at
  once, R = window serves a chunk of any length, and the cost is O(T × 2 ×
  window) whatever the context. Sizing R for the largest chunk instead would
  make the ring's bytes, and a decode step's read of it, grow with the
  largest prefill bucket.
- *Decode* writes the slot's row and attends over the ring, masking by the
  position a row holds and never by its index (``ring_decode_attention``, the
  Pallas kernel ``decode_window_attention``): a row that the slot's own
  positions have not reached is the previous tenant's and reads as "before
  position 0". A full layer's call is ``decode_gqa_attention`` as ever.

Not ported to a model of several kinds, and refused by name at engine
construction (engine/family.py): kv_quant, kv_pages, sessions, the prefix
pool, spec_decode, the mixed step, int8 weights, sp, tp/dp > 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from omnia_tpu.models import kinds
from omnia_tpu.models.config import ModelConfig
from omnia_tpu.ops import attention as _attention
from omnia_tpu.ops.attention import decode_block_rows, gqa_attention
from omnia_tpu.ops.moe import EXPERT_COUNTERS, expert_ffn, init_ffn, unstack_experts
from omnia_tpu.ops.norms import rms_norm
from omnia_tpu.ops.rope import apply_rope, rope_cos_sin, yarn_scaled_cos_sin

#: Every kind a layer can be, in the order a model's stacks stand in.
_KINDS = ("dense_window", "dense_full", "sparse_window", "sparse_full")


def is_stacked(cfg: ModelConfig) -> bool:
    """Whether ``params["layers"]`` is a sequence of stacks: a model with
    window layers, a share of the routed experts or leading dense layers
    (or one cut out of such a model)."""
    return bool(cfg.layer_types is not None or cfg.layer_stacks is not None
                or cfg.moe_ffn_hidden_size or cfg.num_dense_layers)


def decode_counters(cfg: ModelConfig) -> tuple:
    """Counters a decode step sums on the device over its layers, in the
    order ``forward(..., counters=True)`` returns them (engine.metrics keys):
    the expert layer's, for a model that has one."""
    return EXPERT_COUNTERS if cfg.moe_ffn_hidden_size else ()


def ring_rows(cfg: ModelConfig) -> int:
    """Rows of a window layer's ring: the window rounded up to a power of
    two (the decode kernel masks a row's age with ``& (R - 1)``)."""
    return 1 << (cfg.sliding_window - 1).bit_length()


def decode_window_rows(cfg: ModelConfig, lengths) -> int:
    """Ring rows that a window layer's decode kernel spans for live slots of
    those context ``lengths``: the ring's blocks up to a slot's position, the
    whole ring (and no more, whatever the context) once the position has
    passed it. 0 for a model without rings. (engine.metrics'
    ``decode_window_rows``, beside ``decode_kv_blocks`` for the full layers.)"""
    if not cfg.has_window_layers:
        return 0
    ring = ring_rows(cfg)
    rows = decode_block_rows(ring)
    return sum(min(n // rows + 1, ring // rows) * rows for n in lengths)


def rope_tables(cfg: ModelConfig, positions) -> dict:
    """(cos, sin) [..., head_dim // 2] of ``positions`` for each attention kind
    that rotates, made once a program under ``rope.tables``: "window" by
    plain RoPE; "full", where ``rope_on_full_layers``, the same pair unless
    ``rope_full_yarn`` gives the full layers a table of their own (YaRN's
    blended frequencies, cos and sin times the attention factor)."""
    with jax.named_scope("rope.tables"):
        tables = {"window": rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                         cfg.rope_scaling)}
        if cfg.rope_on_full_layers:
            tables["full"] = tables["window"] if cfg.rope_full_yarn is None else (
                yarn_scaled_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                    cfg.rope_full_yarn))
    return tables


def stack_kinds(cfg: ModelConfig) -> tuple:
    """The kind of each stack of ``params["layers"]``: those of ``_KINDS``
    that the model has a layer of (models/kinds.py)."""
    return kinds.stack_kinds(cfg, _KINDS)


def layer_order(cfg: ModelConfig) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ... (benchmark/README.md,
    "`layers`: one tree, or stacks")."""
    return kinds.layer_order(cfg, _KINDS)


def with_layer_order(cfg: ModelConfig, order) -> ModelConfig:
    """The same model with the layers ``order`` names: its own order over
    the cut stacks, any of which may be left with none."""
    return kinds.with_layer_order(cfg, order, _KINDS)


def _runs(cfg: ModelConfig) -> list:
    return kinds.runs(cfg, _KINDS)


def _init_stacks(cfg: ModelConfig, key: jax.Array, dtype):
    """``init_params`` of a model of several kinds: ``layers`` is a list, a
    stack for each of ``stack_kinds(cfg)`` with its layers on axis 0 (a cut
    model's may have none). Of the routed experts only the held share
    exists; a selection bias is ``mlp/bias`` [E] float32, the midpoints of
    N(0, 0.05)'s equal shares in a seeded order, the same on every rank:
    large enough to change which experts are kept, and neither a rank's
    load nor the count of experts a step hits depends on the seed."""
    D, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    out_std = 0.02 / (2 * max(L, 1)) ** 0.5
    counts = kinds.stack_counts(cfg, _KINDS)

    def stack_of(kind, c, key):
        keys = iter(jax.random.split(key, 16))

        def normal(shape, std=0.02, dtype=dtype):
            return (jax.random.normal(next(keys), shape, dtype=jnp.float32) * std).astype(dtype)

        attn = {"wq": normal((c, D, cfg.q_dim)), "wk": normal((c, D, cfg.kv_dim)),
                "wv": normal((c, D, cfg.kv_dim)), "wo": normal((c, cfg.q_dim, D), std=out_std)}
        if cfg.qk_norm:
            attn["qn"] = jnp.ones((c, cfg.head_dim), dtype)
            attn["kn"] = jnp.ones((c, cfg.head_dim), dtype)
        mlp = init_ffn(cfg, c, kind.startswith("sparse"), normal, out_std, lambda: next(keys))
        return {"ln1": jnp.ones((c, D), dtype), "ln2": jnp.ones((c, D), dtype),
                "attn": attn, "mlp": mlp}

    k_embed, k_head, k_layers = jax.random.split(key, 3)
    params = {
        "embed": (jax.random.normal(k_embed, (V, D), jnp.float32) * 0.02).astype(dtype),
        "layers": [stack_of(kind, c, jax.random.fold_in(k_layers, _KINDS.index(kind)))
                   for kind, c in zip(stack_kinds(cfg), counts)],
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k_head, (D, V), jnp.float32) * 0.02).astype(dtype)
    return params


def _ring_image(new, start, n, R: int):
    """What a ring of R rows takes from the real rows of ``new`` [B, T, Hkv,
    D] (the first ``n`` [B] of them, at positions ``start[b] + j``): ring row
    r takes the newest real row whose position is ≡ r (mod R). → (rows [B,
    R, Hkv, D], which of them there is such a row for, bool [B, R]). No pad
    row is ever among them (the module docstring)."""
    T = new.shape[1]
    r = jnp.arange(R, dtype=jnp.int32)[None, :]
    last = (start + n - 1)[:, None]                    # the newest real position
    j = (n - 1)[:, None] - ((last - r) & (R - 1))      # [B, R] its row of `new`
    rows = jnp.take_along_axis(new, jnp.clip(j, 0, T - 1)[:, :, None, None], axis=1)
    return rows, j >= 0


def _ring_put(ring, new, start, n, layer):
    """Ring [L, B, R, Hkv, D] ← ``_ring_image`` of a chunk, layer ``layer``;
    a row the chunk has nothing for keeps what it holds."""
    rows, fresh = _ring_image(new, start, n, ring.shape[2])
    old = jax.lax.dynamic_index_in_dim(ring, layer, 0, keepdims=False)
    rows = jnp.where(fresh[:, :, None, None], rows.astype(ring.dtype), old)
    return jax.lax.dynamic_update_slice(ring, rows[None], (layer, 0, 0, 0, 0))


def _ring_put_step(ring, new, position, live, layer):
    """One decode step's write: ring [L, B, R, Hkv, D] ← ``new`` [B, 1, Hkv,
    D] at row ``position[b] mod R`` of each LIVE slot, one in-place update a
    slot (``_write_kv``); a dead slot's ring is left as it is."""
    R = ring.shape[2]
    for b in range(new.shape[0]):
        at = (layer, b, position[b] & (R - 1), 0, 0)
        row = new[b][None, None].astype(ring.dtype)
        if live is not None:
            row = jnp.where(live[b], row, jax.lax.dynamic_slice(ring, at, row.shape))
        ring = jax.lax.dynamic_update_slice(ring, row, at)
    return ring


def _ring_rows_before(ring, start, window: int, layer):
    """The ``window`` rows at positions ``start[b] - window … start[b] - 1``
    out of layer ``layer`` of a ring [L, B, R, Hkv, D] → [B, window, Hkv, D]
    (what lies before position 0 is whatever the ring holds:
    ``window_attention`` masks it)."""
    R = ring.shape[2]
    rows = (start[:, None] - window + jnp.arange(window, dtype=jnp.int32)[None, :]) & (R - 1)
    held = jax.lax.dynamic_index_in_dim(ring, layer, 0, keepdims=False)
    return jnp.take_along_axis(held, rows[:, :, None, None], axis=1)


def _stack_layer(x, p, experts, at, kind, cfg: ModelConfig, rope, q_positions,
                 cache, cache_layer, write_start, n_real, mesh, live, attn_fn=None):
    """One block of a model of several kinds: ``kind`` its stack's, ``at`` its
    index in the stack (its experts' too), ``cache_layer`` its index into the
    cache arrays of its attention kind, ``rope`` the program's ``rope_tables``
    (a kind that is not among them is not rotated). ``cache``: the whole
    tuple (the module docstring), or None for a chunk on its own (training, a fresh
    prefill), which gets its rows back instead: (k, v) [B, T, Hkv, D] of a
    full layer, [B, R, Hkv, D] of a window layer. ``attn_fn`` overrides a full
    layer's attention over a chunk on its own (training: the einsums), and
    with one a window layer takes the einsum band. → (x, cache or rows,
    counts int32 [2] as EXPERT_COUNTERS)."""
    B, T, _ = x.shape
    attention = kind.split("_")[1]
    window = cfg.sliding_window if attention == "window" else 0
    a = p["attn"]
    with jax.named_scope("attn.qkv"):
        h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
        q = jnp.dot(h, a["wq"]).reshape(B, T, cfg.num_heads, cfg.head_dim)
        k = jnp.dot(h, a["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
        v = jnp.dot(h, a["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        with jax.named_scope("attn.qk_norm"):
            q = rms_norm(q, a["qn"], cfg.rms_norm_eps)
            k = rms_norm(k, a["kn"], cfg.rms_norm_eps)
    if attention in rope:
        cos, sin = rope[attention]
        with jax.named_scope("attn.rope"):
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)

    with jax.named_scope("attn.decode" if T == 1 else "attn.prefill"):
        if window and cache is None:
            with jax.named_scope("attn.window"):
                attn = (_attention.band_attention(q, k, v, None, None, None, window)
                        if attn_fn else
                        _attention.window_attention(q, k, v, None, None, None, window, mesh))
            # A fresh chunk starts at position 0: its rows as a ring that
            # held nothing takes them.
            kept = tuple(_ring_image(rows, jnp.zeros((B,), jnp.int32), n_real,
                                     ring_rows(cfg))[0] for rows in (k, v))
        elif window and T == 1:
            *full, rk, rv = cache
            with jax.named_scope("kv.update"):
                rk = _ring_put_step(rk, k, write_start, live, cache_layer)
                rv = _ring_put_step(rv, v, write_start, live, cache_layer)
            with jax.named_scope("attn.window"):
                attn = _attention.ring_decode_attention(q, rk, rv, q_positions, cache_layer, live, window)
            kept = (*full, rk, rv)
        elif window:
            *full, rk, rv = cache
            with jax.named_scope("attn.window"):
                attn = _attention.window_attention(
                    q, k, v, _ring_rows_before(rk, write_start, window, cache_layer),
                    _ring_rows_before(rv, write_start, window, cache_layer),
                    write_start, window, mesh)
            with jax.named_scope("kv.update"):
                rk = _ring_put(rk, k, write_start, n_real, cache_layer)
                rv = _ring_put(rv, v, write_start, n_real, cache_layer)
            kept = (*full, rk, rv)
        elif cache is None:
            with jax.named_scope("attn.full"):
                attn = (attn_fn(q, k, v, q_positions) if attn_fn else
                        gqa_attention(q, k, v, q_positions, mesh=mesh))
            kept = (k, v)
        else:
            from omnia_tpu.models.llama import _write_kv  # (it imports this module)

            ck, cv, *rings = cache
            with jax.named_scope("kv.update"):
                ck = _write_kv(ck, k, write_start, cache_layer)
                cv = _write_kv(cv, v, write_start, cache_layer)
            with jax.named_scope("attn.full"):
                attn = gqa_attention(q, ck, cv, q_positions, mesh=mesh,
                                     layer=cache_layer, live=live)
            kept = (ck, cv, *rings)
    with jax.named_scope("attn.out"):
        x = x + jnp.dot(attn.reshape(B, T, -1), a["wo"])
    with jax.named_scope("mlp"):  # moe.route/sort/experts/combine/shared inside
        y, counts = expert_ffn(rms_norm(x, p["ln2"], cfg.rms_norm_eps), p["mlp"],
                               experts, at, cfg)
    return x + y, kept, counts


def _run_stacks(params, cfg: ModelConfig, x, rope, q_positions, cache, write_start,
                row, mesh, live, attn_fn=None):
    """Every layer of a model of several kinds, a scan a run (``_runs``)
    under ``stack.<kind>``; ``rope`` is ``rope_tables``, of which a layer
    takes its kind's. With a cache (the whole tuple) it is the carry and
    comes back; without one the chunk's rows come back in its place, an
    array for each cache array ([L of the kind, B, T or R, Hkv, D]). →
    (x, cache or chunks, counts summed over the layers)."""
    B, T, _ = x.shape
    n_real = jnp.broadcast_to(T if row is None else row + 1, (B,)).astype(jnp.int32)
    counts = jnp.zeros((len(EXPERT_COUNTERS),), jnp.int32)
    chunks = {"full": [], "window": []}
    for stack, kind, first, length, cache_first in _runs(cfg):
        layers = params["layers"][stack]
        scanned, experts = (unstack_experts(layers) if kind.startswith("sparse")
                            else (layers, None))

        def body(carry, i, scanned=scanned, experts=experts, kind=kind, first=first,
                 cache_first=cache_first):
            x, cache, counts = carry
            # The layer's leaves where they lie in the stack, as a scan over
            # the stack itself would read them: a run is part of a stack.
            p = jax.tree_util.tree_map(
                lambda leaf: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False), scanned)
            x, kept, c = _stack_layer(
                x, p, experts, i, kind, cfg, rope, q_positions, cache,
                cache_first + i - first, write_start, n_real, mesh, live, attn_fn)
            return ((x, kept, counts + c), None) if cache is not None else (
                (x, None, counts + c), kept)

        with jax.named_scope(f"stack.{kind}"):
            (x, cache, counts), rows = jax.lax.scan(
                body, (x, cache, counts), first + jnp.arange(length, dtype=jnp.int32))
        if rows is not None:
            chunks[kind.split("_")[1]].append(rows)
    if cache is not None:
        return x, cache, counts

    def whole(kind, rows):  # the runs' rows, in the order of the kind's cache
        if len(rows) == 1:
            return rows[0]
        if not rows:
            shape = (0, B, ring_rows(cfg) if kind == "window" else T,
                     cfg.num_kv_heads, cfg.head_dim)
            return jnp.zeros(shape, x.dtype), jnp.zeros(shape, x.dtype)
        return tuple(jnp.concatenate(each, axis=0) for each in zip(*rows))

    if not cfg.has_window_layers:
        return x, whole("full", chunks["full"]), counts
    return x, (*whole("full", chunks["full"]), *whole("window", chunks["window"])), counts


