"""Paged KV cache device layout (EngineConfig.kv_pages).

The slot-contiguous cache reserves ``max_seq`` rows per slot whether the
sequence uses them or not; at large S that slack is what caps concurrent
sessions per chip. The paged layout (vLLM's PagedAttention adapted to
XLA's static-shape constraint) stores rows in one fixed pool

- ``pool``  ``[L, P, PAGE_S, Hkv, D]``  (plain arrays, or QuantKV int8
  rows + ``[L, P, PAGE_S, Hkv]`` scales under ``kv_quant``)
- ``table`` int32 ``[B, max_seq / PAGE_S]`` — per-slot page table; row
  ``s`` of slot ``b`` lives at ``pool[:, table[b, s // PAGE_S],
  s % PAGE_S]``.

Both ride one :class:`PagedKV` pytree, so the engine's ``_ck``/``_cv``
flow through every compiled program, donation chain, and ``device_put``
exactly like the plain arrays they replace. Page allocation/refcounts/
copy-on-write are host-side (engine/kv_pages.py); everything here is
trace-time gather/scatter over a table the host has already made
consistent.

Reads: the Pallas decode kernel gathers K/V blocks through the table in
its BlockSpec index map (ops/decode_attention.py — HBM traffic stays
proportional to context length, now without reserving capacity); the
XLA fallback (prefill/extend/verify, and decode off-TPU) materializes
the per-slot view with ``jnp.take`` and runs the exact contiguous
attention math — which is what makes paged and contiguous serving
bit-identical on the fallback path.

Writes quantize through the same ``quantize_rows`` seam as the
contiguous cache (models/kv_quant.py), so int8 rows are bit-identical
across layouts.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from omnia_tpu.models.kv_quant import QuantKV, is_quant_kv, kv_map, quantize_rows


@jax.tree_util.register_pytree_node_class
class PagedKV:
    """One paged KV cache: pool rows + the page table that orders them."""

    __slots__ = ("pool", "table")

    def __init__(self, pool: Any, table: Any) -> None:
        self.pool = pool
        self.table = table

    def tree_flatten(self) -> tuple[tuple[Any, Any], None]:
        return (self.pool, self.table), None

    @classmethod
    def tree_unflatten(cls, _aux: None, children: Sequence[Any]) -> "PagedKV":
        return cls(*children)

    # Logical (slot-contiguous-equivalent) shape, so shape-inspecting
    # callers ([L, B, S, H, D] unpacks) keep working.
    @property
    def shape(self) -> tuple[int, ...]:
        q = self.pool.q if is_quant_kv(self.pool) else self.pool
        *lead, _p, ps, h, d = q.shape
        b, np_ = self.table.shape
        return (*lead, b, np_ * ps, h, d)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def page_tokens(self) -> int:
        q = self.pool.q if is_quant_kv(self.pool) else self.pool
        return int(q.shape[-3])

    @property
    def nbytes(self) -> int:
        return sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves((self.pool, self.table))
        )

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"PagedKV(pool={self.pool!r}, table={self.table.shape})"


def is_paged(x: Any) -> bool:
    return isinstance(x, PagedKV)


# ---------------------------------------------------------------------------
# Gathers (reads)
# ---------------------------------------------------------------------------


def gather_view(cache: PagedKV) -> Any:
    """Per-layer paged cache → the slot-contiguous view ``[B, S, Hkv,
    D]`` (QuantKV when quantized): the XLA `take` fallback the
    contiguous attention math runs over. Values are copied verbatim, so
    the downstream score/prob matmuls are bit-identical to a contiguous
    cache holding the same rows."""
    table = cache.table  # [B, NP]

    def g(arr):  # arr [P, PS, ...]
        out = jnp.take(arr, table, axis=0)  # [B, NP, PS, ...]
        s = out.shape
        return out.reshape((s[0], s[1] * s[2]) + s[3:])

    return kv_map(g, cache.pool)


def gather_slot(cache: PagedKV, slot: Any) -> Any:
    """Engine-level paged cache → ONE slot's contiguous view
    ``[L, 1, S, Hkv, D]`` (the extend/mixed prefill seam: forward runs
    against this view exactly as it runs against a contiguous slot
    slice, then the written rows scatter back with ``put_chunk``)."""
    np_ = cache.table.shape[1]
    row = lax.dynamic_slice(cache.table, (slot, 0), (1, np_))  # [1, NP]

    def g(arr):  # arr [L, P, PS, ...]
        out = jnp.take(arr, row, axis=1)  # [L, 1, NP, PS, ...]
        s = out.shape
        return out.reshape(s[:2] + (s[2] * s[3],) + s[4:])

    return kv_map(g, cache.pool)


def gather_rows(cache: PagedKV, slot: Any, rows: int) -> Any:
    """One slot's leading ``rows`` rows → ``[L, rows, Hkv, D]`` (the
    session-offload path: only the pages covering the bucket move, and
    the host page format stays identical to the contiguous engine's)."""
    ps = cache.page_tokens
    npg = -(-rows // ps)
    row = lax.dynamic_slice(cache.table, (slot, 0), (1, npg))[0]  # [npg]

    def g(arr):  # arr [L, P, PS, ...]
        out = jnp.take(arr, row, axis=1)  # [L, npg, PS, ...]
        s = out.shape
        flat = out.reshape((s[0], s[1] * s[2]) + s[3:])
        return lax.slice_in_dim(flat, 0, rows, axis=1)

    return kv_map(g, cache.pool)


def gather_pages(pool: Any, idx: Any) -> Any:
    """Pool pages ``idx`` [n] → ``[L, n, PAGE_S, ...]`` (prefix host
    tier demotion: pages move verbatim)."""
    return kv_map(lambda arr: jnp.take(arr, idx, axis=1), pool)


# ---------------------------------------------------------------------------
# Scatters (writes)
# ---------------------------------------------------------------------------


def _flat_scatter(arr: Any, flat_idx: Any, vals: Any, layer: Any = None) -> Any:
    """Scatter rows into the whole pool ``arr [L, P, PS, rest]`` with the
    page axes flattened: ``flat_idx [...]`` indexes the P*PS row axis.
    With ``layer`` the rows ``vals [*idx_shape, rest]`` land in that one
    layer; without, ``vals [L, *idx_shape, rest]`` land in every layer."""
    s = arr.shape
    a2 = arr.reshape((s[0], s[1] * s[2]) + s[3:])
    where = slice(None) if layer is None else layer
    return a2.at[where, flat_idx].set(vals).reshape(s)


def write_rows(cache: PagedKV, new: Any, start: Any, layer: Any) -> PagedKV:
    """The paged edition of llama._write_kv: layer ``layer`` of the whole
    pool ``[L, P, PS, Hkv, D]`` ← new rows ``[B, T, Hkv, D]`` at per-slot
    row offsets ``start [B]``, routed through the page table, in place.
    Fresh rows quantize through the SAME ``quantize_rows`` as the
    contiguous write seam, so stored int8 rows are bit-identical across
    layouts."""
    table, pool = cache.table, cache.pool
    ps = cache.page_tokens
    np_ = table.shape[1]
    t = new.shape[1]
    r = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # [B, T]
    r = jnp.minimum(r, np_ * ps - 1)
    page = jnp.take_along_axis(table, r // ps, axis=1)  # [B, T]
    flat = page * ps + (r % ps)

    if is_quant_kv(pool):
        new = quantize_rows(new)
    pool = kv_map(
        lambda arr, n: _flat_scatter(arr, flat, n.astype(arr.dtype), layer),
        pool, new,
    )
    return PagedKV(pool, table)


def put_chunk(cache: PagedKV, chunk: Any, slot: Any, start: Any) -> PagedKV:
    """Engine-level paged cache ← one slot's chunk ``[L, 1, T, Hkv, D]``
    at rows [start, start+T) — the paged ``cache_put``. The chunk may be
    float (fresh prefill KV — quantized here iff the pool is) or already
    in cache representation (restore/seed copies move verbatim)."""
    table, pool = cache.table, cache.pool
    ps = cache.page_tokens
    np_ = table.shape[1]
    t = chunk.q.shape[2] if is_quant_kv(chunk) else chunk.shape[2]
    row = lax.dynamic_slice(table, (slot, 0), (1, np_))[0]  # [NP]
    r = jnp.minimum(start + jnp.arange(t, dtype=jnp.int32), np_ * ps - 1)
    flat = jnp.take(row, r // ps) * ps + (r % ps)  # [T]

    if is_quant_kv(pool):
        qc = chunk if is_quant_kv(chunk) else quantize_rows(chunk)
        pool = QuantKV(
            _flat_scatter(pool.q, flat, qc.q[:, 0].astype(pool.q.dtype)),
            _flat_scatter(pool.s, flat, qc.s[:, 0].astype(pool.s.dtype)),
        )
    else:
        if is_quant_kv(chunk):
            raise TypeError("quantized chunk written into an unquantized pool")
        pool = _flat_scatter(pool, flat, chunk[:, 0].astype(pool.dtype))
    return PagedKV(pool, table)


def scatter_pages(pool: Any, idx: Any, pages: Any) -> Any:
    """Pool ← pages ``[L, n, PAGE_S, ...]`` at page ids ``idx`` [n]
    (prefix host-tier promotion; pages land verbatim)."""
    if is_quant_kv(pool):
        return QuantKV(
            pool.q.at[:, idx].set(pages.q.astype(pool.q.dtype)),
            pool.s.at[:, idx].set(pages.s.astype(pool.s.dtype)),
        )
    return pool.at[:, idx].set(pages.astype(pool.dtype))


def copy_page(pool: Any, src: Any, dst: Any) -> Any:
    """Pool page ``dst`` ← page ``src`` (all layers) — the device half
    of copy-on-write: a shared page a slot is about to write into is
    duplicated so the prefix entry (and other seeders) keep the
    original."""

    def one(arr):  # [L, P, PS, ...]
        zeros = (0,) * (arr.ndim - 2)
        page = lax.dynamic_slice(
            arr, (0, src) + zeros, (arr.shape[0], 1) + arr.shape[2:]
        )
        return lax.dynamic_update_slice(arr, page, (0, dst) + zeros)

    return kv_map(one, pool)
