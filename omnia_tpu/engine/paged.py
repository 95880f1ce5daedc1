"""Paged-KV wiring for the serving engine (EngineConfig.kv_pages).

The device side is one page pool + per-slot page tables riding the
``PagedKV`` pytree (models/paged_kv.py) — ``self._ck``/``_cv`` flow
through every compiled program unchanged. This mixin owns the HOST side:
the single free list (engine/kv_pages.py ``PageAllocator``) that serves
active slots, the prefix cache (entries hold refcounted page runs —
publish and seed are pure table rewrites, divergent writes trigger
copy-on-write page copies), and session offload/restore, plus the
occupancy gauges (``kv_pages_total/free``, ``kv_page_fragmentation``,
``kv_page_cow_copies``).

Every method here is a guarded no-op while ``kv_pages == 0``
(``self._pages is None``) — the contiguous engine never touches this
file's logic (tests/test_guards.py::test_kv_pages_zero_is_true_noop).

Write protocol (the invariant the whole layout rests on): before ANY
program that writes rows [from, through) of a slot is dispatched, the
engine calls ``_prepare_slot_write`` — shared pages in the range are
swapped for exclusive ones (copied iff they hold rows below ``from``),
missing pages are allocated, and the device table row is re-synced.
Table positions past a slot's pages point at the reserved TRASH page,
so the decode step's frozen-slot garbage writes can never corrupt
another slot's rows. Reads need no preparation: garbage reached through
trash entries sits past every causal mask.
"""

from __future__ import annotations

import logging

import jax.numpy as jnp

from omnia_tpu.engine.kv_pages import TRASH, PageAllocator, PoolExhausted
from omnia_tpu.models import llama
from omnia_tpu.models.kv_quant import is_quant_kv, kv_device, kv_host
from omnia_tpu.models.paged_kv import PagedKV

logger = logging.getLogger(__name__)


def dp_divisibility_error(name: str, value: int, dp: int) -> str:
    """Actionable message for the pool-vs-mesh divisibility checks: the
    offending values plus the nearest valid sizes (the old bare
    'must be divisible by dp' gave the operator nothing to act on)."""
    lo = (value // dp) * dp
    hi = lo + dp
    near = f"{lo} or {hi}" if lo > 0 else f"{hi}"
    return (
        f"{name}={value} must be divisible by dp={dp} so each "
        f"data-parallel shard holds an equal share of the pool; "
        f"nearest valid sizes: {near}"
    )


def validate_paged_config(cfg, meshed: bool) -> None:
    """Construction-time validation of the kv_pages knobs."""
    if cfg.kv_pages <= 0:
        return
    if cfg.kv_pages < 2:
        raise ValueError(
            f"kv_pages={cfg.kv_pages} must be >= 2: page 0 is the "
            f"reserved trash page, so 1 leaves zero usable pages"
        )
    if cfg.kv_page_tokens < 1 or cfg.max_seq % cfg.kv_page_tokens != 0:
        divisors = [d for d in (16, 32, 64, 128, 256)
                    if d <= cfg.max_seq and cfg.max_seq % d == 0]
        raise ValueError(
            f"kv_page_tokens={cfg.kv_page_tokens} must divide "
            f"max_seq={cfg.max_seq} (the page table is static-shape "
            f"[num_slots, max_seq/kv_page_tokens]); valid sizes include "
            f"{divisors or [cfg.max_seq]}"
        )
    if meshed and cfg.kv_pages % max(cfg.dp, 1) != 0:
        raise ValueError(
            dp_divisibility_error("kv_pages", cfg.kv_pages, cfg.dp)
        )


class _PagedKVMixin:
    """Paged-pool methods of :class:`InferenceEngine`. All engine-thread
    state (same ownership discipline as the session registry)."""

    _pages = None  # PageAllocator when kv_pages > 0, else None

    def _paged_on(self) -> bool:
        return self._pages is not None

    # -- device state ----------------------------------------------------

    def _alloc_paged_kv(self):
        """Fresh (ck, cv) PagedKV pair — pool + all-trash tables — at
        the engine's layout/sharding. Pure allocation (no allocator
        books): shared by ``_init_paged_state`` and the parallel-warmup
        worker states (engine/warmup.py), which chain donated paged
        operands through their own pool copy."""
        cfg = self.cfg
        np_pos = cfg.num_page_positions()

        def init():
            pool_k, pool_v = llama.init_kv_cache(
                self.model_cfg, cfg.kv_pages, cfg.kv_page_tokens,
                dtype=self._dtype, kv_quant=self._kv_quant,
            )
            # Two table copies (one per cache) so donation never sees the
            # same buffer twice; _sync_table_row updates them in lockstep.
            tk = jnp.zeros((cfg.num_slots, np_pos), jnp.int32)
            tv = jnp.zeros((cfg.num_slots, np_pos), jnp.int32)
            return PagedKV(pool_k, tk), PagedKV(pool_v, tv)

        return self._born_sharded(
            init, llama.paged_kv_specs(self._kv_quant)
        )

    def _init_paged_state(self) -> None:
        """(Re)allocate the page pool, tables, and allocator books —
        the paged half of ``_init_device_state`` (crash recovery calls
        it too: device pages died, host-paged sessions/prefixes keep
        their rows)."""
        cfg = self.cfg
        self._cache = tuple(self._alloc_paged_kv())
        self._pk = self._pv = None  # the prefix cache shares THIS pool
        self._pages = PageAllocator(cfg.kv_pages, cfg.kv_page_tokens, cfg.num_slots)
        if self._prefix_pool is not None:
            # Device page runs died with the pool; host-paged entries
            # survive — the paged edition of on_device_reset.
            for e in list(self._prefix_pool.entries()):
                if e.pages is not None:
                    e.pages = None
                    self._prefix_pool.evictions += 1
                    if e.host_k is None:
                        self._prefix_pool.drop_entry(e)
            self._prefix_pool.page_release = self._pages.release_pages
            if hasattr(self, "metrics"):
                self.metrics["prefix_cache_evictions"] = (
                    self._prefix_pool.evictions
                )
        if hasattr(self, "metrics"):
            self._update_page_metrics()

    def _sync_table_row(self, slot_idx: int) -> None:
        """Push one slot's full table row to the device (always the
        whole TRASH-padded row — one fixed-shape update regardless of
        how many positions changed)."""
        row = jnp.asarray(
            self._pages.table_row(slot_idx, self.cfg.num_page_positions()),
            jnp.int32,
        )
        self._ck = PagedKV(self._ck.pool, self._ck.table.at[slot_idx].set(row))
        self._cv = PagedKV(self._cv.pool, self._cv.table.at[slot_idx].set(row))

    def _update_page_metrics(self) -> None:
        a = self._pages
        self.metrics["kv_pages_free"] = a.free_count
        self.metrics["kv_page_fragmentation"] = a.fragmentation()
        self.metrics["kv_page_cow_copies"] = a.cow_copies

    # -- the write protocol ----------------------------------------------

    def _prepare_slot_write(self, slot_idx: int, from_row: int,
                            through_row: int) -> None:
        """Make rows [from_row, through_row) of a slot writable BEFORE
        the write program is dispatched: exclusive pages everywhere in
        the range (copy-on-write for shared pages holding surviving
        rows), fresh pages where the table points at trash, and the
        device table row re-synced. No-op while kv_pages == 0."""
        if self._pages is None:
            return
        through_row = min(through_row, self.cfg.max_seq)
        if through_row <= from_row:
            return
        need = self._pages.writes_needed(slot_idx, from_row, through_row)
        if need > self._pages.free_count and not self._reclaim_pages(
            need, protect_slot=slot_idx
        ):
            raise PoolExhausted(
                f"kv page pool exhausted writing rows [{from_row}, "
                f"{through_row}) of slot {slot_idx}: need {need} pages, "
                f"{self._pages.free_count} free of {self._pages.total} "
                f"(size kv_pages up, or lower concurrency)"
            )
        acts = self._pages.prepare_write(slot_idx, from_row, through_row)
        for _pos, new_page, copy_src in acts:
            if copy_src is not None:
                self._ck, self._cv = self._page_copy_fn(
                    self._ck, self._cv, copy_src, new_page
                )
        if acts:
            self._sync_table_row(slot_idx)
            self._update_page_metrics()

    def _prealloc_decode_pages(self, steps: int) -> None:
        """Extend every active slot's pages past its dispatched-write
        frontier before a decode chunk of ``steps`` tokens — decode
        writes must never land through a trash entry.

        Exhaustion policy: with the pool oversubscribed (the whole
        point of paging), concurrent decodes can outgrow it after
        reclaim has drained every idle source. That must degrade ONE
        stream, not the batch: the slot that cannot get pages finishes
        early with LENGTH (same class as hitting the cache end), its
        freed pages serve the survivors, and nothing reaches the
        fail-everything recovery path."""
        if self._pages is None:
            return
        s_max = self.cfg.max_seq
        for i, s in enumerate(self._slots):
            if s.active:
                cov = self._pages.covered[i]
                try:
                    self._prepare_slot_write(i, cov, min(cov + steps, s_max))
                except PoolExhausted:
                    from omnia_tpu.engine.types import FinishReason

                    logger.warning(
                        "kv page pool exhausted mid-decode: finishing "
                        "slot %d early with LENGTH at %d generated "
                        "tokens (%d/%d pages free) — size kv_pages up "
                        "for this concurrency",
                        i, s.generated, self._pages.free_count,
                        self._pages.total,
                    )
                    self._finish_slot(i, FinishReason.LENGTH)

    def _trim_slot_pages(self, slot_idx: int, keep_rows: int) -> None:
        """Return every page past ``keep_rows`` to the free list (the
        bucket-padding slack after placement, everything for a freed
        slot) and point the vacated table positions back at trash."""
        if self._pages is None:
            return
        freed = self._pages.release_from(slot_idx, keep_rows)
        if freed:
            self._sync_table_row(slot_idx)
            self._update_page_metrics()

    def _free_slot_pages(self, slot_idx: int) -> None:
        self._trim_slot_pages(slot_idx, 0)

    def _prepare_slot_restore(self, slot_idx: int, host_k) -> None:
        """Session restore, paged edition: fresh pages covering the
        host rows, table synced, then the (shared) restore program
        scatters the rows through the table."""
        if self._pages is None:
            return
        rows = (host_k.q if is_quant_kv(host_k) else host_k).shape[1]
        self._free_slot_pages(slot_idx)
        self._prepare_slot_write(slot_idx, 0, int(rows))

    # -- reclaim ---------------------------------------------------------

    def _reclaim_pages(self, need: int, protect_slot: int = -1) -> bool:
        """Free pages until ``need`` are available: demote LRU unpinned
        prefix entries to the host tier, then offload idle pinned
        sessions. A demotion whose pages are all still shared with a
        live slot frees nothing NOW (the slot's release frees them
        later) — the loop must fall through to session offload in that
        case, not give up. False only when neither source progressed
        (every page is referenced by live work)."""
        while self._pages.free_count < need:
            before = self._pages.free_count
            if self._prefix_pool is not None:
                cands = [
                    e for e in self._prefix_pool.entries()
                    if e.pages is not None and e.refs == 0
                ]
                if cands:
                    # Prefer entries whose pages actually free (no
                    # co-holder), LRU within each class — demoting a
                    # fully-shared entry pays a host gather for zero
                    # immediate pages.
                    def key(e):
                        frees = all(
                            self._pages.refs.get(p, 0) == 1 for p in e.pages
                        )
                        return (not frees, e.last_used)

                    self._paged_demote_entry(min(cands, key=key))
            if self._pages.free_count > before:
                continue
            idle = [
                (sess.last_used, sid)
                for sid, sess in self._sessions.items()
                if sess.slot is not None and sess.slot != protect_slot
                and not self._slots[sess.slot].active
            ]
            if idle:
                self._offload_session(self._sessions[min(idle)[1]])
            if self._pages.free_count <= before:
                return False  # no forward progress anywhere
        return True

    # -- prefix cache over page runs -------------------------------------

    def _paged_adopt_entry(self, entry, slot_idx: int, matched: int) -> bool:
        """Seed a slot from a prefix entry: point the slot's leading
        table positions at the entry's pages (refcounted — ZERO device
        copies; the old pool's seed-copy program is gone). A partially
        matched tail page is adopted too: the suffix prefill's first
        write into it triggers the copy-on-write swap, preserving the
        matched rows. Host-paged entries promote via one page-run
        scatter into fresh pages that slot and entry then share."""
        ps = self.cfg.kv_page_tokens
        npg = -(-matched // ps)
        # The slot's stale pages (a diverged session, a dropped pin)
        # free FIRST — they may cover the promote's own allocation, and
        # reclaiming around them would demote/offload for nothing.
        self._free_slot_pages(slot_idx)
        if entry.pages is None and entry.host_k is not None:
            npg_e = -(-len(entry.tokens) // ps)
            if not self._reclaim_pages(npg_e, protect_slot=slot_idx):
                return False
            pages = self._pages.alloc_pages(npg_e)
            bucket = self.cfg.page_bucket_for(npg_e)
            idx = jnp.asarray(pages + [TRASH] * (bucket - npg_e), jnp.int32)
            self._ck, self._cv = self._scatter_pages_fn(
                self._ck, self._cv, idx,
                kv_device(entry.host_k), kv_device(entry.host_v),
            )
            entry.pages = pages  # the entry owns these references
            entry.host_k = entry.host_v = None
            self.metrics["prefix_cache_host_hits"] += 1
        if entry.pages is None:
            # Dropped between match and use (stale radix path after a
            # device reset) — rebuild on miss.
            self._prefix_pool.drop_entry(entry)
            return False
        self._pages.adopt(slot_idx, entry.pages[:npg], matched)
        self._sync_table_row(slot_idx)
        self._update_page_metrics()
        return True

    def _paged_publish(self, slot_idx: int, tokens: tuple,
                       registered: bool) -> None:
        """Publish a prefix from a freshly-prefilled slot: share the
        slot's leading pages with a new entry (refcount only — the
        store-copy program of the old dedicated pool is gone; the pages
        simply outlive the slot)."""
        npg = -(-len(tokens) // self.cfg.kv_page_tokens)
        pages = self._pages.share(slot_idx, npg)
        entry = self._prefix_pool.insert(
            tuple(tokens), self.cfg.page_bucket_for(npg), None, registered
        )
        entry.pages = pages
        self.metrics["prefix_cache_insertions"] += 1
        self._update_page_metrics()

    def _paged_demote_entry(self, entry) -> None:
        """LRU demotion to the host tier: gather the entry's page run
        (TRASH-padded to its bucket) to host RAM verbatim, release the
        device pages."""
        npg = -(-len(entry.tokens) // self.cfg.kv_page_tokens)
        bucket = self.cfg.page_bucket_for(npg)
        idx = jnp.asarray(entry.pages + [TRASH] * (bucket - npg), jnp.int32)
        k, v = self._gather_pages_fn(self._ck, self._cv, idx)
        self._pages.release_pages(entry.pages)
        entry.pages = None
        self._prefix_pool.evictions += 1
        self._prefix_pool.demoted_to_host(entry, kv_host(k), kv_host(v))
        self.metrics["prefix_cache_evictions"] = self._prefix_pool.evictions
        self._update_page_metrics()

    # -- warmup ----------------------------------------------------------

