"""Pallas decode-attention kernel numerics vs the XLA reference path
(interpret mode on CPU; the real TPU path compiles the same kernel)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omnia_tpu.ops.attention import gqa_attention
from omnia_tpu.ops.decode_attention import (
    decode_gqa_attention,
    decode_gqa_attention_paged,
    flat_rows,
)


def _setup(B=4, S=512, H=8, Hkv=2, D=128, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), dtype=dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype=dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype=dtype)
    return q, k, v


L = 3  # layers of the whole caches the kernel is handed
LAYERS = pytest.mark.parametrize("layer", [0, L - 1])
# KV heads of 128 lanes: the float32 caches of these cases take the block
# [rows · Hkv, D] under 8 heads (``flat_rows``) and [rows, Hkv, D] at 8.
KV_HEADS = pytest.mark.parametrize("kv_heads", [1, 2, 4, 8])


def _whole(x, layer):
    """x as layer ``layer`` of an L-layer cache whose every other layer
    is poison (NaN, or ±127 for int8 rows): the kernel takes the whole
    cache and may address only the layer it is told."""
    x = np.asarray(x)
    poison = 127 if x.dtype == np.int8 else np.nan
    out = np.full((L, *x.shape), poison, x.dtype)
    out[layer] = x
    return jnp.asarray(out)


def _kernel(q, k, v, pos, layer=1, k_scale=None, v_scale=None, **kw):
    """decode_gqa_attention on per-layer operands placed at ``layer``."""
    if k_scale is not None:
        kw.update(k_scale=_whole(k_scale, layer), v_scale=_whole(v_scale, layer))
    return decode_gqa_attention(
        q, _whole(k, layer), _whole(v, layer), pos, layer, interpret=True, **kw
    )


def _kernel_paged(q, pool_k, pool_v, table, pos, layer=1, k_scale=None,
                  v_scale=None, **kw):
    if k_scale is not None:
        kw.update(k_scale=_whole(k_scale, layer), v_scale=_whole(v_scale, layer))
    return decode_gqa_attention_paged(
        q, _whole(pool_k, layer), _whole(pool_v, layer), table, pos, layer,
        interpret=True, **kw
    )


def _paginate(k, v, page_s, free_pages=3, seed=7):
    """Scatter contiguous caches into a scrambled page pool + table
    (the first `free_pages` pool pages stay unreferenced — 'free')."""
    B, S, Hkv, D = k.shape
    npg = S // page_s
    perm = np.random.RandomState(seed).permutation(B * npg)
    pool_k = np.zeros((B * npg + free_pages, page_s, Hkv, D), np.asarray(k).dtype)
    pool_v = np.zeros_like(pool_k)
    table = np.zeros((B, npg), np.int32)
    for b in range(B):
        for j in range(npg):
            pid = int(perm[b * npg + j]) + free_pages
            pool_k[pid] = np.asarray(k[b, j * page_s:(j + 1) * page_s])
            pool_v[pid] = np.asarray(v[b, j * page_s:(j + 1) * page_s])
            table[b, j] = pid
    return jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(table)


class TestDecodeAttention:
    @LAYERS
    @pytest.mark.parametrize("positions", [[0, 5, 255, 511], [37, 499, 256, 128]])
    def test_matches_xla_reference(self, positions, layer):
        """The kernel on layer ``layer`` of a 5-D cache equals the einsum
        on that layer's slice; every other layer is NaN and changes
        nothing."""
        q, k, v = _setup()
        pos = jnp.asarray(positions, dtype=jnp.int32)
        ref = gqa_attention(q, k, v, pos[:, None])[:, 0]
        out = _kernel(q[:, 0], k, v, pos, layer, block_s=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @KV_HEADS
    def test_rows_past_position_do_not_influence(self, kv_heads):
        """Poison cache rows beyond each position with huge values — the
        kernel must produce identical output (those blocks are skipped)."""
        q, k, v = _setup(B=2, S=256, H=2 * kv_heads, Hkv=kv_heads, D=128)
        assert flat_rows(kv_heads, 128, k.dtype, 64) == (kv_heads < 8)
        pos = jnp.asarray([63, 190], dtype=jnp.int32)
        out_clean = _kernel(q[:, 0], k, v, pos, block_s=64)
        k_poison, v_poison = np.asarray(k).copy(), np.asarray(v).copy()
        for b, p in enumerate([63, 190]):
            k_poison[b, p + 1:] = 1e9
            v_poison[b, p + 1:] = -1e9
        out_poison = _kernel(q[:, 0], k_poison, v_poison, pos, block_s=64)
        np.testing.assert_allclose(np.asarray(out_clean), np.asarray(out_poison))

    @pytest.mark.parametrize("kv_heads", [1, 2, 4, 8, 16])
    def test_bf16_inputs(self, kv_heads):
        """A bfloat16 tile has 16 sublanes: up to 8 heads (the served 4 and 8
        among them) the block is [rows · Hkv, D] and a word of two heads is
        taken apart by a shift and a mask, which is the cast to float32."""
        q, k, v = _setup(B=2, S=256, H=2 * kv_heads, Hkv=kv_heads, D=128, dtype=jnp.bfloat16)
        assert flat_rows(kv_heads, 128, k.dtype, 128) == (kv_heads < 16)
        pos = jnp.asarray([100, 200], dtype=jnp.int32)
        ref = gqa_attention(q, k, v, pos[:, None])[:, 0]
        out = _kernel(q[:, 0], k, v, pos, block_s=128)
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
            atol=3e-2, rtol=3e-2,
        )

    def test_indivisible_cache_rejected(self):
        q, k, v = _setup(B=1, S=100, H=2, Hkv=1, D=128)
        with pytest.raises(ValueError, match="divisible"):
            _kernel(q[:, 0], k, v, jnp.zeros((1,), jnp.int32), block_s=64)

    @LAYERS
    @pytest.mark.parametrize("positions", [[0, 5, 255, 511], [37, 499, 256, 128]])
    def test_quantized_matches_dequantized_reference(self, positions, layer):
        """int8-KV edition (models/kv_quant.py): the kernel streaming
        int8 rows + scale blocks must equal the XLA reference over the
        DEQUANTIZED cache to float epsilon — the scale application in
        VMEM is exact algebra, not an approximation."""
        from omnia_tpu.models import kv_quant as kvq

        q, k, v = _setup()
        pos = jnp.asarray(positions, dtype=jnp.int32)
        qk, qv = kvq.quantize_rows(k), kvq.quantize_rows(v)
        ref = gqa_attention(
            q, kvq.dequantize_rows(qk), kvq.dequantize_rows(qv), pos[:, None]
        )[:, 0]
        out = _kernel(
            q[:, 0], qk.q, qv.q, pos, layer, k_scale=qk.s, v_scale=qv.s,
            block_s=128,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @KV_HEADS
    def test_quantized_rows_past_position_do_not_influence(self, kv_heads):
        """Scale blocks ride the same clamped index map as the KV
        blocks: poisoned rows AND poisoned scales beyond each position
        must not change the output. (int8: four heads a word, so one head
        and whole fours take the block [rows · Hkv, D]; two keep [rows, Hkv, D].)"""
        from omnia_tpu.models import kv_quant as kvq

        q, k, v = _setup(B=2, S=256, H=2 * kv_heads, Hkv=kv_heads, D=128)
        assert flat_rows(kv_heads, 128, jnp.int8, 64) == (kv_heads != 2)
        pos = jnp.asarray([63, 190], dtype=jnp.int32)
        qk, qv = kvq.quantize_rows(k), kvq.quantize_rows(v)
        clean = _kernel(
            q[:, 0], qk.q, qv.q, pos, k_scale=qk.s, v_scale=qv.s, block_s=64,
        )
        ks_p, vs_p = np.asarray(qk.s).copy(), np.asarray(qv.s).copy()
        kq_p, vq_p = np.asarray(qk.q).copy(), np.asarray(qv.q).copy()
        for b, p in enumerate([63, 190]):
            kq_p[b, p + 1:] = 127
            vq_p[b, p + 1:] = -127
            ks_p[b, p + 1:] = 1e9
            vs_p[b, p + 1:] = 1e9
        poisoned = _kernel(
            q[:, 0], kq_p, vq_p, pos, k_scale=ks_p, v_scale=vs_p, block_s=64,
        )
        np.testing.assert_allclose(np.asarray(clean), np.asarray(poisoned))

    def test_quantized_dispatch_from_gqa_attention(self, monkeypatch):
        """gqa_attention unpacks a QuantKV cache into the kernel's
        int8+scale operands (the engine's serving route on TPU)."""
        import omnia_tpu.ops.attention as attn
        from omnia_tpu.models import kv_quant as kvq

        q, k, v = _setup(B=2, S=256, H=4, Hkv=2, D=128)
        pos = jnp.asarray([10, 200], dtype=jnp.int32)
        qk, qv = kvq.quantize_rows(k), kvq.quantize_rows(v)
        monkeypatch.setenv("OMNIA_PALLAS_DECODE", "interpret")
        attn._pallas_decode_mode.cache_clear()
        try:
            out = attn.gqa_attention(q, qk, qv, pos[:, None])
            monkeypatch.setenv("OMNIA_PALLAS_DECODE", "0")
            attn._pallas_decode_mode.cache_clear()
            ref = attn.gqa_attention(q, qk, qv, pos[:, None])
            np.testing.assert_allclose(
                np.asarray(out[:, 0]), np.asarray(ref[:, 0]), atol=2e-5, rtol=2e-5
            )
        finally:
            attn._pallas_decode_mode.cache_clear()

    @LAYERS
    @pytest.mark.parametrize(
        "positions",
        [
            [0, 5, 255, 511],     # incl. single-page sequences (pos < 64)
            [37, 499, 256, 128],  # partial last pages + exact boundaries
            [63, 64, 127, 510],   # last row of a page / first of the next
        ],
    )
    def test_paged_matches_contiguous_kernel(self, positions, layer):
        """Paged edition vs the contiguous kernel at the SAME block size
        over a scrambled page pool: the table only reorders DMAs, so the
        outputs must be bit-identical — including partial last pages and
        single-page sequences (within-block iota masking)."""
        q, k, v = _setup()
        pos = jnp.asarray(positions, dtype=jnp.int32)
        ref = _kernel(q[:, 0], k, v, pos, L - 1 - layer, block_s=64)
        pool_k, pool_v, table = _paginate(k, v, page_s=64)
        out = _kernel_paged(q[:, 0], pool_k, pool_v, table, pos, layer)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_paged_free_and_dead_pages_never_contribute(self):
        """Poison every pool page the tables do not reference (the free
        list) AND the referenced rows past each position — output must
        not move: dead pages are simply never addressed, and rows past
        the position are masked/skipped like the contiguous kernel."""
        q, k, v = _setup(B=2, S=256, H=4, Hkv=2, D=128)
        pos = jnp.asarray([63, 190], dtype=jnp.int32)
        pool_k, pool_v, table = _paginate(k, v, page_s=64)
        clean = _kernel_paged(q[:, 0], pool_k, pool_v, table, pos)
        kp, vp = np.asarray(pool_k).copy(), np.asarray(pool_v).copy()
        referenced = set(np.asarray(table).ravel().tolist())
        for pid in range(kp.shape[0]):
            if pid not in referenced:
                kp[pid] = 1e9
                vp[pid] = -1e9
        for b, p in enumerate([63, 190]):
            for j in range(table.shape[1]):
                pid = int(table[b, j])
                lo = j * 64
                if lo > p:
                    kp[pid] = 1e9      # whole page past the position
                    vp[pid] = -1e9
                elif lo <= p < lo + 64:
                    kp[pid, p - lo + 1:] = 1e9  # partial-page tail
                    vp[pid, p - lo + 1:] = -1e9
        poisoned = _kernel_paged(q[:, 0], kp, vp, table, pos)
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))

    @LAYERS
    def test_paged_quantized_matches_dequantized_reference(self, layer):
        """int8 scale-block path: the paged kernel streaming int8 pool
        pages + scale pages through the table must equal the XLA
        reference over the dequantized contiguous cache."""
        from omnia_tpu.models import kv_quant as kvq

        q, k, v = _setup()
        pos = jnp.asarray([37, 499, 256, 128], dtype=jnp.int32)
        qk, qv = kvq.quantize_rows(k), kvq.quantize_rows(v)
        ref = gqa_attention(
            q, kvq.dequantize_rows(qk), kvq.dequantize_rows(qv), pos[:, None]
        )[:, 0]
        pool_kq, pool_vq, table = _paginate(qk.q, qv.q, page_s=64)
        pool_ks, pool_vs, _t = _paginate(
            qk.s[..., None], qv.s[..., None], page_s=64
        )
        out = _kernel_paged(
            q[:, 0], pool_kq, pool_vq, table, pos, layer,
            k_scale=pool_ks[..., 0], v_scale=pool_vs[..., 0],
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_paged_dispatch_from_gqa_attention(self, monkeypatch):
        """gqa_attention routes a PagedKV cache to the paged kernel when
        Pallas is on, and to the XLA take-fallback otherwise — equal
        numerics either way (the engine's serving routes)."""
        import omnia_tpu.ops.attention as attn
        from omnia_tpu.models.paged_kv import PagedKV

        q, k, v = _setup(B=2, S=256, H=4, Hkv=2, D=128)
        pos = jnp.asarray([10, 200], dtype=jnp.int32)
        pool_k, pool_v, table = _paginate(k, v, page_s=64)
        pk, pv = PagedKV(pool_k, table), PagedKV(pool_v, table)
        monkeypatch.setenv("OMNIA_PALLAS_DECODE", "interpret")
        attn._pallas_decode_mode.cache_clear()
        try:
            out = attn.gqa_attention(q, pk, pv, pos[:, None])
            monkeypatch.setenv("OMNIA_PALLAS_DECODE", "0")
            attn._pallas_decode_mode.cache_clear()
            fallback = attn.gqa_attention(q, pk, pv, pos[:, None])
            ref = attn.gqa_attention(q, k, v, pos[:, None])
            # The take-fallback materializes the same values the
            # contiguous cache holds — bit-identical.
            np.testing.assert_array_equal(
                np.asarray(fallback), np.asarray(ref)
            )
            np.testing.assert_allclose(
                np.asarray(out[:, 0]), np.asarray(ref[:, 0]),
                atol=2e-5, rtol=2e-5,
            )
        finally:
            attn._pallas_decode_mode.cache_clear()

    def test_dispatch_from_gqa_attention(self, monkeypatch):
        """gqa_attention routes T==1 to the kernel when enabled."""
        import omnia_tpu.ops.attention as attn

        q, k, v = _setup(B=2, S=256, H=4, Hkv=2, D=128)
        pos = jnp.asarray([10, 200], dtype=jnp.int32)
        monkeypatch.setenv("OMNIA_PALLAS_DECODE", "interpret")
        attn._pallas_decode_mode.cache_clear()
        try:
            out = attn.gqa_attention(q, k, v, pos[:, None])
            ref_disabled_env = attn.gqa_attention  # same fn, reference below
            monkeypatch.setenv("OMNIA_PALLAS_DECODE", "0")
            attn._pallas_decode_mode.cache_clear()
            ref = attn.gqa_attention(q, k, v, pos[:, None])
            np.testing.assert_allclose(
                np.asarray(out[:, 0]), np.asarray(ref[:, 0]), atol=2e-5, rtol=2e-5
            )
        finally:
            attn._pallas_decode_mode.cache_clear()


EDITIONS = pytest.mark.parametrize("edition", ["plain", "int8", "paged"])
_BLOCK = 256  # rows a block in the cases below (the page size when paged)


def _edition_run(edition, q, k, v, pos, live=None, dead=()):
    """The kernel of one edition over caches k, v [B, S, Hkv, D] at 256
    rows a block, with every cache row (and scale) of the slots in
    ``dead`` poisoned with NaN after the layout is made."""
    from omnia_tpu.models import kv_quant as kvq

    def poison(x, rows):
        x = np.asarray(x).copy()
        x[rows] = 127 if x.dtype == np.int8 else np.nan
        return x

    kw = {} if live is None else {"live": jnp.asarray(live)}
    dead = list(dead)
    if edition == "paged":
        pool_k, pool_v, table = _paginate(k, v, page_s=_BLOCK)
        pages = np.asarray(table)[dead].ravel()
        return _kernel_paged(q, poison(pool_k, pages), poison(pool_v, pages),
                             table, pos, **kw)
    if edition == "int8":
        qk, qv = kvq.quantize_rows(k), kvq.quantize_rows(v)
        return _kernel(
            q, poison(qk.q, dead), poison(qv.q, dead), pos,
            k_scale=poison(qk.s, dead), v_scale=poison(qv.s, dead),
            block_s=_BLOCK, **kw,
        )
    return _kernel(q, poison(k, dead), poison(v, dead), pos, block_s=_BLOCK,
                   **kw)


def _edition_ref(edition, q, k, v, pos):
    """The einsum path over the values the edition's cache holds."""
    from omnia_tpu.models import kv_quant as kvq

    if edition == "int8":
        k = kvq.dequantize_rows(kvq.quantize_rows(k))
        v = kvq.dequantize_rows(kvq.quantize_rows(v))
    return gqa_attention(q, k, v, pos[:, None])[:, 0]


class TestLiveSlotsOnly:
    """The kernel's work follows the live (slot, block) pairs: a live
    slot takes ``pos // block + 1`` grid steps, a dead one none — it
    reads nothing and its output row is zeros."""

    @EDITIONS
    @KV_HEADS
    def test_dead_slot_reads_nothing_and_returns_zeros(self, edition, kv_heads):
        q, k, v = _setup(B=4, S=512, H=2 * kv_heads, Hkv=kv_heads, D=128)
        pos = jnp.asarray([300, 0, 17, 511], jnp.int32)
        live = [1, 0, 1, 0]
        clean = _edition_run(edition, q[:, 0], k, v, pos)
        out = _edition_run(edition, q[:, 0], k, v, pos, live=live, dead=[1, 3])
        out = np.asarray(out)
        np.testing.assert_array_equal(out[[0, 2]], np.asarray(clean)[[0, 2]])
        np.testing.assert_array_equal(out[[1, 3]], 0.0)
        assert np.isfinite(out).all()

    @EDITIONS
    @pytest.mark.parametrize(
        "live", [[1, 1, 1, 1], [0, 0, 0, 0], [0, 0, 1, 0], [1, 0, 0, 1]],
        ids=["all-live", "all-dead", "one-live", "ends-live"],
    )
    def test_trip_count_edges(self, edition, live):
        """Positions on both sides of a block edge and at the cache's
        last row, under every pattern of live and dead neighbours: a
        slot's first block follows another slot's last, or nothing."""
        S = 512
        q, k, v = _setup(B=4, S=S, H=8, Hkv=4, D=128)
        pos = jnp.asarray([0, 255, 256, S - 1], jnp.int32)
        ref = np.array(_edition_ref(edition, q, k, v, pos))
        ref[~np.asarray(live, bool)] = 0.0
        dead = [b for b, on in enumerate(live) if not on]
        out = _edition_run(edition, q[:, 0], k, v, pos, live=live, dead=dead)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)

    @EDITIONS
    def test_live_none_is_every_slot_live(self, edition):
        q, k, v = _setup(B=3, S=512, H=4, Hkv=2, D=128, seed=5)
        pos = jnp.asarray([5, 400, 256], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(_edition_run(edition, q[:, 0], k, v, pos)),
            np.asarray(_edition_run(edition, q[:, 0], k, v, pos, live=[1, 1, 1])),
        )

    @pytest.mark.parametrize("mask", [[True, False], [1, 0]], ids=["bool", "int"])
    def test_gqa_attention_hands_the_mask_to_the_kernel(self, route, mask):
        """``gqa_attention(..., live=)`` on the kernel route; the einsum
        route takes the argument and ignores it."""
        q, k, v = _setup(B=2, S=256, H=4, Hkv=2, D=128)
        pos = jnp.asarray([[10], [200]], jnp.int32)
        live = jnp.asarray(mask)
        route("0")
        ref = np.asarray(gqa_attention(q, k, v, pos, live=live))
        np.testing.assert_array_equal(ref, np.asarray(gqa_attention(q, k, v, pos)))
        route("interpret")
        out = np.asarray(gqa_attention(q, k, v, pos, live=live))
        np.testing.assert_allclose(out[0], ref[0], atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(out[1], 0.0)


# ---------------------------------------------------------------------------
# The kernel under a device mesh (shard_map over "dp" × "tp"), and the
# layouts it refuses — ops/attention.py::_decode_path / check_decode_kernel
# ---------------------------------------------------------------------------


@pytest.fixture
def route(monkeypatch):
    """Set the decode-kernel route for the rest of the test."""
    import omnia_tpu.ops.attention as attn

    def set_route(mode):
        monkeypatch.setenv("OMNIA_PALLAS_DECODE", mode)
        attn._pallas_decode_mode.cache_clear()

    yield set_route
    attn._pallas_decode_mode.cache_clear()  # monkeypatch restores the env


class TestKernelUnderMesh:
    @pytest.mark.parametrize("layer,D", [(0, 64), (L - 1, 64), (L - 1, 128)])
    @pytest.mark.parametrize(
        "dp,tp,layout",
        [(dp, tp, layout)
         for dp, tp in [(1, 2), (2, 2), (1, 4)]
         for layout in ["plain", "int8", "paged"]
         if not (layout == "paged" and dp > 1)],  # refused: TestKernelRefusals
    )
    def test_sharded_kernel_matches_einsum(self, devices8, route, dp, tp,
                                           layout, layer, D):
        """Whole caches sharded the way the engine shards them (layers
        whole, slots over dp, KV heads over tp): the shard_mapped kernel
        on layer ``layer`` must give what the GSPMD-partitioned einsum
        path gives on that layer's slice (the other layers are NaN). At
        128 lanes a device's own two heads or one take the block [rows ·
        Hkv, D] inside the shard_map (int8 at two heads keeps [rows, Hkv, D])."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from omnia_tpu.models import kv_quant as kvq
        from omnia_tpu.models.paged_kv import PagedKV
        from omnia_tpu.parallel import make_mesh

        mesh = make_mesh(dp, tp, devices=devices8)
        q, k, v = _setup(B=4, S=512, H=8, Hkv=4, D=D)
        pos = jnp.asarray([3, 255, 256, 510], dtype=jnp.int32)

        def put(x, *spec):
            return jax.device_put(x, NamedSharding(mesh, P(*spec)))

        def put_whole(x, *spec):
            return put(_whole(x, layer), None, *spec)

        q = put(q, "dp", None, "tp", None)
        if layout == "paged":
            pool_k, pool_v, table = _paginate(k, v, page_s=64)
            kc = PagedKV(put_whole(pool_k, None, None, "tp", None), put(table))
            vc = PagedKV(put_whole(pool_v, None, None, "tp", None), put(table))
        elif layout == "int8":
            def quant(x):
                c = kvq.quantize_rows(x)
                return kvq.QuantKV(put_whole(c.q, "dp", None, "tp", None),
                                   put_whole(c.s, "dp", None, "tp"))
            kc, vc = quant(k), quant(v)
        else:
            kc, vc = (put_whole(x, "dp", None, "tp", None) for x in (k, v))

        def run(mode):
            route(mode)
            return jax.jit(
                lambda q, kc, vc, p: gqa_attention(q, kc, vc, p[:, None],
                                                   mesh=mesh, layer=layer)
            )(q, kc, vc, pos)

        out, ref = run("interpret"), run("0")
        assert out.sharding.spec == P("dp", None, "tp", None) or tp == 1
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize("D", [64, 128])
    @pytest.mark.parametrize(
        "dp,tp,layout",
        [(2, 2, "plain"), (2, 2, "int8"), (1, 2, "paged")],
    )
    def test_live_mask_is_sliced_with_the_slots(self, devices8, route, dp, tp,
                                                layout, D):
        """The mask goes into the shard_map sliced over "dp" like the
        positions: each shard skips its own dead slots (their rows and
        scales are NaN) and gives the einsum's answer for its live ones."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from omnia_tpu.models import kv_quant as kvq
        from omnia_tpu.models.paged_kv import PagedKV
        from omnia_tpu.parallel import make_mesh

        mesh = make_mesh(dp, tp, devices=devices8)
        q, k, v = _setup(B=4, S=512, H=8, Hkv=4, D=D)
        pos = jnp.asarray([3, 255, 256, 510], dtype=jnp.int32)
        live = np.asarray([True, False, False, True])  # one a dp shard
        route("0")
        ref = np.asarray(gqa_attention(q, k, v, pos[:, None]))

        def put(x, *spec):
            return jax.device_put(x, NamedSharding(mesh, P(*spec)))

        def dead_rows(x):
            x = np.asarray(x).copy()
            x[~live] = 127 if x.dtype == np.int8 else np.nan
            return x

        if layout == "paged":
            pool_k, pool_v, table = _paginate(k, v, page_s=64)
            dead_pages = np.asarray(table)[~live].ravel()
            caches = []
            for pool in (pool_k, pool_v):
                pool = np.asarray(pool).copy()
                pool[dead_pages] = np.nan
                caches.append(PagedKV(put(pool, None, None, "tp", None),
                                      put(table)))
            kc, vc = caches
        elif layout == "int8":
            def quant(x):
                c = kvq.quantize_rows(x)
                return kvq.QuantKV(put(dead_rows(c.q), "dp", None, "tp", None),
                                   put(dead_rows(c.s), "dp", None, "tp"))
            kc, vc = quant(k), quant(v)
            ref = np.asarray(gqa_attention(
                q, kvq.dequantize_rows(kvq.quantize_rows(k)),
                kvq.dequantize_rows(kvq.quantize_rows(v)), pos[:, None],
            ))
        else:
            kc, vc = (put(dead_rows(x), "dp", None, "tp", None) for x in (k, v))

        route("interpret")
        out = np.asarray(jax.jit(
            lambda q, kc, vc, p, live: gqa_attention(
                q, kc, vc, p[:, None], mesh=mesh, live=live)
        )(put(q, "dp", None, "tp", None), kc, vc, pos, put(live, "dp")))
        np.testing.assert_allclose(out[live], ref[live], atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(out[~live], 0.0)

    def test_single_slot_view_is_replicated_over_dp(self, devices8, route):
        """extend runs T=1 on one slot's view: B=1 does not divide over
        dp=2, so the slot axis stays whole while heads still shard."""
        from omnia_tpu.parallel import make_mesh

        mesh = make_mesh(2, 2, devices=devices8)
        q, k, v = _setup(B=1, S=256, H=4, Hkv=2, D=64)
        pos = jnp.asarray([[100]], dtype=jnp.int32)
        route("interpret")
        out = jax.jit(lambda *a: gqa_attention(*a, mesh=mesh))(q, k, v, pos)
        route("0")
        ref = gqa_attention(q, k, v, pos)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


class TestKernelRefusals:
    """With the kernel routed on, a layout it cannot serve is an error —
    at engine construction — never a quiet switch to the einsum path."""

    @pytest.mark.parametrize(
        "kwargs,exc,match",
        [
            (dict(cache_len=300, num_kv_heads=8, paged=False),
             ValueError, "multiple"),
            (dict(cache_len=1024, num_kv_heads=2, paged=False, tp=4),
             ValueError, "KV heads"),
            (dict(cache_len=1024, num_kv_heads=8, paged=True, dp=2),
             NotImplementedError, "page pool"),
        ],
        ids=["cache-not-multiple-of-block", "heads-not-divisible", "paged-dp"],
    )
    def test_refusals(self, route, kwargs, exc, match):
        from omnia_tpu.ops.attention import check_decode_kernel

        route("interpret")
        with pytest.raises(exc, match=match):
            check_decode_kernel(**kwargs)
        route("0")  # routed off: the einsum path takes any layout
        check_decode_kernel(**kwargs)

    def test_short_and_block_multiple_caches_pass(self, route):
        from omnia_tpu.ops.attention import check_decode_kernel

        route("interpret")
        for cache_len in (64, 200, 256, 1024):  # ≤ one block, or multiples
            check_decode_kernel(cache_len, 8, paged=False, tp=4)

    def test_engine_refuses_at_construction(self, route):
        from omnia_tpu.engine import EngineConfig, InferenceEngine
        from omnia_tpu.models import get_config

        cfg = get_config("test-tiny", max_seq_len=512)
        ecfg = EngineConfig(num_slots=2, max_seq=300, prefill_buckets=(16,),
                            dtype="float32")
        route("interpret")
        with pytest.raises(ValueError, match="cache length 300"):
            InferenceEngine(cfg, ecfg)
        route("0")
        InferenceEngine(cfg, ecfg)

    def test_trace_time_recheck_raises_not_falls_back(self, route):
        """gqa_attention called directly with a cache the block does not
        divide: an error, where it used to return the einsum result."""
        q, k, v = _setup(B=2, S=300, H=4, Hkv=2, D=64)
        pos = jnp.asarray([[10], [200]], dtype=jnp.int32)
        route("interpret")
        with pytest.raises(ValueError, match="cache length 300"):
            gqa_attention(q, k, v, pos)


class TestWhichBlockACallTakes:
    """``flat_rows``: the one rule for the shape of a grid step's K and V
    block, from KV heads (a device's own), head width, the cache's type and
    the rows a block."""

    @pytest.mark.parametrize("kv_heads,head_dim,dtype,block_s,flat", [
        (4, 128, jnp.bfloat16, 256, True),     # mellum2-12b-a2p5b
        (8, 128, jnp.bfloat16, 256, True),     # mistral-7b, k-exaone-236b-a23b
        (8, 128, jnp.bfloat16, 128, True),     # k-exaone's ring of 128 rows
        (2, 128, jnp.bfloat16, 256, True),     # mistral-7b at tp=4
        (1, 128, jnp.bfloat16, 256, True),
        (32, 128, jnp.bfloat16, 256, False),   # olmo-hybrid-7b: no sublane empty
        (16, 128, jnp.bfloat16, 256, False),
        (3, 128, jnp.bfloat16, 256, False),    # no whole words of two heads
        (8, 64, jnp.bfloat16, 256, False),     # llama3-1b: heads of half a tile
        (2, 16, jnp.float32, 256, False),      # the tiny presets
        (4, 128, jnp.float32, 256, True),      # the long checks in float32
        (8, 128, jnp.float32, 256, False),     # eight float32 heads fill a tile
        (4, 128, jnp.int8, 64, True),
        (8, 128, jnp.int8, 64, True),
        (16, 128, jnp.int8, 64, True),
        (2, 128, jnp.int8, 64, False),         # half a word of four heads
        (32, 128, jnp.int8, 64, False),
        (4, 128, jnp.float16, 256, False),     # no rule for taking its words apart
        (1, 128, jnp.bfloat16, 8, False),      # a block of half a tile of rows
        (4, 128, jnp.bfloat16, 4, True),       # four rows of four heads: one tile
    ])
    def test_the_predicate(self, kv_heads, head_dim, dtype, block_s, flat):
        assert flat_rows(kv_heads, head_dim, dtype, block_s) is flat
        assert flat_rows(kv_heads, head_dim, jnp.dtype(dtype), block_s) is flat

    @pytest.mark.parametrize("preset,engine,want", [
        ("llama3-8b", dict(), "full=2048x128"),              # 8 heads: 256 rows x 8
        ("llama3-8b", dict(tp=4), "full=512x128"),           # a device's own two
        ("llama3-8b", dict(kv_quant="int8"), "full=2048x128"),
        ("llama3-8b", dict(kv_pages=64, kv_page_tokens=64), "full=512x128"),
        ("llama3-1b", dict(), "full=256x8x64"),              # heads of 64 lanes
        ("test-tiny-window", dict(), "full=256x2x16,window=8x2x16"),
    ])
    def test_the_engines_start_up_line_names_the_block(self, route, preset, engine, want):
        """``engine/family.py::decode_blocks``, what "engine built:" logs
        beside the kernels' route: rows · heads x lanes where the heads lie
        among the rows, rows x heads x lanes where they keep an axis, a kind
        of cache; nothing with the kernels off."""
        from omnia_tpu.engine.family import decode_blocks
        from omnia_tpu.engine.types import EngineConfig
        from omnia_tpu.models import get_config

        cfg = get_config(preset)
        ecfg = EngineConfig(num_slots=4, max_seq=2048, **engine)
        route("interpret")
        assert decode_blocks(cfg, ecfg, jnp.bfloat16) == want
        route("0")
        assert decode_blocks(cfg, ecfg, jnp.bfloat16) == ""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
    @KV_HEADS
    def test_both_blocks_give_the_same_numbers(self, monkeypatch, kv_heads, dtype):
        """The same call with the rule switched off takes the block [rows,
        Hkv, D]: the same float32 products and sums a head, so the two agree
        to float32 rounding whatever the cache's type."""
        import omnia_tpu.ops.decode_attention as dk
        from omnia_tpu.models import kv_quant as kvq

        q, k, v = _setup(B=3, S=256, H=2 * kv_heads, Hkv=kv_heads, D=128,
                         dtype=jnp.float32 if dtype == jnp.int8 else dtype)
        pos = jnp.asarray([0, 70, 255], jnp.int32)
        kw = {}
        if dtype == jnp.int8:
            qk, qv = kvq.quantize_rows(k), kvq.quantize_rows(v)
            k, v, kw = qk.q, qv.q, dict(k_scale=qk.s, v_scale=qv.s)

        def run():
            jax.clear_caches()  # the rule is read while tracing
            return np.asarray(_kernel(q[:, 0], k, v, pos, block_s=64, **kw), np.float32)

        got = run()
        monkeypatch.setattr(dk, "flat_rows", lambda *a: False)
        want = run()
        np.testing.assert_allclose(got, want, atol=2e-6 if dtype != jnp.bfloat16 else 8e-3,
                                   rtol=1e-5)
