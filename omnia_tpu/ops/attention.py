"""Grouped-query attention over a slot-contiguous KV cache.

Design notes (TPU-first):

- One attention routine serves both prefill and decode. The KV cache is laid
  out slot-contiguously: cache row ``s`` holds the key/value for absolute
  position ``s`` of that sequence, so the causal mask is simply
  ``key_index <= query_position``. Unified masking means one compiled kernel
  shape per (batch, q_len) bucket instead of separate mask plumbing.
- Softmax and the score matmul accumulate in float32; inputs stay bf16 so both
  matmuls hit the MXU.
- GQA is expressed by reshaping Q to [B, T, Hkv, G, D] and batching the
  einsums over the KV-head axis — no materialized KV repeat (which would
  multiply HBM traffic by the group size).
- A chunk of more than one query a slot (prefill, extend) runs through the
  blocked Pallas kernel of ops/prefill_attention.py where ``prefill_kernel_on``
  says so: a running softmax over tiles in VMEM, no ``[H, T, S]`` scores in
  HBM, no key block past the queries' positions. The einsums below keep the
  CPU, int8 and paged caches, a mesh, chunks that are no whole 128-row tiles
  (speculation's verify steps) and training, and are the kernel's oracle. A
  window layer's chunk (``window_attention``) takes the same kernel with a
  lower bound on the key blocks where ``window_kernel_on`` says so, and the
  einsum band (``band_attention``) else.
- Head axes are sharded over the "tp" mesh axis by the caller (weights carry
  the sharding; XLA propagates it through the einsum path with no collectives
  inside attention). The Pallas decode kernel cannot be partitioned by XLA, so
  under a mesh it runs inside a ``shard_map`` over slots ("dp") and heads
  ("tp"): each device runs the kernel on the KV heads it already holds.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from omnia_tpu.models.kv_quant import is_quant_kv, kv_map
from omnia_tpu.models.paged_kv import PagedKV, gather_view, is_paged

_NEG_INF = -1e30

# Decode (T==1) steps can route to the length-aware Pallas kernel
# (ops/decode_attention.py) whose HBM traffic is proportional to actual
# context length instead of cache capacity. OMNIA_PALLAS_DECODE:
#   auto (default) = on when running on TPU; 1 = force; 0 = off;
#   interpret = Pallas interpreter (tests on CPU).
_DECODE_BLOCK_S = 256


@functools.lru_cache(maxsize=1)
def _pallas_decode_mode() -> str:
    mode = os.environ.get("OMNIA_PALLAS_DECODE", "auto").lower()
    if mode == "auto":
        return "1" if jax.default_backend() == "tpu" else "0"
    return mode


def pallas_decode_mode() -> str:
    """Resolved decode-kernel routing ("1"/"0"/"interpret") — surfaced by
    the engine log and the smoke so the route a run took is visible."""
    return _pallas_decode_mode()


def _kernel_on() -> bool:
    return _pallas_decode_mode() in ("1", "interpret")


def decode_block_rows(cache_len: int, page_tokens: int = 0) -> int:
    """Cache rows in one block of the decode kernel: a page of a paged
    pool, else ``_DECODE_BLOCK_S`` (the whole cache when it is shorter).
    A live slot at position ``p`` costs the kernel ``p // rows + 1``
    blocks; a dead slot none."""
    return page_tokens or min(_DECODE_BLOCK_S, cache_len)


def check_decode_kernel(cache_len: int, num_kv_heads: int, paged: bool,
                        dp: int = 1, tp: int = 1) -> None:
    """Raise when the decode kernel is routed on and cannot serve this
    layout. The engine calls it at construction, so a shape the kernel
    refuses is an error there and never a quiet switch to the einsum
    path mid-serving; ``_decode_path`` re-checks at trace time."""
    if not _kernel_on():
        return
    if not paged and cache_len % decode_block_rows(cache_len):
        raise ValueError(
            f"decode kernel: cache length {cache_len} is not a multiple of "
            f"its {_DECODE_BLOCK_S}-row block; size max_seq to a multiple"
        )
    if num_kv_heads % tp:
        raise ValueError(
            f"decode kernel: {num_kv_heads} KV heads do not divide over "
            f"tp={tp}; each device must hold whole KV heads"
        )
    if paged and dp > 1:
        raise NotImplementedError(
            "decode kernel: a page pool sharded over dp holds global page "
            "ids, so one slot's pages span devices; use dp=1 with kv_pages"
        )


def prefill_kernel_on(T: int, S: int, width: int, plain: bool = True, mesh=None) -> bool:
    """Whether a chunk of T queries (T > 1) over S rows of keys takes the
    blocked Pallas kernel (ops/prefill_attention.py) and not the einsums,
    from what the call can see: the switch the decode kernels have, T and S
    whole numbers of 128-row tiles, heads ``width`` lanes wide in whole
    128s, the rows plain arrays (``plain``: neither QuantKV nor paged) and
    no mesh to shard them. The engine counts ``prefill_tokens_blocked`` by
    it, over what its programs hand the model (engine/family.py::
    prefill_blocked; the width: ``ModelConfig.attn_value_width``)."""
    from omnia_tpu.ops.prefill_attention import QUERY_TILE

    return (_kernel_on() and plain and mesh is None and T > 1
            and T % QUERY_TILE == 0 and S % QUERY_TILE == 0 and width % 128 == 0)


# A window layer's chunk keeps the einsum band (``band_attention``) while a
# slot's float32 band scores ``[H, T, Q + window]`` are at most this many
# bytes: up to 96 MiB they never leave the chip's fast memory and the band
# costs 0.03–0.15 ms a layer, half to a third of the blocked kernel, whose
# tiles execute T × (tq + window) products for the band's T × window; from
# 128 MiB they go through HBM three times, 0.6–1.3 ms against the kernel's
# 0.2–0.45 (PERF.md section 6, PR 48: 13 shapes, windows of 128 to 1,024).
_BAND_SCORES_MOST = 96 << 20


def _band_queries(T: int, window: int) -> int:
    """Rows of a block of ``band_attention``'s queries: the window, or all T
    where the window does not divide them."""
    return window if T % window == 0 else T


def window_kernel_on(T: int, window: int, heads: int, width: int, fresh: bool,
                     mesh=None) -> bool:
    """Whether a window layer's chunk of T queries a slot takes the blocked
    kernel (``window_attention``) and not the einsum band, from what the
    call can see: ``prefill_kernel_on`` over the rows of keys the call hands
    it (a ``fresh`` chunk its own T, a piece ``[the window rows before it |
    its own]``), and then either a fresh chunk no longer than the window (the
    window never binds: the causal call a full layer makes, faster than the
    band at every size) or band scores of more than ``_BAND_SCORES_MOST``
    bytes. The engine asks it for a model with rings as it asks
    ``prefill_kernel_on`` for the full layers (engine/family.py::
    prefill_blocked)."""
    if not prefill_kernel_on(T, T if fresh else window + T, width, True, mesh):
        return False
    return (fresh and T <= window) or (
        heads * T * (_band_queries(T, window) + window) * 4 > _BAND_SCORES_MOST)


def _map_rows(fn, cache):
    """``fn`` over the row arrays of a cache of any layout (plain,
    QuantKV, or a PagedKV's pool; the page table passes through)."""
    if is_paged(cache):
        return PagedKV(kv_map(fn, cache.pool), cache.table)
    return kv_map(fn, cache)


def _decode_path(q, k_cache, v_cache, q_positions, mesh, layer, live):
    """The Pallas decode kernel over layer ``layer`` of the whole cache,
    or None when it is routed off. Under a mesh the call is wrapped in a
    shard_map: the layer axis unsharded, slots over "dp" (when they
    divide; a single-slot view is replicated), heads over "tp",
    positions, the live mask and the page table sliced with the slots."""
    if not _kernel_on():
        return None
    from omnia_tpu.ops import decode_attention as dk

    if layer is None:
        # A per-layer cache is a whole cache of one layer (a reshape).
        k_cache, v_cache = (_map_rows(lambda a: a[None], c)
                            for c in (k_cache, v_cache))
        layer = 0
    interpret = _pallas_decode_mode() == "interpret"
    paged = is_paged(k_cache)
    B, (S, Hkv) = q.shape[0], k_cache.shape[-3:-1]
    if live is None:
        live = jnp.ones((B,), jnp.int32)
    dp, tp = (mesh.shape["dp"], mesh.shape["tp"]) if mesh is not None else (1, 1)
    check_decode_kernel(S, Hkv, paged, dp, tp)
    b = "dp" if B % dp == 0 else None
    if paged:
        # The kernel gathers K/V blocks through the scalar-prefetched
        # page table — one block per page, the online-softmax body
        # unchanged. Pages are not sliced over "dp" (check above).
        kernel = functools.partial(dk.decode_gqa_attention_paged,
                                   interpret=interpret)
        k, v = k_cache.pool, v_cache.pool
        mid, mid_specs = (k_cache.table,), (P(b, None),)
        kv_spec = P(None, None, None, "tp", None)
    else:
        kernel = functools.partial(
            dk.decode_gqa_attention, interpret=interpret,
            block_s=decode_block_rows(S),
        )
        k, v = k_cache, v_cache
        mid, mid_specs = (), ()
        kv_spec = P(None, b, None, "tp", None)
    scales, scale_specs = (), ()
    if is_quant_kv(k):
        # int8 KV: the kernel streams the int8 rows + scale rows and
        # applies the scales in VMEM (half the HBM KV traffic).
        scales, scale_specs = (k.s, v.s), (P(*kv_spec[:-1]),) * 2
        k, v = k.q, v.q
    def run(live, *operands):  # the scales are optional and positional
        return kernel(*operands, live=live)

    operands = (live.astype(jnp.int32), q[:, 0], k, v, *mid, q_positions[:, 0],
                jnp.asarray(layer, jnp.int32), *scales)
    if mesh is not None:
        from omnia_tpu.parallel.compat import shard_map

        head_spec = P(b, "tp", None)
        run = shard_map(
            run, mesh,
            in_specs=(P(b), head_spec, kv_spec, kv_spec, *mid_specs, P(b), P(),
                      *scale_specs),
            out_specs=head_spec,
        )
    return run(*operands)[:, None]


def gqa_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    q_positions: jnp.ndarray,
    mesh=None,
    layer=None,
    live=None,
) -> jnp.ndarray:
    """Attention of queries against a slot-contiguous KV cache.

    q: [B, T, H, D] (already rotary-embedded)
    k_cache, v_cache: [B, S, Hkv, D] (position s stored at row s), either
        plain arrays or QuantKV (int8 rows + [B, S, Hkv] f32 scales —
        EngineConfig.kv_quant). Dequantization is FUSED: the score
        matmul runs against the int8 rows and the per-row scale
        multiplies the score/prob matrices — the cache is never
        upcast wholesale.
    q_positions: int [B, T] absolute position of each query token.
    mesh: the engine's device mesh when operands are sharded over it
        (only the decode kernel needs it; the einsum path is GSPMD's).
    layer: when given, k_cache/v_cache are the WHOLE caches
        [L, B, S, Hkv, D] (or the whole paged pool) and attention runs
        over layer ``layer`` of them. The decode kernel indexes the layer
        in its block index map, so no layer is sliced out in front of
        it; the einsum path reads that one layer.
    live: bool/int [B], or None for "every slot live". The decode kernel
        reads nothing for a slot marked dead and returns zeros for it;
        the einsum path (and any T > 1) ignores it — a dead slot's
        output is discarded by whoever marked it dead.
    Returns [B, T, H, D]. T == 1 goes to the decode kernel, T > 1 to the
    blocked prefill kernel, each where it is routed on and serves the call
    (``_decode_path``, ``prefill_kernel_on``); ``einsum_attention`` else.
    """
    B, T, H, D = q.shape

    if T == 1:
        fused = _decode_path(q, k_cache, v_cache, q_positions, mesh, layer,
                             live)
        if fused is not None:
            return fused

    plain = not (is_paged(k_cache) or is_quant_kv(k_cache))
    if prefill_kernel_on(T, k_cache.shape[-3], D, plain, mesh):
        from omnia_tpu.ops.prefill_attention import prefill_attention

        # The heads side by side on the minor axis (a reshape): a KV head's
        # columns are one block of it, in the chunk or in the whole cache.
        Hkv = k_cache.shape[-2]
        return prefill_attention(
            q.reshape(B, T, H * D), k_cache.reshape(*k_cache.shape[:-2], Hkv * D),
            v_cache.reshape(*v_cache.shape[:-2], Hkv * D), q_positions, layer,
            kv_heads=Hkv, scale=D**-0.5, interpret=_pallas_decode_mode() == "interpret",
        ).reshape(B, T, H, D)
    return einsum_attention(q, k_cache, v_cache, q_positions, layer)


def einsum_attention(q, k_cache, v_cache, q_positions, layer=None):
    """``gqa_attention`` by einsums over all S rows, the masked scores ``[H,
    T, S]`` in float32: every layout of the cache, every T, any backend, and
    differentiable (models/llama.py::forward_train names it as its
    ``attn_fn``: a Pallas call has no VJP)."""
    B, T, H, D = q.shape
    if layer is not None:
        # One read of the layer; nothing is written back.
        def take(arr):
            return jax.lax.dynamic_index_in_dim(arr, layer, 0, keepdims=False)

        k_cache, v_cache = _map_rows(take, k_cache), _map_rows(take, v_cache)

    if is_paged(k_cache):
        # XLA `take` fallback (prefill/extend/verify, and decode off
        # TPU): materialize the per-slot view once and run the EXACT
        # contiguous math below — same shapes, same contraction order,
        # so paged serving is bit-identical to contiguous on this path.
        # Rows reached through trash-page table entries are garbage, but
        # they sit at positions past every slot's written prefix, where
        # the causal mask already zeroes them exactly.
        k_cache = gather_view(k_cache)
        v_cache = gather_view(v_cache)

    S = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    G = H // Hkv

    qg = q.reshape(B, T, Hkv, G, D)
    # scores [B, Hkv, G, T, S]
    if is_quant_kv(k_cache):
        # q·k as a MIXED float × int8 dot (the quant.qdot idiom): the
        # int8 rows are a DIRECT dot operand, so no dequantized copy of
        # the cache is ever expressed in the HLO, and the per-(row,
        # head) scale factors out of the head-dim contraction onto the
        # score matrix.
        scores = jax.lax.dot_general(
            jnp.moveaxis(qg, 2, 1),            # [B, Hkv, T, G, D]
            jnp.swapaxes(k_cache.q, 1, 2),     # [B, Hkv, S, D] int8
            (((4,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        )                                      # [B, Hkv, T, G, S]
        scores = jnp.swapaxes(scores, 2, 3)
        scores = scores * jnp.transpose(k_cache.s, (0, 2, 1))[:, :, None, None, :]
    else:
        scores = jnp.einsum(
            "bthgd,bshd->bhgts", qg, k_cache, preferred_element_type=jnp.float32
        )
    scores = scores * (D**-0.5)

    key_idx = jnp.arange(S, dtype=jnp.int32)
    # valid iff key position <= query position (causal; rows past the written
    # prefix have key_idx > q_pos so they are masked automatically)
    mask = key_idx[None, None, :] <= q_positions[:, :, None]  # [B, T, S]
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)

    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)

    if is_quant_kv(v_cache):
        # The v scale varies along the contracted S axis, so it folds
        # into probs (same size as the score matrix, already resident)
        # before the mixed f32 × int8 pv dot — again no dequantized
        # cache copy expressed.
        v_s = jnp.transpose(v_cache.s, (0, 2, 1))[:, :, None, None, :]
        pv = jax.lax.dot_general(
            probs * v_s,                       # [B, Hkv, G, T, S] f32
            jnp.swapaxes(v_cache.q, 1, 2),     # [B, Hkv, S, D] int8
            (((4,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        )                                      # [B, Hkv, G, T, D]
        out = jnp.transpose(pv, (0, 3, 1, 2, 4)).astype(q.dtype)
    else:
        probs = probs.astype(v_cache.dtype)
        out = jnp.einsum("bhgts,bshd->bthgd", probs, v_cache)
    return out.reshape(B, T, H, D)


# ---------------------------------------------------------------------------
# Window layers (models/llama.py's stacks): a band over [the rows before the
# chunk | the chunk] on the prefill / extend path (``window_attention``: the
# blocked kernel or ``band_attention``'s einsums), a ring on the decode path.
# ---------------------------------------------------------------------------


def band_attention(q, k, v, prev_k, prev_v, first, window: int):
    """Causal attention in which a query sees its own row and the ``window``
    - 1 before it, over a chunk and the rows that precede it.

    q [B, T, H, D]; k, v [B, T, Hkv, D], the chunk's own rows at positions
    ``first[b] + t``; prev_k, prev_v [B, window, Hkv, D], the rows at
    positions ``first[b] - window + j`` (one before ``first`` is the last;
    those before position 0 are masked whatever they hold), or None for a
    chunk with nothing before it. → [B, T, H, D].

    The score tensor is the band, never [H, T, S] masked: the queries go in
    blocks of Q = ``window`` rows (one block of T where ``window`` does not
    divide T), and block n meets rows n·Q … n·Q + Q + window - 1 of [prev |
    chunk], the only ones any of its queries sees: [H, T, Q + window]
    scores in float32, O(T × (2 × window)) whatever the context."""
    B, T, H, D = q.shape
    Hkv, W = k.shape[2], window
    G = H // Hkv
    Q = _band_queries(T, W)
    nb = T // Q
    if prev_k is None:
        prev_k = prev_v = jnp.zeros((B, W, Hkv, D), k.dtype)
    at = (jnp.arange(nb, dtype=jnp.int32)[:, None] * Q
          + jnp.arange(Q + W, dtype=jnp.int32)[None, :])              # [nb, Q + W]
    kb = jnp.concatenate([prev_k.astype(k.dtype), k], axis=1)[:, at]   # [B, nb, Q+W, Hkv, D]
    vb = jnp.concatenate([prev_v.astype(v.dtype), v], axis=1)[:, at]
    scores = jnp.einsum("bnqhgd,bnkhd->bnhgqk", q.reshape(B, nb, Q, Hkv, G, D), kb,
                        preferred_element_type=jnp.float32) * (D**-0.5)
    # Query i of a block lies at row W + n·Q + i of [prev | chunk], key j at
    # n·Q + j: causal j <= W + i, inside the window j > i.
    i = jnp.arange(Q, dtype=jnp.int32)[:, None]
    j = jnp.arange(Q + W, dtype=jnp.int32)[None, :]
    seen = ((j > i) & (j <= i + W))[None, None]                        # [1, 1, Q, Q+W]
    if first is None:
        real = (at >= W)[None, :, None, :]                             # [1, nb, 1, Q+W]
    else:
        real = (first[:, None, None] - W + at[None] >= 0)[:, :, None, :]  # [B, nb, 1, Q+W]
    scores = jnp.where((seen & real)[:, :, None, None], scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = (probs / probs.sum(axis=-1, keepdims=True)).astype(v.dtype)
    out = jnp.einsum("bnhgqk,bnkhd->bnqhgd", probs, vb)
    return out.reshape(B, T, H, D)


def window_attention(q, k, v, prev_k, prev_v, first, window: int, mesh=None):
    """``band_attention`` (its operands and its result) by the route: the
    blocked kernel where ``window_kernel_on`` says so, the einsum band else
    (the CPU, a mesh, heads off 128 lanes, chunks and windows in no whole
    128-row tiles, a band whose scores are small). The kernel meets the keys
    as one array of rows: a fresh chunk's own, queries at rows 0 … T − 1
    (where T ≤ ``window`` the window never binds: the causal call a full
    layer makes); a piece's ``[prev | chunk]``, queries at rows ``window +
    t``, of which the rows before ``window − first[b]`` lie before position
    0 and are below the slot's lowest row, whatever they hold."""
    B, T, H, D = q.shape
    fresh = prev_k is None
    if not window_kernel_on(T, window, H, D, fresh, mesh):
        return band_attention(q, k, v, prev_k, prev_v, first, window)
    from omnia_tpu.ops.prefill_attention import prefill_attention

    lowest, at = None, 0
    if not fresh:
        k = jnp.concatenate([prev_k.astype(k.dtype), k], axis=1)
        v = jnp.concatenate([prev_v.astype(v.dtype), v], axis=1)
        lowest, at = jnp.maximum(window - first, 0), window
    Hkv = k.shape[2]
    positions = jnp.broadcast_to(at + jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    return prefill_attention(
        q.reshape(B, T, H * D), k.reshape(B, -1, Hkv * D), v.reshape(B, -1, Hkv * D),
        positions, None, lowest, kv_heads=Hkv, scale=D**-0.5,
        window=0 if fresh and T <= window else window,   # it never binds: the causal call
        interpret=_pallas_decode_mode() == "interpret",
    ).reshape(B, T, H, D)


def ring_decode_attention(q, ring_k, ring_v, q_positions, layer, live, window: int):
    """One decode step of a window layer over layer ``layer`` of its rings
    [L, B, R, Hkv, D] (R a power of two): row r of a slot holds the newest
    position ≡ r (mod R) at or before the slot's, its own included. q [B,
    1, H, D] → [B, 1, H, D]. The Pallas kernel where it is routed on
    (ops/decode_attention.py::decode_window_attention: a dead slot reads
    nothing), else the same mask over the ring by einsum."""
    B, _, H, D = q.shape
    R, Hkv = ring_k.shape[2:4]
    if _kernel_on():
        from omnia_tpu.ops import decode_attention as dk

        if live is None:
            live = jnp.ones((B,), jnp.int32)
        return dk.decode_window_attention(
            q[:, 0], ring_k, ring_v, q_positions[:, 0], jnp.asarray(layer, jnp.int32),
            live=live.astype(jnp.int32), window=window,
            block_s=decode_block_rows(R), interpret=_pallas_decode_mode() == "interpret",
        )[:, None]
    k = jax.lax.dynamic_index_in_dim(ring_k, layer, 0, keepdims=False)   # [B, R, Hkv, D]
    v = jax.lax.dynamic_index_in_dim(ring_v, layer, 0, keepdims=False)
    scores = jnp.einsum("bhgd,bshd->bhgs", q[:, 0].reshape(B, Hkv, H // Hkv, D), k,
                        preferred_element_type=jnp.float32) * (D**-0.5)
    back = (q_positions - jnp.arange(R, dtype=jnp.int32)[None, :]) & (R - 1)  # [B, R]
    seen = (back < window) & (back <= q_positions)
    scores = jnp.where(seen[:, None, None, :], scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = (probs / probs.sum(axis=-1, keepdims=True)).astype(v.dtype)
    return jnp.einsum("bhgs,bshd->bhgd", probs, v).reshape(B, 1, H, D)
