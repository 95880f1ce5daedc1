"""The gated delta rule with ONE decay a head (a scalar gate), keys and
values of different widths, three ways that agree.

A head keeps a state ``S`` [dk, dv] float32 (S₀ = 0). A token brings a query
and a key ``q, k`` [dk], a value ``v`` [dv], a log-decay ``g`` ≤ 0, one number
a head (``α = exp(g)``), and a write strength ``β``, in (0, 1) or, where the
model allows negative eigenvalues, in (0, 2):

    S' = α·S            S ← S' + β·k·(v − S'ᵀk)ᵀ            o = Sᵀq

that is ``S ← (I − β k kᵀ)·α·S + β k vᵀ``: the decay BEFORE the update, the
state float32 whatever the stream's type. It is ops/kda.py's rule with the
decay the same for every channel of a head, and that one difference is why
this is a module beside it and not a case of it:

- ``delta_recurrent`` / ``delta_step``: a token a step, the rule as written
  (``kda_step`` with the scalar broadcast over the channels: the same
  float32 arithmetic). What the other two are tested against.
- ``delta_chunked`` (T > 1): chunks of ``CHUNK`` tokens under one ``lax.scan``
  that carries the state, the WY / UT transform as ops/kda.py's docstring
  sets it out. With a scalar decay the pairwise matrix is a matmul times a
  matrix of decays, ``A[i, j] = (k_i·k_j)·exp(G_i − G_j)``, and ``exp(G_i −
  G_j)`` for j ≤ i has an exponent ≤ 0 as it stands (G falls along a chunk):
  nothing can overflow, so none of ``kda._chunk_blocks``' 16-row blocks, which
  exist to keep a channel-wise ``exp(−G)`` out of a factorised product, is
  needed or paid for. ``(I + Diag(β)·A)⁻¹`` is ``kda._unit_lower_inverse`` (by
  halves; β up to 2 leaves it unit lower triangular).
  *What is rounded:* as in ops/kda.py, the four einsums that read or write
  the carried state (``K̃·S``, ``Q̃·S``, ``tril(B)·U``, ``K̂ᵀ·U``) run at the default
  precision (float32 operands through bfloat16 in one pass on a TPU), which
  a configuration states (``assumed.extend_matmul_precision``); ``K·Kᵀ``,
  ``Q·Kᵀ``, the inverse and ``T·rhs`` are ``Precision.HIGHEST``.
  A row with ``β = 0`` and ``g = 0`` leaves the state as it was: a piece's pad
  rows and a length that is no multiple of the chunk.
- ``decode_delta_state`` (T == 1): one step of every live slot over layer
  ``layer`` of the whole state, in place. The state is held PACKED, ``p``
  heads side by side along the lanes: ``[L, B, H/p, dk, p·dv]``
  (``pack_state`` / ``unpack_state``; packed head j holds heads j·p … j·p +
  p − 1, head c of them in lanes c·dv … (c + 1)·dv − 1). The chip stores an
  array's last axis in whole 128-lane tiles, so a 192-wide value would sit
  in 256 lanes and every read and write of a state would move a third more
  than the state holds; two heads side by side are 384 lanes, three whole
  tiles. ``p`` is the caller's word (models/stacks.py::``state_heads_a_row``,
  from the model's shapes) and is read here off the operands: H of ``q`` over
  the state's heads. p = 1 is the plain ``[L, B, H, dk, dv]``. On a TPU a
  Pallas kernel whose grid is (live slot × group of packed heads) from a
  scalar-prefetched list, each block read once and written once, exact
  float32 on the vector unit; the state is aliased in and out and a dead
  slot's blocks are not visited. Inside a packed row a head's key and query
  column reach that head's dv lanes only (a select by lane); v, α and β
  ride as rows of p·dv lanes, and ``S'ᵀk`` and ``Sᵀq`` are sums down the
  sublanes, so every element sees the float32 operations it would see alone,
  in the same order. dk and p·dv need be no whole 128: a block is the
  state's own last two axes whole (dk a multiple of 8 sublanes), and the
  packed heads of a block are the largest divisor of H/p whose block stays
  under ``BLOCK_BYTES`` (15 packed heads of 96 × 384: 5, 0.74 MB a block).
  Elsewhere ``delta_step`` on the layer unpacked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from omnia_tpu.ops.kda import _mm, _unit_lower_inverse, kda_recurrent, kda_step

#: Tokens of one chunk of ``delta_chunked``.
CHUNK = 64
#: The most bytes of state one grid step of the decode kernel moves in (and
#: out): a step's fixed cost is about a third of a microsecond, this much
#: takes a microsecond and a half, and in and out double-buffered it is a
#: third of the scoped VMEM. A larger block buys nothing: a slot's 15 packed
#: heads as one block of 2.2 MB read 455 µs a layer's call for 457 in three
#: of five (chip_delta_state.py, PR 51: both move what they move at 620 GB/s,
#: a read and a write stream's share of HBM), at three times the VMEM.
BLOCK_BYTES = 5 << 18


def delta_step(S, q, k, v, g, beta):
    """One token: S [..., dk, dv] f32; q, k [..., dk]; v [..., dv]; g, beta
    [...] → (o [..., dv] f32, S). Plain float32 on any backend."""
    return kda_step(S, q, k, v, g[..., None], beta)


def delta_recurrent(q, k, v, g, beta, S0):
    """The rule a token a step. q, k [B, T, H, dk]; v [B, T, H, dv]; g, beta
    [B, T, H]; S0 [B, H, dk, dv] → (o [B, T, H, dv] f32, S_T)."""
    return kda_recurrent(q, k, v, g[..., None], beta, S0)


def _chunk(S, x):
    """One chunk of C tokens for every slot and head: S [B, H, dk, dv]; q, k
    [B, H, C, dk]; v [B, H, C, dv]; g, beta [B, H, C] → (S_C, o [B, H, C, dv]).
    The four einsums against the state carry no ``precision`` (the module
    docstring); everything that does not touch it is ``HIGHEST``."""
    q, k, v, g, beta = x
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-1)                                   # [B, H, C]
    i = jnp.arange(C)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              G[..., :, None] - G[..., None, :], -jnp.inf))
    kT = jnp.swapaxes(k, -1, -2)
    A = _mm(k, kT) * decay                                       # [B, H, C, C]
    Bq = _mm(q, kT) * decay                                      # j <= i
    T = _unit_lower_inverse(
        jnp.where(i[:, None] > i[None, :], beta[..., None] * A, 0.0))
    eG, last = jnp.exp(G)[..., None], G[..., -1:]
    rhs = beta[..., None] * (v - jnp.einsum("bhck,bhkv->bhcv", k * eG, S))
    U = _mm(T, rhs)
    o = jnp.einsum("bhck,bhkv->bhcv", q * eG, S) + jnp.einsum("bhij,bhjv->bhiv", Bq, U)
    S = jnp.exp(last)[..., None] * S + jnp.einsum(
        "bhck,bhcv->bhkv", k * jnp.exp(last - G)[..., None], U)
    return S, o


def delta_chunked(q, k, v, g, beta, S0, chunk: int = CHUNK):
    """The rule over T tokens in chunks. Shapes as ``delta_recurrent``. T
    need be no multiple of ``chunk``: the rows that fill the last chunk have
    β = 0 and g = 0 and leave the state as it is."""
    B, T, H, _ = q.shape
    f32 = jnp.float32
    C = min(chunk, T)
    pad = -T % C
    N = (T + pad) // C

    def chunks(a):  # [B, T, H, ...] → [N, B, H, C, ...]
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(jnp.moveaxis(a.reshape(B, N, C, *a.shape[2:]), 1, 0), 2, 3)

    S, o = jax.lax.scan(_chunk, S0.astype(f32), tuple(map(chunks, (q, k, v, g, beta))))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(B, N * C, H, -1)
    return o[:, :T], S


def pack_state(S, p: int):
    """[..., H, dk, dv] → [..., H/p, dk, p·dv]: ``p`` heads side by side along
    the lanes (the module docstring). One transposing copy; p = 1 is S."""
    if p == 1:
        return S
    *lead, H, dk, dv = S.shape
    S = jnp.swapaxes(S.reshape(*lead, H // p, p, dk, dv), -3, -2)
    return S.reshape(*lead, H // p, dk, p * dv)


def unpack_state(S, p: int):
    """``pack_state``'s inverse: [..., H/p, dk, p·dv] → [..., H, dk, dv]."""
    if p == 1:
        return S
    *lead, packed, dk, lanes = S.shape
    S = jnp.swapaxes(S.reshape(*lead, packed, dk, p, lanes // p), -3, -2)
    return S.reshape(*lead, packed * p, dk, lanes // p)


def head_block(H: int, dk: int, dv: int) -> int:
    """(Packed) heads of one block of the decode kernel: the largest divisor
    of H whose [dk, dv] float32 states stay under ``BLOCK_BYTES`` (at least
    one). For a packed state H and dv are the packed counts, H/p and p·dv:
    the bytes are the block's real ones."""
    fit = [h for h in range(1, H + 1) if H % h == 0 and h * dk * dv * 4 <= BLOCK_BYTES]
    return max(fit, default=1)


def _columns(vec, n: int, dk: int):
    """The first ``n`` rows of vec [R, W], dk lanes of each, as columns [dk,
    ≥ n]: a tile of 128 rows (zeros behind the n) transposed, 128 lanes of
    keys at a time."""
    rows = -n // 8 * -8
    wide = -dk // 128 * -128
    tile = jnp.concatenate([vec[:rows, :wide], jnp.zeros((128 - rows, wide), vec.dtype)])
    return tile.T[:dk]


def _state_kernel(layer_ref, work_ref, zeros_ref, vec_ref, s_ref, o_ref, s_out_ref, *,
                  heads: int, p: int):
    """One grid step a (live slot, group of ``heads`` packed heads). vec_ref
    [1, heads, R, W]: rows k of each of a packed head's ``p`` heads, then q of
    each (dk lanes), then v, α, β (p·dv lanes, a head's dv lanes its own);
    s_ref, s_out_ref [1, heads, dk, p·dv] (k down the sublanes, the heads'
    values along the lanes); o_ref [1, heads, 1, p·dv]."""
    del layer_ref, work_ref, zeros_ref
    dk, lanes = s_ref.shape[-2:]
    dv = lanes // p
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def by_head(cols):  # p columns [dk, 1] → [dk, lanes]: head c's over its dv lanes
        out = cols[-1]
        for c in range(p - 2, -1, -1):
            out = jnp.where(lane < (c + 1) * dv, cols[c], out)
        return out

    for h in range(heads):
        vec = vec_ref[0, h]                                     # [R, W]
        # The 2p key and query rows as columns, one transpose for all of them:
        # cols[i, r] = vec[r, i].
        cols = _columns(vec, 2 * p, dk)
        k_col = by_head([cols[:, c:c + 1] for c in range(p)])
        q_col = by_head([cols[:, p + c:p + c + 1] for c in range(p)])
        v, alpha, beta = (vec[2 * p + i:2 * p + i + 1, :lanes] for i in range(3))
        S = s_ref[0, h] * alpha                                 # α·S
        r = jnp.sum(S * k_col, axis=0, keepdims=True)           # S'ᵀk  [1, p·dv]
        S = S + k_col * (beta * (v - r))
        o_ref[0, h] = jnp.sum(S * q_col, axis=0, keepdims=True)
        s_out_ref[0, h] = S


def _step_vectors(q, k, v, g, beta, p: int):
    """A step's operands as the kernel reads them, a tile a packed head: q, k
    [B, H, dk]; v [B, H, dv]; g, beta [B, H] → [B, H/p, R, W] float32, rows k
    of each of the p heads, q of each, then v, α = exp(g) and β with the
    heads side by side (α and β over their head's dv lanes); W whole 128s
    that hold dk and p·dv, R = 2p + 3 in whole tiles of eight sublanes."""
    f32 = jnp.float32
    B, H, dk = q.shape
    dv = v.shape[-1]
    W = -max(dk, p * dv) // 128 * -128

    def rows(a):  # [B, H, dk] → [B, H/p, p, W]: a head a row
        return jnp.pad(a.astype(f32).reshape(B, H // p, p, dk), ((0, 0),) * 3 + ((0, W - dk),))

    def lanes(a):  # [B, H, dv] or [B, H] → [B, H/p, 1, W]: the heads side by side
        a = jnp.broadcast_to(a.astype(f32).reshape(B, H, -1), (B, H, dv))
        return jnp.pad(a.reshape(B, H // p, 1, p * dv), ((0, 0),) * 3 + ((0, W - p * dv),))

    spare = jnp.zeros((B, H // p, -(2 * p + 3) % 8, W), f32)
    return jnp.concatenate(
        [rows(k), rows(q), lanes(v), lanes(jnp.exp(g.astype(f32))), lanes(beta), spare], axis=2)


@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def _state_call(state, vectors, layer, live, p: int, interpret: bool = False):
    """state [L, B, H/p, dk, p·dv] f32, vectors [B, H/p, R, W] f32 → (o [B,
    H/p, 1, p·dv], state): the Pallas call, over the live slots' blocks only."""
    L, B, H, dk, dv = state.shape
    R, W = vectors.shape[-2:]
    hb = head_block(H, dk, dv)
    groups = H // hb
    live = jnp.ones((B,), bool) if live is None else live.astype(bool)
    # The live slots first, in slot order; the steps past them never run.
    work = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_work = live.sum(dtype=jnp.int32) * groups
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1), work]

    def vec_index(w, layer_ref, work_ref):
        return (work_ref[w // groups], w % groups, 0, 0)

    def state_index(w, layer_ref, work_ref):
        return (layer_ref[0], work_ref[w // groups], w % groups, 0, 0)

    state_spec = pl.BlockSpec((None, 1, hb, dk, dv), state_index, memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_work,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, hb, R, W), vec_index, memory_space=pltpu.VMEM),
            state_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, hb, 1, dv), vec_index, memory_space=pltpu.VMEM),
            state_spec,
        ],
    )
    return pl.pallas_call(
        functools.partial(_state_kernel, heads=hb, p=p),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        grid_spec=grid_spec,
        # The outputs start as zeros and as the state itself: a dead slot's
        # output row stays zero and its state's blocks are never visited.
        input_output_aliases={len(prefetch): 0, len(prefetch) + 2: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_delta_state",
    )(*prefetch, jnp.zeros((B, H, 1, dv), jnp.float32), vectors, state)


def decode_delta_state(state, q, k, v, g, beta, layer, live=None, *, kernel: bool = False,
                       interpret: bool = False):
    """One decode step of layer ``layer`` of the whole packed state [L, B,
    H/p, dk, p·dv] float32, in place: q, k [B, H, dk]; v [B, H, dv]; g, beta
    [B, H]; ``live`` bool [B] or None (every slot); p is H over the state's
    heads. → (o [B, H, dv] f32, state). A dead slot's state is left as it is
    and its output row is not to be used. ``kernel``: the Pallas call, else
    ``delta_step`` on the layer taken out, unpacked, and put back."""
    B, H, _ = q.shape
    p = H // state.shape[2]
    if state.shape[2:] != (H // p, k.shape[-1], p * v.shape[-1]):
        raise ValueError(f"a state {state.shape} is no packing of {H} heads of "
                         f"{k.shape[-1]} x {v.shape[-1]}")
    if kernel:
        vectors = _step_vectors(q, k, v, g, beta, p)
        o, state = _state_call(state, vectors, layer, live, p=p, interpret=interpret)
        return o.reshape(B, H, -1), state
    S = unpack_state(jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False), p)
    o, new = delta_step(S, q, k, v, g, beta)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, S)
    return o, jax.lax.dynamic_update_slice_in_dim(state, pack_state(new, p)[None], layer, axis=0)
