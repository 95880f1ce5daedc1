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
  ``layer`` of the whole state ``[L, B, H, dk, dv]``, in place. On a TPU a
  Pallas kernel whose grid is (live slot × group of heads) from a
  scalar-prefetched list, each block read once and written once, exact
  float32 on the vector unit; the state is aliased in and out and a dead
  slot's blocks are not visited. dk and dv need be no whole 128: a block is
  the state's own last two axes whole (dk a multiple of 8 sublanes), and
  the heads of a block are the largest divisor of H whose block stays
  under ``BLOCK_BYTES`` (30 heads of 96 × 192: 15, 1.1 MB a block).
  Elsewhere ``delta_step``.
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
#: third of the scoped VMEM.
BLOCK_BYTES = 5 << 18
#: Rows of a head's tile of step vectors (k, q, v, α, β; a float32 tile's
#: eight sublanes) and the lanes of a row: whole 128s that hold dk and dv.
_VECTORS = 8


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


def head_block(H: int, dk: int, dv: int) -> int:
    """Heads of one block of the decode kernel: the largest divisor of H
    whose [dk, dv] float32 states stay under ``BLOCK_BYTES`` (at least one)."""
    fit = [h for h in range(1, H + 1) if H % h == 0 and h * dk * dv * 4 <= BLOCK_BYTES]
    return max(fit, default=1)


def _state_kernel(layer_ref, work_ref, zeros_ref, vec_ref, s_ref, o_ref, s_out_ref, *,
                  heads: int):
    """One grid step a (live slot, group of ``heads`` heads). vec_ref [1,
    heads, 8, W]: rows k, q (dk lanes), v (dv lanes), α, β (every lane) of
    each head; s_ref, s_out_ref [1, heads, dk, dv] (k down the sublanes, v
    along the lanes); o_ref [1, heads, 1, dv]."""
    del layer_ref, work_ref, zeros_ref
    dk, dv = s_ref.shape[-2:]
    W = vec_ref.shape[-1]

    def column(row):  # [1, W] → [dk, 1], entry [i] = row[i]
        return jnp.broadcast_to(row, (W, W)).T[:dk, :1]

    for h in range(heads):
        vec = vec_ref[0, h]                                     # [8, W]
        k_col, q_col = column(vec[0:1]), column(vec[1:2])
        S = s_ref[0, h] * vec[3:4, :dv]                         # α·S
        r = jnp.sum(S * k_col, axis=0, keepdims=True)           # S'ᵀk  [1, dv]
        S = S + k_col * (vec[4:5, :dv] * (vec[2:3, :dv] - r))
        o_ref[0, h] = jnp.sum(S * q_col, axis=0, keepdims=True)
        s_out_ref[0, h] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_call(state, vectors, layer, live, interpret: bool = False):
    """state [L, B, H, dk, dv] f32, vectors [B, H, 8, W] f32 → (o [B, H, 1,
    dv], state): the Pallas call, over the live slots' blocks only."""
    L, B, H, dk, dv = state.shape
    W = vectors.shape[-1]
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
            pl.BlockSpec((1, hb, _VECTORS, W), vec_index, memory_space=pltpu.VMEM),
            state_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, hb, 1, dv), vec_index, memory_space=pltpu.VMEM),
            state_spec,
        ],
    )
    return pl.pallas_call(
        functools.partial(_state_kernel, heads=hb),
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
    """One decode step of layer ``layer`` of the whole state [L, B, H, dk,
    dv] float32, in place: q, k [B, H, dk]; v [B, H, dv]; g, beta [B, H];
    ``live`` bool [B] or None (every slot). → (o [B, H, dv] f32, state). A
    dead slot's state is left as it is and its output row is not to be
    used. ``kernel``: the Pallas call, else ``delta_step`` on the layer taken
    out and put back."""
    f32 = jnp.float32
    if kernel:
        B, H, dk = q.shape
        W = -max(dk, v.shape[-1]) // 128 * -128

        def row(a):  # [B, H, n] or [B, H] → [B, H, W]
            a = a.astype(f32)
            return (jnp.broadcast_to(a[..., None], (B, H, W)) if a.ndim == 2
                    else jnp.pad(a, ((0, 0), (0, 0), (0, W - a.shape[-1]))))

        rows = [row(a) for a in (k, q, v, jnp.exp(g.astype(f32)), beta)]
        vectors = jnp.stack(rows + [jnp.zeros((B, H, W), f32)] * (_VECTORS - len(rows)), axis=2)
        o, state = _state_call(state, vectors, layer, live, interpret=interpret)
        return o[:, :, 0], state
    S = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    o, new = delta_step(S, q, k, v, g, beta)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, S)
    return o, jax.lax.dynamic_update_slice_in_dim(state, new[None], layer, axis=0)
