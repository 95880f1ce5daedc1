"""The gated delta rule with a channel-wise decay (KDA linear attention),
three ways that agree.

A head keeps a state ``S`` [dk, dv] float32 (S₀ = 0). A token brings a query
and a key ``q, k`` [dk], a value ``v`` [dv], a log-decay ``g`` [dk] ≤ 0 a
channel (``α = exp(g)``) and a write strength ``β`` in (0, 1):

    S' = Diag(α)·S            S ← S' + β·k·(v − S'ᵀk)ᵀ            o = Sᵀq

that is ``S ← (I − β k kᵀ)·Diag(α)·S + β k vᵀ``. The decay comes BEFORE the
update. The state is float32 whatever the stream's type: it is summed into
over a whole context, and the published kernels keep it so.

- ``kda_recurrent``: one token a ``lax.scan`` step, the rule as written.
  What the other two are tested against; nothing serves through it.
- ``kda_chunked`` (T > 1: prefill, a prompt's piece): chunks of ``CHUNK``
  tokens under one ``lax.scan`` that carries the state. With ``G_i`` the
  cumulative log-decay inside the chunk, ``u_i = β_i (v_i − S'_iᵀ k_i)`` solves
  the unit lower-triangular system ``(I + Diag(β)·A)·U = Diag(β)·(V − K̃·S₀)``,
  ``A[i, j] = Σ_d k_i[d] k_j[d] exp(G_i[d] − G_j[d])`` for j < i (the WY / UT
  transform); then ``O = Q̃·S₀ + tril(B)·U`` with ``B`` the same sum over
  ``q_i, k_j`` for j ≤ i, and ``S_C = Diag(exp(G_C))·S₀ + K̂ᵀ·U``, where ``K̃_i =
  k_i ⊙ exp(G_i)``, ``Q̃_i = q_i ⊙ exp(G_i)``, ``K̂_j = k_j ⊙ exp(G_C − G_j)``.
  *Every exponent is a difference G_i − G_j with j ≤ i, never positive*: the
  factorised form ``(q ⊙ exp(G))·(k ⊙ exp(−G))`` overflows float32 inside one
  chunk at the decays this model has (1.6 a token is e^102 over 64). The
  pairwise tensor ``[C, C, dk]`` exists a chunk at a time, inside the scan,
  and feeds ONE reduction (A and B together), so that XLA fuses it away.
  A row with ``β = 0`` and ``g = 0`` leaves the state as it was: that is how
  a piece's pad rows and a length that is no multiple of the chunk are held.
- ``decode_kda_state`` (T == 1): one step of every live slot over layer
  ``layer`` of the whole state ``[L, B, H, dk, dv]``, in place. On a TPU a
  Pallas kernel whose grid is (live slot × group of ``HEAD_BLOCK`` heads)
  from a scalar-prefetched list: decay, ``S'ᵀk``, the rank-one update and
  ``Sᵀq`` in one pass over a block that is read once and written once, in
  float32 on the vector unit (no matmul unit: nothing is rounded to
  bfloat16). A dead slot's state is neither read nor written (the state
  is aliased in and out, and its blocks are not visited). Elsewhere
  ``kda_step``, the same step in ``jax.numpy``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Tokens of one chunk of ``kda_chunked``.
CHUNK = 64
#: Heads of one block of the decode kernel: 16 blocks of [128, 128] float32
#: are 1 MB, so a grid step moves 2 MB against its fixed cost of about a
#: third of a microsecond (one head a step would be 128 KB: the fixed cost
#: would be twice the transfer's time).
HEAD_BLOCK = 16
#: Rows of a head's tile of step vectors: α, k, q, v, β, and pad to the
#: float32 tile's eight sublanes.
_VECTORS = 8

_HIGHEST = jax.lax.Precision.HIGHEST


def kda_step(S, q, k, v, g, beta):
    """One token: S [..., dk, dv] f32; q, k, g [..., dk]; v [..., dv]; beta
    [...] → (o [..., dv] f32, S). Plain float32 on any backend."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    S = S * jnp.exp(g)[..., :, None]
    r = jnp.einsum("...kv,...k->...v", S, k, precision=_HIGHEST)
    S = S + k[..., :, None] * (beta[..., None] * (v - r))[..., None, :]
    return jnp.einsum("...kv,...k->...v", S, q, precision=_HIGHEST), S


def kda_recurrent(q, k, v, g, beta, S0):
    """The rule a token a step. q, k, g [B, T, H, dk]; v [B, T, H, dv]; beta
    [B, T, H]; S0 [B, H, dk, dv] → (o [B, T, H, dv] f32, S_T)."""
    def body(S, x):
        o, S = kda_step(S, *x)
        return S, o

    S, o = jax.lax.scan(body, S0.astype(jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _chunk(S, x):
    """One chunk of C tokens for every slot and head: S [B, H, dk, dv]; q,
    k, g [B, H, C, dk]; v [B, H, C, dv]; beta [B, H, C] → (S_C, o [B, H, C,
    dv]). The module docstring has the mathematics. Its matmuls (and the
    triangular system) carry no ``precision``: on a TPU float32 operands are
    rounded to bfloat16 in one pass and summed in float32, so a piece reads
    the float32 state through bfloat16 once a chunk, where ``kda_step`` and
    the decode kernel are exact. A configuration states that beside its
    state's type (``assumed.extend_matmul_precision``)."""
    q, k, v, g, beta = x
    C = q.shape[2]
    G = jnp.cumsum(g, axis=2)                                   # [B, H, C, dk]
    # A and B in one reduction over the pairwise decays, rows [q | k]
    # against k: exponents are G_i - G_j for j <= i and -inf elsewhere.
    i = jnp.arange(C)
    lower = i[:, None] >= i[None, :]                            # j <= i
    diff = G[:, :, :, None, :] - G[:, :, None, :, :]            # [B, H, C, C, dk]
    decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
    both = jnp.stack([q, k], axis=2)                            # [B, H, 2, C, dk]
    AB = jnp.sum(both[:, :, :, :, None, :] * k[:, :, None, None, :, :]
                 * decay[:, :, None], axis=-1)                  # [B, H, 2, C, C]
    Bq, A = AB[:, :, 0], AB[:, :, 1]
    strict = i[:, None] > i[None, :]
    M = jnp.eye(C, dtype=jnp.float32) + jnp.where(strict, beta[..., None] * A, 0.0)
    eG = jnp.exp(G)
    rhs = beta[..., None] * (v - jnp.einsum("bhck,bhkv->bhcv", k * eG, S))
    U = jax.scipy.linalg.solve_triangular(M, rhs, lower=True, unit_diagonal=True)
    o = jnp.einsum("bhck,bhkv->bhcv", q * eG, S) + jnp.einsum("bhij,bhjv->bhiv", Bq, U)
    last = G[:, :, -1:, :]                                      # G_C
    S = (jnp.exp(last[:, :, 0, :, None]) * S
         + jnp.einsum("bhck,bhcv->bhkv", k * jnp.exp(last - G), U))
    return S, o


def kda_chunked(q, k, v, g, beta, S0, chunk: int = CHUNK):
    """The rule over T tokens in chunks. Shapes as ``kda_recurrent``. T need
    be no multiple of ``chunk``: the rows that fill the last chunk have β = 0
    and g = 0 and leave the state as it is."""
    B, T, H, _ = q.shape
    f32 = jnp.float32
    C = min(chunk, T)
    pad = -T % C
    N = (T + pad) // C

    def chunks(a):  # [B, T, H, ...] → [N, B, H, C, ...]
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(B, N, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    S, o = jax.lax.scan(_chunk, S0.astype(f32), tuple(map(chunks, (q, k, v, g, beta))))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(B, N * C, H, -1)
    return o[:, :T], S


def _state_kernel(layer_ref, work_ref, zeros_ref, vec_ref, s_ref, o_ref, s_out_ref, *,
                  heads: int):
    """One grid step a (live slot, group of ``heads`` heads). vec_ref [1,
    heads, 8, d]: rows α, k, q, v, β of each head; s_ref, s_out_ref [1, heads,
    d, d] (k down the sublanes, v along the lanes); o_ref [1, heads, 1, d]."""
    del layer_ref, work_ref, zeros_ref
    d = s_ref.shape[-1]

    def column(row):  # [1, d] → [d, d], entry [i, :] = row[i]
        return jnp.broadcast_to(row, (d, d)).T

    for h in range(heads):
        vec = vec_ref[0, h]                                     # [8, d]
        k_col = column(vec[1:2])
        S = s_ref[0, h] * column(vec[0:1])                      # Diag(α)·S
        r = jnp.sum(S * k_col, axis=0, keepdims=True)           # S'ᵀk  [1, d]
        S = S + k_col * (vec[4:5] * (vec[3:4] - r))
        o_ref[0, h] = jnp.sum(S * column(vec[2:3]), axis=0, keepdims=True)
        s_out_ref[0, h] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_call(state, vectors, layer, live, interpret: bool = False):
    """state [L, B, H, d, d] f32, vectors [B, H, 8, d] f32 → (o [B, H, 1, d],
    state): the Pallas call, over the live slots' blocks only."""
    L, B, H, d, _ = state.shape
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H
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

    state_spec = pl.BlockSpec((None, 1, hb, d, d), state_index, memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_work,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, hb, _VECTORS, d), vec_index, memory_space=pltpu.VMEM),
            state_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, hb, 1, d), vec_index, memory_space=pltpu.VMEM),
            state_spec,
        ],
    )
    return pl.pallas_call(
        functools.partial(_state_kernel, heads=hb),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        grid_spec=grid_spec,
        # The outputs start as zeros and as the state itself: a dead slot's
        # output row stays zero and its state's blocks are never visited.
        input_output_aliases={len(prefetch): 0, len(prefetch) + 2: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_kda_state",
    )(*prefetch, jnp.zeros((B, H, 1, d), jnp.float32), vectors, state)


def decode_kda_state(state, q, k, v, g, beta, layer, live=None, *, kernel: bool = False,
                     interpret: bool = False):
    """One decode step of layer ``layer`` of the whole state [L, B, H, dk,
    dv] float32, in place: q, k, g [B, H, dk]; v [B, H, dv]; beta [B, H];
    ``live`` bool [B] or None (every slot). → (o [B, H, dv] f32, state). A
    dead slot's state is left as it is and its output row is not to be
    used. ``kernel``: the Pallas call (dk == dv, a multiple of 128 on the
    chip), else ``kda_step`` on the layer taken out and put back."""
    f32 = jnp.float32
    if kernel:
        B, H, d = q.shape
        rows = [jnp.exp(g.astype(f32)), k, q, v,
                jnp.broadcast_to(beta[..., None], (B, H, d))]
        vectors = jnp.stack([r.astype(f32) for r in rows]
                            + [jnp.zeros((B, H, d), f32)] * (_VECTORS - len(rows)), axis=2)
        o, state = _state_call(state, vectors, layer, live, interpret=interpret)
        return o[:, :, 0], state
    S = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    o, new = kda_step(S, q, k, v, g, beta)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, S)
    return o, jax.lax.dynamic_update_slice_in_dim(state, new[None], layer, axis=0)
