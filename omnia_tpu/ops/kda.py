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
  *No exponent is ever positive*: the factorised form ``(q ⊙ exp(G))·(k ⊙
  exp(−G))`` over a whole chunk overflows float32 at the decays this model
  has (1.6 a token is e^102 over 64). So a chunk's rows are cut into
  sub-blocks of ``SUB`` (``_chunk_blocks``). Only the diagonal ``[SUB, SUB]``
  blocks of A and B are pairwise over dk, ``exp(G_i − G_j)`` masked to j ≤ i
  on ``[SUB, SUB, dk]``, summed on the vector unit. Under them a block row I
  meets every earlier row j through G at the block's first row ``s``:
  ``(k_i ⊙ exp(G_i − G_s))·(k_j ⊙ exp(G_s − G_j))``, a matmul, and both
  exponents are ≤ 0 because G falls along a chunk and j < s ≤ i. The system
  is not solved row by row either: ``N = Diag(β)·A`` is strictly lower
  triangular and ``T = (I + N)⁻¹`` is taken by halves, ``[[L, 0], [R, D]]⁻¹ =
  [[L⁻¹, 0], [−D⁻¹·R·L⁻¹, D⁻¹]]`` from two rows up (``_unit_lower_inverse``:
  two matmuls a level, five levels a chunk), then ``U = T·rhs``.
  *What is rounded:* the four einsums that read or write the carried state
  (``K̃·S``, ``Q̃·S``, ``tril(B)·U``, ``K̂ᵀ·U``) run at the default precision,
  float32 operands through bfloat16 in one pass, as a configuration states
  (``assumed.extend_matmul_precision``); A, B, T and ``T·rhs`` are
  ``Precision.HIGHEST``, as the sums and the float32 solve were that they
  replace. All of a chunk stays inside the scan: on the chip that is faster
  than the state-free part done for a piece's 16 chunks at once.
  A row with ``β = 0`` and ``g = 0`` leaves the state as it was: that is how
  a piece's pad rows, a length that is no multiple of the chunk and a chunk
  that is no multiple of ``SUB`` are held.
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
#: Rows of a sub-block of a chunk: only the diagonal [SUB, SUB] blocks are
#: pairwise over dk. The published kernels' choice; two float32 tiles' sublanes.
SUB = 16
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


def _mm(a, b):
    """a [..., m, n] · b [..., n, p] in float32 with nothing rounded."""
    return jnp.matmul(a, b, precision=_HIGHEST)


def _unit_lower_inverse(N):
    """(I + N)⁻¹ for N [..., n, n] strictly lower triangular, by halves:
    [[L, 0], [R, D]]⁻¹ = [[L⁻¹, 0], [−D⁻¹·R·L⁻¹, D⁻¹]], both halves' inverses
    in one batch, down to two rows (N² = 0 there: I − N). That is forward
    substitution by blocks, two matmuls a level, and as stable as the
    row-by-row solve. The closed product (I − N)(I + N²)(I + N⁴)… over a
    whole chunk is not: keys that share a direction under a weak decay give
    powers of N with entries of 1e7 and more that cancel to an inverse of
    order 1 (the benchmark's ``correct`` caught it on the chip at eight times
    the parent's distance; ``tests/test_kimi_linear.py`` has the case)."""
    n = N.shape[-1]
    if n <= 2:
        return jnp.eye(n, dtype=N.dtype) - N
    if n % 2:  # a row and a column of the identity, taken off again
        grown = jnp.pad(N, [(0, 0)] * (N.ndim - 2) + [(0, 1), (0, 1)])
        return _unit_lower_inverse(grown)[..., :n, :n]
    h = n // 2
    halves = _unit_lower_inverse(jnp.stack([N[..., :h, :h], N[..., h:, h:]], axis=-3))
    L, D = halves[..., 0, :, :], halves[..., 1, :, :]
    return jnp.concatenate([
        jnp.concatenate([L, jnp.zeros_like(L)], axis=-1),
        jnp.concatenate([-_mm(D, _mm(N[..., h:, :h], L)), D], axis=-1)], axis=-2)


def _chunk_blocks(q, k, g, beta):
    """What a chunk's rule needs of its inputs alone (nothing here reads the
    carried state): q, k, g [..., C, dk], beta [..., C], C a whole number of
    sub-blocks → K̃, Q̃, K̂ [..., C, dk], exp(G_C) [..., dk], T = (I +
    Diag(β)·A)⁻¹ and tril(B) [..., C, C]. The module docstring has the block
    form. All of it is float32 and its matmuls are ``HIGHEST``: A and B were
    sums on the vector unit and the system a float32 solve before they were
    matmuls, and nothing is rounded now that was not then."""
    *lead, C, dk = q.shape
    sub = min(SUB, C)
    nb = C // sub
    G = jnp.cumsum(g, axis=-2)
    Gb, qb, kb = (a.reshape(*lead, nb, sub, dk) for a in (G, q, k))
    first = Gb[..., :1, :]                                      # G at a block's first row
    # The diagonal blocks, pairwise over dk: exponents G_i - G_j for j <= i
    # and -inf elsewhere. A sum for B and a sum for A: each fuses with its
    # products and the decays; one sum over stacked rows [q | k] leaves the
    # decays' products in memory between two fusions (the chip reads 0.32 ms
    # a piece against 0.86).
    i = jnp.arange(sub)
    decay = jnp.exp(jnp.where((i[:, None] >= i[None, :])[:, :, None],
                              Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf))
    diag = jnp.stack([jnp.sum(r[..., :, None, :] * kb[..., None, :, :] * decay, axis=-1)
                      for r in (qb, kb)], axis=-4)              # [..., 2, nb, sub, sub]
    # Under them, block row I against every row j before its first, through
    # G at that first row: both exponents are <= 0 (G falls along a chunk),
    # and a later j has -inf.
    before = (jnp.arange(C)[None, :] < jnp.arange(nb)[:, None] * sub)[:, :, None]
    cols = k[..., None, :, :] * jnp.exp(
        jnp.where(before, first - G[..., None, :, :], -jnp.inf))   # [..., nb, C, dk]
    rows = jnp.stack([qb, kb], axis=-4) * jnp.exp(Gb - first)[..., None, :, :, :]
    under = jnp.einsum("...xnid,...njd->...xnij", rows, cols,
                       precision=_HIGHEST)                      # [..., 2, nb, sub, C]
    on_diag = jnp.eye(nb, dtype=bool)[:, None, :, None]         # block I of block row I
    AB = (under.reshape(*lead, 2, nb, sub, nb, sub)
          + jnp.where(on_diag, diag[..., None, :], 0.0)).reshape(*lead, 2, C, C)
    r = jnp.arange(C)
    T = _unit_lower_inverse(
        jnp.where(r[:, None] > r[None, :], beta[..., None] * AB[..., 1, :, :], 0.0))
    eG, last = jnp.exp(G), G[..., -1:, :]
    return k * eG, q * eG, k * jnp.exp(last - G), jnp.exp(last[..., 0, :]), T, AB[..., 0, :, :]


def _chunk(S, x):
    """One chunk of C tokens for every slot and head: S [B, H, dk, dv]; q, k,
    g [B, H, C, dk]; v [B, H, C, dv]; beta [B, H, C] → (S_C, o [B, H, C, dv]).
    The module docstring has the mathematics. The four einsums that read or
    write the state carry no ``precision``: on a TPU float32 operands are
    rounded to bfloat16 in one pass and summed in float32, so a piece reads
    the float32 state through bfloat16 once a chunk, where ``kda_step`` and
    the decode kernel are exact. A configuration states that beside its
    state's type (``assumed.extend_matmul_precision``). ``T·rhs`` is
    ``HIGHEST`` as the solve it replaces was float32: at the default the
    state a piece leaves stands 5.3e-3 from the recurrence's, not 3.7e-3."""
    q, k, v, g, beta = x
    Kt, Qt, Kh, eGC, T, Bq = _chunk_blocks(q, k, g, beta)
    rhs = beta[..., None] * (v - jnp.einsum("bhck,bhkv->bhcv", Kt, S))
    U = _mm(T, rhs)
    o = jnp.einsum("bhck,bhkv->bhcv", Qt, S) + jnp.einsum("bhij,bhjv->bhiv", Bq, U)
    S = eGC[..., None] * S + jnp.einsum("bhck,bhcv->bhkv", Kh, U)
    return S, o


def kda_chunked(q, k, v, g, beta, S0, chunk: int = CHUNK):
    """The rule over T tokens in chunks. Shapes as ``kda_recurrent``. T need
    be no multiple of ``chunk``, nor a chunk of ``SUB``: the rows that fill
    the last chunk, and every chunk to whole sub-blocks, have β = 0 and g = 0
    and leave the state as it is."""
    B, T, H, _ = q.shape
    f32 = jnp.float32
    C = min(chunk, T)
    pad = -T % C
    N = (T + pad) // C
    fill = -C % min(SUB, C)

    def chunks(a):  # [B, T, H, ...] → [N, B, H, C + fill, ...]
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(B, N, C, *a.shape[2:])
        a = jnp.pad(a, ((0, 0), (0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 3))
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    S, o = jax.lax.scan(_chunk, S0.astype(f32), tuple(map(chunks, (q, k, v, g, beta))))
    o = jnp.moveaxis(jnp.moveaxis(o[:, :, :, :C], 2, 3), 0, 1).reshape(B, N * C, H, -1)
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
