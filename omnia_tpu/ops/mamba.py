"""The Mamba-1 selective scan: a diagonal recurrence a channel, three ways
that agree, and the decode kernel over the states where they lie.

A layer of E channels keeps a state ``S`` [N, E] float32 (S₀ = 0): N numbers a
channel, held with the channels along the lanes. A token brings, for every
channel c, an input ``u[c]`` and a step ``Δ[c]`` > 0, and for the whole layer
two vectors ``B``, ``C`` [N]; the layer owns ``A`` [N, E] < 0 (``−exp(A_log)``)
and a skip ``D`` [E]:

    S[n, c] ← exp(Δ[c]·A[n, c])·S[n, c] + Δ[c]·B[n]·u[c]
    y[c]    = Σ_n S[n, c]·C[n] + D[c]·u[c]

There are no heads, keys or values, and the decay differs for every one of
the N·E entries and every token: nothing here is a matmul. Everything is
float32 on the vector unit whatever the stream's type, the exponent and the
sum over n among it, as the published kernels have it.

- ``mamba_step``: one token, the rule as written. ``mamba_recurrent``: the
  plain loop a token a step; what the other two are tested against.
- ``mamba_chunked`` (T > 1: a prompt's piece from a carried state): one
  ``lax.scan`` over chunks of ``CHUNK`` tokens. A chunk's decays ``exp(Δ·A)``
  and inputs ``Δ·B·u`` are made for its tokens at once ([B, CHUNK, N, E], a
  few MB), the state passes through them a token at a time, and the chunk's
  outputs are one sum over n. No ``[T, N, E]`` of a whole piece is ever live
  (E·N·4 = 328 KB a token: 336 MB a layer at 1,024 rows). A row with Δ = 0
  leaves the state exactly as it was (decay 1, input 0): a piece's pad rows
  and a length that is no multiple of the chunk. There is no matmul whose
  precision could be stated: the arithmetic is ``mamba_step``'s.
- ``mamba_scan`` (T > 1 on a TPU, where ``scan_takes`` the shape): the same
  piece as one Pallas kernel. The recurrence is a channel's own, so the grid
  is (slot, block of channels, block of ``SCAN_ROWS`` tokens) with the tokens
  innermost and in order: a block of channels keeps its state [N, e] in VMEM
  scratch from the piece's first token to its last (S0 in at the first block
  of tokens, S out at the last), and a token costs the loads of its rows of Δ
  and Δ·u, some twenty vector operations a tile of 128 channels and the store
  of its row of y: nothing of [T, N, E] ever exists, in VMEM or in HBM. B and
  C come with every number already spread over a tile's 128 lanes ([B, T, N,
  128], 8 KB a token each), so the body needs no transpose and no broadcast
  along the lanes. The arithmetic is ``mamba_step``'s, in its order.
- ``decode_mamba_state`` (T == 1): one step of every live slot over layer
  ``layer`` of the whole state ``[L, B, N, E]``, in place. On a TPU a Pallas
  kernel: the layer index and the list of slot groups with a live slot are
  scalar-prefetched, a grid step takes ``ROWS`` consecutive slots' states of
  one block of channels ([ROWS, N, e] float32, whole tiles: N = 16 sublanes,
  e whole 128 lanes), reads it once and writes it once where it lies
  (aliased in and out); a group without a live slot is not visited. A dead
  slot inside a visited group gets Δ = 0, u = 0, B = 0: its state is written
  back as read. The step's vectors ride as rows of [B, E] arrays (Δ, Δ·u; a
  group's are one whole tile) and B, C as lanes of a [B, 128] array, turned
  into columns by one tile transpose a grid step. Elsewhere ``mamba_step`` on
  the layer taken out and put back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Tokens of one chunk of ``mamba_chunked``.
CHUNK = 16
#: Slots of one grid step of the decode kernel: a tile's sublanes of the
#: step's [B, E] float32 vectors.
ROWS = 8
#: The most bytes of state one grid step of the decode kernel moves in (and
#: out): ops/delta.py's, for its reasons (in and out double-buffered it is a
#: third of the scoped VMEM).
BLOCK_BYTES = 5 << 18
#: Tokens of one grid step of ``mamba_scan``: what a piece's length is a
#: multiple of where the kernel takes it (every prefill bucket of every cell is).
SCAN_ROWS = 128
#: Channels of one block of ``mamba_scan`` at most: [SCAN_ROWS, 1280] float32
#: blocks of Δ, Δ·u and y are 0.66 MB each, double-buffered 3.9 MB beside 4 MB
#: of B and C.
SCAN_LANES = 1280
#: Lanes the kernel's body works on at a time: a [N, 512] float32 piece of a
#: 16-number state is eight vector registers, so a slot's chain of products
#: stays in registers from its load to its store.
_LANES_A_PIECE = 512


def mamba_step(S, u, dt, Bv, Cv, A, D):
    """One token: S [..., N, E] f32; u, dt [..., E]; Bv, Cv [..., N]; A [N, E]
    (negative); D [E] → (y [..., E] f32, S). Plain float32 on any backend."""
    f32 = jnp.float32
    u, dt = u.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt[..., None, :] * A.astype(f32))
    S = decay * S + Bv.astype(f32)[..., :, None] * (dt * u)[..., None, :]
    y = jnp.sum(S * Cv.astype(f32)[..., :, None], axis=-2) + D.astype(f32) * u
    return y, S


def mamba_recurrent(u, dt, Bv, Cv, A, D, S0):
    """The rule a token a step. u, dt [B, T, E]; Bv, Cv [B, T, N]; A [N, E]; D
    [E]; S0 [B, N, E] → (y [B, T, E] f32, S_T)."""
    def token(S, x):
        y, S = mamba_step(S, *x, A, D)
        return S, y

    S, y = jax.lax.scan(token, S0.astype(jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (u, dt, Bv, Cv)))
    return jnp.moveaxis(y, 0, 1), S


def mamba_chunked(u, dt, Bv, Cv, A, D, S0, chunk: int = CHUNK):
    """The rule over T tokens in chunks. Shapes as ``mamba_recurrent``. T need
    be no multiple of ``chunk``: the rows that fill the last chunk have Δ = 0
    and leave the state as it is, as every row whose ``dt`` is 0 does."""
    B, T, E = u.shape
    f32 = jnp.float32
    C = min(chunk, T)
    pad = -T % C
    A = A.astype(f32)

    def chunks(a):  # [B, T, W] → [T/C, B, C, W]
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(a.reshape(B, -1, C, a.shape[-1]), 1, 0)

    def one(S, x):
        u, dt, Bv, Cv = x                                        # [B, C, E or N]
        decay = jnp.exp(dt[:, :, None, :] * A)                   # [B, C, N, E]
        fed = Bv[..., None] * (dt * u)[:, :, None, :]
        states = []
        for t in range(C):
            S = decay[:, t] * S + fed[:, t]
            states.append(S)
        y = jnp.sum(jnp.stack(states, axis=1) * Cv[..., None], axis=2)
        return S, y

    S, y = jax.lax.scan(one, S0.astype(f32), tuple(map(chunks, (u, dt, Bv, Cv))))
    y = jnp.moveaxis(y, 0, 1).reshape(B, -1, E)[:, :T]
    return y + D.astype(f32) * u.astype(f32), S


def scan_takes(T: int, N: int, E: int) -> bool:
    """Whether ``mamba_scan``'s Pallas call takes a piece of T tokens: whole
    blocks of ``SCAN_ROWS`` tokens and whole tiles of state."""
    return T > 1 and T % SCAN_ROWS == 0 and N % 8 == 0 and E % 128 == 0


def _scan_lanes(E: int) -> int:
    return max(e for e in range(128, min(E, SCAN_LANES) + 1, 128) if E % e == 0)


def _scan_kernel(a_ref, dt_ref, du_ref, b_ref, c_ref, s0_ref, y_ref, s_out_ref, s_ref):
    """One grid step a (slot, block of channels, block of tokens). a_ref [N,
    e]; dt_ref, du_ref, y_ref [rows, e]; b_ref, c_ref [rows, N, 128] (a number
    a tile row); s0_ref, s_out_ref [N, e]; s_ref [N, e] scratch, the state
    between two blocks of tokens."""
    t_block = pl.program_id(2)
    rows, e = dt_ref.shape

    @pl.when(t_block == 0)
    def _first():
        s_ref[...] = s0_ref[...]

    def eight(g, carry):
        """Eight tokens: a tile's sublanes of Δ, Δ·u and y (the chip loads rows
        at a dynamic index in whole tiles only), a tile of channels at a time,
        whose state stays in registers from the first of them to the last."""
        at8 = pl.ds(pl.multiple_of(g * 8, 8), 8)
        b, c = b_ref[at8], c_ref[at8]                              # [8, N, 128]
        for lo in range(0, e, 128):
            at = slice(lo, lo + 128)
            dt, du, A = dt_ref[at8, at], du_ref[at8, at], a_ref[:, at]
            S, ys = s_ref[:, at], []
            for r in range(8):
                S = S * jnp.exp(dt[r:r + 1] * A) + b[r] * du[r:r + 1]
                ys.append(jnp.sum(S * c[r], axis=0, keepdims=True))
            s_ref[:, at] = S
            y_ref[at8, at] = jnp.concatenate(ys, axis=0)
        return carry

    jax.lax.fori_loop(0, rows // 8, eight, 0)

    @pl.when(t_block == pl.num_programs(2) - 1)
    def _last():
        s_out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_scan(u, dt, Bv, Cv, A, D, S0, interpret: bool = False):
    """``mamba_chunked``'s operands and results, by the Pallas kernel: T whole
    blocks of ``SCAN_ROWS`` tokens (``scan_takes``)."""
    f32 = jnp.float32
    B, T, E = u.shape
    N = A.shape[0]
    e = _scan_lanes(E)
    u, dt = u.astype(f32), dt.astype(f32)

    def spread(a):  # [B, T, N] → [B, T, N, 128]: a number a row of lanes
        return jnp.broadcast_to(a.astype(f32)[..., None], (B, T, N, 128))

    rows_spec = pl.BlockSpec((None, SCAN_ROWS, e), lambda b, c, t: (b, t, c),
                             memory_space=pltpu.VMEM)
    bc_spec = pl.BlockSpec((None, SCAN_ROWS, N, 128), lambda b, c, t: (b, t, 0, 0),
                           memory_space=pltpu.VMEM)
    state_spec = pl.BlockSpec((None, N, e), lambda b, c, t: (b, 0, c), memory_space=pltpu.VMEM)
    y, S = pl.pallas_call(
        _scan_kernel,
        out_shape=[jax.ShapeDtypeStruct((B, T, E), f32), jax.ShapeDtypeStruct((B, N, E), f32)],
        grid=(B, E // e, T // SCAN_ROWS),
        in_specs=[pl.BlockSpec((N, e), lambda b, c, t: (0, c), memory_space=pltpu.VMEM),
                  rows_spec, rows_spec, bc_spec, bc_spec, state_spec],
        out_specs=[rows_spec, state_spec],
        scratch_shapes=[pltpu.VMEM((N, e), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mamba_scan",
    )(A.astype(f32), dt, dt * u, spread(Bv), spread(Cv), S0.astype(f32))
    return y + D.astype(f32) * u, S


def lane_block(N: int, E: int, rows: int = ROWS) -> int:
    """Channels of one block of the decode kernel: the most whole 128 lanes
    that divide E with ``rows`` slots' [N, e] float32 states under
    ``BLOCK_BYTES`` (at least 128)."""
    fit = [e for e in range(128, E + 1, 128)
           if E % e == 0 and rows * N * e * 4 <= BLOCK_BYTES]
    return max(fit, default=128)


def kernel_takes(B: int, N: int, E: int) -> bool:
    """Whether ``decode_mamba_state``'s Pallas call takes a state [L, B, N, E]:
    whole tiles (N whole eights of sublanes, at most 64 so that B and C share
    a row of 128 lanes; E whole 128 lanes) and slots in whole groups of
    ``ROWS``, or fewer than ``ROWS`` of them (one group, the array's own)."""
    return N % 8 == 0 and N <= 64 and E % 128 == 0 and (B % ROWS == 0 or B < ROWS)


def _state_kernel(layer_ref, work_ref, zeros_ref, a_ref, dt_ref, du_ref, bc_ref, s_ref,
                  y_ref, s_out_ref, *, rows: int):
    """One grid step a (block of channels, group of ``rows`` slots). a_ref [N,
    e]; dt_ref, du_ref [rows, e] (Δ and Δ·u); bc_ref [rows, 128] (B in lanes 0
    … N − 1, C in N … 2N − 1); s_ref, s_out_ref [rows, N, e]; y_ref [rows, e]."""
    del layer_ref, work_ref, zeros_ref
    N, e = a_ref.shape
    piece = next(p for p in (_LANES_A_PIECE, 256, 128) if e % p == 0)
    # The slots' B and C rows as columns, one transpose for all of them:
    # cols[i, r] = bc[r, i].
    bc = bc_ref[...]
    cols = jnp.concatenate([bc, jnp.zeros((128 - rows, 128), bc.dtype)]).T
    for r in range(rows):
        b_col, c_col = cols[:N, r:r + 1], cols[N:2 * N, r:r + 1]  # [N, 1]
        for lo in range(0, e, piece):
            at = slice(lo, lo + piece)
            dt = dt_ref[r:r + 1, at]                               # [1, piece]
            S = s_ref[r, :, at] * jnp.exp(dt * a_ref[:, at]) + b_col * du_ref[r:r + 1, at]
            y_ref[r:r + 1, at] = jnp.sum(S * c_col, axis=0, keepdims=True)
            s_out_ref[r, :, at] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_call(state, A, dt, du, bc, layer, live, interpret: bool = False):
    """state [L, B, N, E] f32; A [N, E]; dt, du [B, E]; bc [B, 128] f32 → (y
    [B, E] without the skip, state): the Pallas call, over the groups of slots
    with a live one."""
    L, B, N, E = state.shape
    rows = min(ROWS, B)
    groups = B // rows
    e = lane_block(N, E, rows)
    live = jnp.ones((B,), bool) if live is None else live.astype(bool)
    alive = live.reshape(groups, rows).any(axis=1)
    # The groups with a live slot first, in order; the steps past them never run.
    work = jnp.argsort(~alive, stable=True).astype(jnp.int32)
    n_work = alive.sum(dtype=jnp.int32)
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1), work]

    def rows_index(c, w, layer_ref, work_ref):
        return (work_ref[w], c)

    def state_index(c, w, layer_ref, work_ref):
        return (layer_ref[0], work_ref[w], 0, c)

    rows_spec = pl.BlockSpec((rows, e), rows_index, memory_space=pltpu.VMEM)
    state_spec = pl.BlockSpec((None, rows, N, e), state_index, memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        # Channels outermost: a block of A is fetched once for all its groups.
        grid=(E // e, n_work),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((N, e), lambda c, w, *_: (0, c), memory_space=pltpu.VMEM),
            rows_spec, rows_spec,
            pl.BlockSpec((rows, 128), lambda c, w, layer_ref, work_ref: (work_ref[w], 0),
                         memory_space=pltpu.VMEM),
            state_spec,
        ],
        out_specs=[rows_spec, state_spec],
    )
    return pl.pallas_call(
        functools.partial(_state_kernel, rows=rows),
        out_shape=[jax.ShapeDtypeStruct((B, E), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        grid_spec=grid_spec,
        # The outputs start as zeros and as the state itself: a dead group's
        # output rows stay zero and its states' blocks are never visited.
        input_output_aliases={len(prefetch): 0, len(prefetch) + 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="decode_mamba_state",
    )(*prefetch, jnp.zeros((B, E), jnp.float32), A, dt, du, bc, state)


def decode_mamba_state(state, u, dt, Bv, Cv, A, D, layer, live=None, *,
                       kernel: bool = False, interpret: bool = False):
    """One decode step of layer ``layer`` of the whole state [L, B, N, E]
    float32, in place: u, dt [B, E]; Bv, Cv [B, N]; A [N, E]; D [E]; ``live``
    bool [B] or None (every slot). → (y [B, E] f32, state). A dead slot's
    state is left as it is and its output row is not to be used. ``kernel``:
    the Pallas call where ``kernel_takes`` the shape, else ``mamba_step`` on
    the layer taken out and put back."""
    f32 = jnp.float32
    L, B, N, E = state.shape
    if u.shape != (B, E) or Bv.shape != (B, N) or A.shape != (N, E):
        raise ValueError(f"a state {state.shape} is not that of u {u.shape}, B "
                         f"{Bv.shape} and A {A.shape}")
    u, dt, Bv, Cv = (a.astype(f32) for a in (u, dt, Bv, Cv))
    if kernel and kernel_takes(B, N, E):
        if live is not None:  # Δ = 0, u = 0, B = 0: decay 1 and nothing fed
            dt, fed, Bv, Cv = (jnp.where(live[:, None], a, 0.0) for a in (dt, u, Bv, Cv))
        else:
            fed = u
        bc = jnp.pad(jnp.concatenate([Bv, Cv], axis=1), ((0, 0), (0, 128 - 2 * N)))
        y, state = _state_call(state, A.astype(f32), dt, dt * fed, bc, layer, live,
                               interpret=interpret)
        return y + D.astype(f32) * u, state
    S = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    y, new = mamba_step(S, u, dt, Bv, Cv, A, D)
    if live is not None:
        new = jnp.where(live[:, None, None], new, S)
    return y, jax.lax.dynamic_update_slice_in_dim(state, new[None], layer, axis=0)
