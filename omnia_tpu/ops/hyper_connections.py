"""Manifold-constrained hyper-connections: a residual of n copies a token,
mixed around every sublayer by maps the token itself decides.

A token's residual is ``X`` [n, D], carried flat as the last axis ``[..., n·D]``
(copy i is lanes ``i·D … (i+1)·D − 1``). Around a sublayer ``f`` (attention, or
the FFN / expert layer; benchmark/reference/xing4_ref.py is the same
mathematics in plain float32):

- ``x̄ = vec(X) / sqrt(mean(vec(X)²) + ε)``, no gain (one would fold into Φ);
- ``a = α ⊙ (x̄·Φ) + b`` with Φ [n·D, 2n + n²] = ``[Φ_pre | Φ_post | Φ_res]``,
  b [2n + n²] = ``[b_pre | b_post | vec(B_res)]`` (row-major), α [3] one scalar
  a group;
- ``h_pre = σ(a_pre)``, ``h_post = 2σ(a_post)``, ``H_res = sinkhorn(exp(clip(A_res)))``:
  ``iters`` times the columns divided by their sums + eps, then the rows;
- ``u = Σ_i h_pre[i]·X[i]`` is what the sublayer's norm sees, and
  ``X'[i] = Σ_j H_res[i, j]·X[j] + h_post[i]·f(u)``.

``pre`` gives ``u`` and the maps, ``post`` the new stream. Everything but the
stream's own type is float32: ``X·Φ`` takes the stream as it lies (exact
products of what bfloat16 holds, accumulated in float32) and is divided by
the flat norm afterwards, which is the same number as ``x̄·Φ``. The maps of a
token are 2n + n² values against its n·D of stream, so they are kept with the
token axes LAST (``[n, n, B, T]``): Sinkhorn's sums are then adds of whole
vectors, and its 2·iters steps fuse into one elementwise pass (``sinkhorn``).
The two mixes are written over the copies' lane-aligned slices, as sums of n
scaled vectors, and never as a matmul with a contraction of n.

What it costs (my chip runs, PR 35): 2 % of a 1.5 k-token prefill's device
seconds and under 1 % of a decode step's. Counted as the stream read twice and
written once a sublayer, the mix moved 1.2 to 1.4 TB/s where HBM gives 819
GB/s: the chip's compiler keeps a prompt's stream (58 MB at 2048 × 14336 in
bfloat16; its buffers are marked ``S(1)`` in the compiled program) on the chip
from one pass to the next, so the passes (the norm, ``X·Φ``, the pre-mix, the
residual mix) are not bound by HBM, and a kernel that merged them has little to
win at these lengths.

Scopes: ``hc.mix`` around all of it, inside it ``hc.maps`` (the flat norm,
``X·Φ``, the two sigmoids) and ``hc.sinkhorn``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def sinkhorn(m, iters: int, eps: float):
    """m [n, n, ...] positive, rows on axis 0 and columns on axis 1 →
    doubly stochastic to rounding: ``iters`` times, every column divided by
    its sum + eps, then every row by its.

    Unrolled over the iterations and over the n² entries, each a whole
    vector over the token axes, with the sums written as adds: a ``reduce``
    ends a fusion, and 2·iters of them a sublayer were 80 launches of a few
    microseconds each (the chip's compiler, PR 35); adds and divides alone
    are one elementwise pass."""
    n = m.shape[0]
    rows = [[m[i, j] for j in range(n)] for i in range(n)]
    for _ in range(iters):
        # 2n reciprocals and 2n² products an iteration, not 2n² divides: the
        # same number to an ulp, and the CPU's compiler takes a twentieth
        # of the time over the 640 chained divides (22 s a program, PR 35).
        cols = [1.0 / (sum(rows[i][j] for i in range(n)) + eps) for j in range(n)]
        rows = [[v * cols[j] for j, v in enumerate(row)] for row in rows]
        sums = [1.0 / (sum(row) + eps) for row in rows]
        rows = [[v * sums[i] for v in row] for i, row in enumerate(rows)]
    return jnp.stack([jnp.stack(row) for row in rows])


def maps(x, p, n: int, *, iters: int, eps: float, clamp: tuple, norm_eps: float):
    """x [..., n·D] → (h_pre [n, ...], h_post [n, ...], h_res [n, n, ...]) in
    float32, the token axes last. ``p``: ``phi`` [n·D, 2n + n²], ``bias``
    [2n + n²], ``alpha`` [3]."""
    with jax.named_scope("hc.maps"):
        xf = x.astype(F32)
        inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + norm_eps)
        z = jnp.dot(x, p["phi"], preferred_element_type=F32) * inv
        alpha = p["alpha"].astype(F32)[np.repeat(np.arange(3), [n, n, n * n])]
        a = jnp.moveaxis(z * alpha + p["bias"].astype(F32), -1, 0)   # [2n + n², ...]
        h_pre, h_post = jax.nn.sigmoid(a[:n]), 2.0 * jax.nn.sigmoid(a[n:2 * n])
    with jax.named_scope("hc.sinkhorn"):
        a_res = a[2 * n:].reshape(n, n, *a.shape[1:])
        h_res = sinkhorn(jnp.exp(jnp.clip(a_res, *clamp)), iters, eps)
    return h_pre, h_post, h_res


def _copies(x, n: int):
    d = x.shape[-1] // n
    return [x[..., i * d:(i + 1) * d].astype(F32) for i in range(n)]


@jax.named_scope("hc.mix")
def pre(x, p, n: int, **constants):
    """The sublayer's side of the stream: (u [..., D] in the stream's type,
    (h_post, h_res) for ``post``)."""
    h_pre, h_post, h_res = maps(x, p, n, **constants)
    u = sum(h_pre[i][..., None] * c for i, c in enumerate(_copies(x, n)))
    return u.astype(x.dtype), (h_post, h_res)


@jax.named_scope("hc.mix")
def post(x, y, mixes):
    """x [..., n·D], the sublayer's output y [..., D] → the new stream."""
    h_post, h_res = mixes
    n = h_post.shape[0]
    copies, yf = _copies(x, n), y.astype(F32)
    out = [sum(h_res[i, j][..., None] * c for j, c in enumerate(copies))
           + h_post[i][..., None] * yf for i in range(n)]
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


def expand(x, n: int, width: int):
    """The stream a table's rows start: a row of the model's width copied n
    times, one already n·width wide the copies themselves (a sub-model cut
    by benchmark/harness/correct.py is handed the stream as its table)."""
    return x if x.shape[-1] == n * width else jnp.tile(x, n)


def fold(x, n: int):
    """What leaves the last layer: the copies summed, [..., D]."""
    return sum(_copies(x, n)).astype(x.dtype)
