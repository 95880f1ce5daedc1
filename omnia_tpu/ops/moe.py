"""Mixture-of-experts routing and capacity-based dispatch.

The reference has no on-device models at all (its "Mixtral" is a string on a
Provider CR routed to a SaaS API — reference api/v1alpha1/provider_types.go,
agentruntime_types.go:382-414). Here MoE executes on the chip, so dispatch
efficiency is ours to win. Two interchangeable implementations, both exact
on the tokens they serve:

- ``moe_dense``: compute every expert, combine with top-k-masked router
  weights. No token ever drops; ~E/k redundant FLOPs. Right choice for tiny
  token counts (serving decode: a handful of slots) where the dispatch
  bookkeeping would dominate and dropped tokens are unacceptable.
- ``moe_dispatch``: GShard-style capacity dispatch, sort-based
  (MegaBlocks-style): assignments are sorted by expert, tokens are
  gathered into a static [E, C, d] buffer, expert FFNs run as batched
  einsums on the MXU, and results scatter-add back per token. O(N·K·d)
  memory — no O(N²) one-hot tensors — so long-context prefill fits HBM.
  Tokens past an expert's capacity contribute zero (standard capacity-drop
  semantics); use capacity_factor ≥ ~2 at small batch.

A third, ``moe_dropless``, is the expert layer of a chip that holds a
SHARE of the experts (models/mla.py): it routes over all of them, sorts the
(token, expert) assignments, and runs one grouped matmul over the
assignments that landed on the experts it holds. No capacity, so nothing
drops: at every token count every assignment on a held expert is
multiplied by that expert's matrices, accumulated in float32, none capped
or approximated. What changes with the count is the tile the grouped
matmul is computed in: a call of ``GROUPED_MATMUL_MIN_ROWS`` rows (one row
tile) or more, a prompt's and a served decode step's alike, runs through
the Pallas kernel of ops/grouped_matmul.py where the kernels are routed
on; a call shorter than a tile (a one-slot engine's step, a tiny test
model's) and every call off the TPU through ``jax.lax.ragged_dot``
(``_grouped_matmul``). Mixtral's ``moe_mlp`` moves onto it, and the two
above go, in a later PR (ROADMAP).

Sharding: expert-leading weights [E, d, f] shard E over the "tp" axis
(expert parallelism). The [E, C, d] buffer shards over E, each device runs
its experts' FFNs, and the scatter-add back to tokens reduces over E with
a GSPMD-inserted psum. Activations are replicated over tp — the right
trade at serving batch sizes; token-sharded all-to-all dispatch is the
large-batch training variant.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from omnia_tpu.ops.attention import _kernel_on, _pallas_decode_mode
from omnia_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul


def top_k_weights(logits, k: int, scoring: str = "softmax", bias=None):
    """Router logits float32 [..., E] → (top_w, top_i), each [..., k]: an
    expert's score is the softmax over all E or, ``scoring`` "sigmoid", the
    sigmoid of its own logit; the k with the largest score are kept, with a
    selection ``bias`` [E] the k with the largest score + bias; the weights
    are the kept scores themselves (never score + bias), renormalised to
    sum 1."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"router scoring {scoring!r}: softmax or sigmoid")
    scores = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(logits)
    if bias is None:
        top_w, top_i = jax.lax.top_k(scores, k)
    else:
        top_i = jax.lax.top_k(scores + bias.astype(scores.dtype), k)[1]
        top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    return top_w / top_w.sum(axis=-1, keepdims=True), top_i


def route_sparse(h, router_w, num_experts_per_tok: int, scoring: str = "softmax",
                 bias=None):
    """Router: h [..., d] × router_w [d, E] → (top_w, top_i), each [..., K].

    Mixtral semantics by default: float32 softmax over all experts, keep the
    top-k, renormalize kept weights to sum 1 (``top_k_weights`` says what
    ``scoring`` and ``bias`` change). The single source of routing truth —
    every MoE implementation derives from it so they can never diverge.
    """
    with jax.named_scope("moe.route"):
        logits = jnp.dot(h, router_w).astype(jnp.float32)
        return top_k_weights(logits, num_experts_per_tok, scoring, bias)


def route_topk(h, router_w, num_experts_per_tok: int):
    """Dense combine weights [..., E]: top-k renormalized, zero elsewhere."""
    E = router_w.shape[-1]
    top_w, top_i = route_sparse(h, router_w, num_experts_per_tok)
    return jnp.sum(
        jax.nn.one_hot(top_i, E, dtype=top_w.dtype) * top_w[..., None], axis=-2
    )


def moe_dense(h, p, num_experts_per_tok: int):
    """All-expert MoE: exact, no drops, ~E/k extra FLOPs. h: [B, T, d]."""
    combine = route_topk(h, p["router"], num_experts_per_tok)  # [B,T,E]
    gate = jnp.einsum("btd,edf->betf", h, p["wg"])
    up = jnp.einsum("btd,edf->betf", h, p["wu"])
    expert_out = jnp.einsum("betf,efd->betd", jax.nn.silu(gate) * up, p["wd"])
    return jnp.einsum("bte,betd->btd", combine.astype(h.dtype), expert_out)


def moe_dispatch(h, p, num_experts_per_tok: int, capacity_factor: float = 2.0):
    """Capacity-based dispatched MoE. h: [B, T, d] → [B, T, d].

    Sort-based (MegaBlocks-style) routing: the N·K (token, expert)
    assignments are sorted by expert, positions within each expert come
    from bincount offsets, and tokens move through a [E·C, d] buffer via
    gather/scatter — O(N·K·d) memory, never an O(N²) one-hot tensor, so
    long-context prefill stays HBM-feasible. Tokens beyond an expert's
    capacity C = ceil(N·k/E · capacity_factor) are dropped (contribute
    zero), matching GShard semantics. All shapes static.
    """
    B, T, d = h.shape
    E = p["router"].shape[-1]
    K = num_experts_per_tok
    N = B * T
    capacity = max(1, int(-(-N * K * capacity_factor // E)))  # ceil
    NK = N * K

    flat = h.reshape(N, d)
    top_w, top_i = route_sparse(flat, p["router"], K)  # [N, K]

    e_flat = top_i.reshape(NK)  # token-major assignment list
    w_flat = top_w.reshape(NK)
    tok_of = jnp.repeat(jnp.arange(N, dtype=jnp.int32), K)

    order = jnp.argsort(e_flat)  # stable → within an expert, token order kept
    e_s, w_s, t_s = e_flat[order], w_flat[order], tok_of[order]
    counts = jnp.bincount(e_flat, length=E)
    starts = jnp.cumsum(counts) - counts  # first row of each expert's run
    pos = jnp.arange(NK, dtype=jnp.int32) - starts[e_s]
    keep = pos < capacity
    # Overflow assignments land in a trash row past the buffer.
    dest = jnp.where(keep, e_s * capacity + pos, E * capacity)

    xs = jnp.zeros((E * capacity + 1, d), flat.dtype).at[dest].set(flat[t_s])
    xs = xs[: E * capacity].reshape(E, capacity, d)
    gate = jnp.einsum("ecd,edf->ecf", xs, p["wg"])
    up = jnp.einsum("ecd,edf->ecf", xs, p["wu"])
    ys = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up, p["wd"])

    contrib = ys.reshape(E * capacity, d)[jnp.clip(dest, 0, E * capacity - 1)]
    contrib = contrib * (w_s * keep).astype(flat.dtype)[:, None]
    out = jnp.zeros((N, d), flat.dtype).at[t_s].add(contrib)
    return out.reshape(B, T, d)


# Below this many tokens the dense path is both faster (no dispatch
# bookkeeping) and safer (zero drops); above it, dispatched FLOPs win.
DISPATCH_MIN_TOKENS = 64


def moe_mlp(h, p, num_experts_per_tok: int, capacity_factor: float = 2.0):
    """Shape-static auto-selection: decode-sized inputs go dense, prefill/train
    inputs go dispatched. The branch is on the *traced shape*, so each
    compiled program contains exactly one implementation."""
    B, T, _ = h.shape
    if B * T < DISPATCH_MIN_TOKENS:
        return moe_dense(h, p, num_experts_per_tok)
    return moe_dispatch(h, p, num_experts_per_tok, capacity_factor)


#: A grouped matmul of this many row tiles or more goes through the Pallas
#: kernel. The served models' decode steps call with 192–512 rows (slots ×
#: k), their shortest prompt buckets with 2,048 and more; at a step's rows
#: the kernel reads the hit experts' matrices 1.2–1.7 × faster than
#: ``ragged_dot`` (PERF.md section 6, PR 42 and PR 44). Below one tile
#: nothing is measured and no served shape gets there.
GROUPED_MATMUL_MIN_ROW_TILES = 1
#: The same in rows (tokens × k); the engine's start-up line says it
#: beside ``pallas_decode_mode()``.
GROUPED_MATMUL_MIN_ROWS = GROUPED_MATMUL_MIN_ROW_TILES * ROW_TILE


def _ragged_matmul(xs, w, sizes, layer):
    """``_grouped_matmul`` by ``jax.lax.ragged_dot``: with ``layer`` the
    stack is one group axis of L·Eh (a reshape) in which every other
    layer's groups are empty."""
    if layer is None:
        return jax.lax.ragged_dot(xs, w, sizes)
    L, Eh = w.shape[:2]
    stack_sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((L * Eh,), sizes.dtype), sizes, (layer * Eh,))
    return jax.lax.ragged_dot(xs, w.reshape(L * Eh, *w.shape[2:]), stack_sizes)


def _grouped_matmul(xs, w, sizes, layer):
    """Rows of ``xs`` in runs of ``sizes`` against each run's own matrix of
    ``w`` [Eh, k, n]. With ``layer``, ``w`` is the whole stack [L, Eh, k, n]
    and the runs meet layer ``layer``'s matrices where they lie. Slicing
    the layer out in front instead copies all its experts, hit or not,
    once a call (1.6 GB a layer a step at Mistral-Small-4's widths: 62 % of
    a decode step, PERF.md section 6, PR 32). The route is chosen by the
    call's static row count against ``GROUPED_MATMUL_MIN_ROWS`` (read from
    the module at each call: one row tile, so the kernel from a served
    decode step's rows up and ``ragged_dot`` below a tile), so a compiled
    program holds exactly one; both give a held row the same product,
    accumulated in float32."""
    if _kernel_on() and xs.shape[0] >= GROUPED_MATMUL_MIN_ROWS:
        return grouped_matmul(xs, w, sizes, layer,
                              interpret=_pallas_decode_mode() == "interpret")
    return _ragged_matmul(xs, w, sizes, layer)


def moe_dropless(h, p, num_experts_per_tok: int, first_expert: int = 0,
                 routed_scaling_factor: float = 1.0, layer=None,
                 scoring: str = "softmax"):
    """Dropless expert layer over the experts this chip holds.

    h [N, d]; ``p["router"]`` [d, E] scores all E experts; ``p["wg"]``,
    ``p["wu"]`` [Eh, d, f] and ``p["wd"]`` [Eh, f, d] are experts
    ``first_expert … first_expert + Eh - 1``, the only ones here (with
    ``layer``, an index, the three are the layers' stacks [L, Eh, …] and
    the layer's are used in place: ``_grouped_matmul``). Router
    logits in float32, softmax over all E (``scoring`` "sigmoid": each
    expert's own), the k largest (with ``p["bias"]`` [E], a selection bias:
    the k largest of score + bias) renormalised to sum 1
    (``top_k_weights``), times ``routed_scaling_factor``. Returns ``(out [N, d],
    held, hit)``: the weighted sum, a token, of its assignments' outputs
    on held experts (what an absent expert would add is left out: its
    chip adds it), how many of the N·k assignments landed on a held
    expert, and how many held experts got at least one.

    The N·k assignments are sorted by expert with those on absent
    experts last; the grouped matmul (``_grouped_matmul``) multiplies each
    held expert's run of rows by that expert's weights and visits no
    expert whose run is empty; the rows return to token order by the
    inverse permutation and sum over k. Every shape is static; the group
    sizes are data."""
    N, d = h.shape
    Eh, K = p["wg"].shape[-3], num_experts_per_tok
    with jax.named_scope("moe.route"):
        logits = jnp.dot(h, p["router"], preferred_element_type=jnp.float32)
        top_w, top_i = top_k_weights(logits, K, scoring, p.get("bias"))
        top_w = top_w * routed_scaling_factor
    with jax.named_scope("moe.sort"):
        local = top_i.reshape(N * K) - first_expert      # token-major
        held = (local >= 0) & (local < Eh)
        group = jnp.where(held, local, Eh)               # absent experts sort last
        order = jnp.argsort(group)                       # stable
        sizes = jnp.bincount(group, length=Eh + 1)[:Eh].astype(jnp.int32)
        xs = h[order // K]                               # [N·K, d]
    with jax.named_scope("moe.experts"):
        gate = _grouped_matmul(xs, p["wg"], sizes, layer)
        up = _grouped_matmul(xs, p["wu"], sizes, layer)
        ys = _grouped_matmul(jax.nn.silu(gate) * up, p["wd"], sizes, layer)
    with jax.named_scope("moe.combine"):
        # Rows past the held runs are whatever the grouped matmul left there.
        ys = jnp.where(held[order][:, None], ys, 0).astype(jnp.float32)
        ys = ys[jnp.argsort(order)].reshape(N, K, d)     # back to token order
        out = jnp.sum(ys * top_w[:, :, None], axis=1).astype(h.dtype)
    return out, held.sum(dtype=jnp.int32), (sizes > 0).sum(dtype=jnp.int32)


#: What ``expert_ffn`` counts a layer, in the order it returns them (the
#: engine.metrics keys a decode step's sums land under).
EXPERT_COUNTERS = ("moe_assignments_held", "moe_experts_hit")

_EXPERT_STACKS = ("wg", "wu", "wd")


def selection_bias(key, layers: int, held: int, experts: int):
    """A seeded selection bias [layers, experts] float32 for a router that
    picks by ``s + b``: one set of values whatever the seed, layer or rank
    (the midpoints of N(0, 0.05)'s ``held`` equal shares), in an order the
    seed draws for each layer, the ranks alike. Values drawn an expert favour
    one rank's experts over another's, and their shape says how many experts
    a step's tokens hit (the favoured are hit by every step, the rest
    seldom): a chip's load and a decode step's cost would follow the seed."""
    values = 0.05 * jax.scipy.special.ndtri((jnp.arange(held) + 0.5) / held)
    order = jax.vmap(lambda k: jax.random.permutation(k, held))(jax.random.split(key, layers))
    return jnp.tile(values.astype(jnp.float32)[order], (1, experts // held))


def init_ffn(cfg, layers: int, sparse: bool, normal, out_std: float, next_key):
    """The FFN's parameters of a stack of ``layers`` layers of one kind, for
    either family's ``init_params``: a dense SwiGLU of ``ffn_hidden_size``, or
    the router over all experts, the held share's three stacks, the shared
    expert and the selection bias. ``normal(shape, std=)`` draws a leaf in
    the caller's type from the caller's keys, ``next_key()`` the bias's."""
    D, F = cfg.hidden_size, cfg.moe_ffn_hidden_size

    def swiglu_of(lead, width):
        return {"wg": normal((*lead, D, width)), "wu": normal((*lead, D, width)),
                "wd": normal((*lead, width, D), std=out_std)}

    if not sparse:
        return swiglu_of((layers,), cfg.ffn_hidden_size)
    mlp = {"router": normal((layers, D, cfg.num_experts)),
           **swiglu_of((layers, cfg.experts_held), F)}
    if cfg.num_shared_experts:
        mlp["shared"] = swiglu_of((layers,), cfg.num_shared_experts * F)
    if cfg.router_bias:
        mlp["bias"] = selection_bias(next_key(), layers, cfg.experts_held, cfg.num_experts)
    return mlp


def unstack_experts(layers):
    """(what the layer scan slices a layer at a time, the routed experts'
    three stacks [L, Eh, …] whole): the experts are nine tenths of a
    layer's bytes and a step needs only those a token chose, so the scan
    must not slice a layer's out (``_grouped_matmul``). Of several stacks
    this is a sparse one's; a dense layer's FFN is read whole."""
    mlp = layers["mlp"]
    scanned = {**layers, "mlp": {k: v for k, v in mlp.items() if k not in _EXPERT_STACKS}}
    return scanned, {k: mlp[k] for k in _EXPERT_STACKS}


def swiglu(h, p):
    return jnp.dot(jax.nn.silu(jnp.dot(h, p["wg"])) * jnp.dot(h, p["wu"]), p["wd"])


def expert_ffn(h2, p, experts, layer, cfg):
    """The FFN of one layer of a model that holds a share of the routed
    experts (models/mla.py, and models/llama.py's stacks): the routed
    experts held here (``experts``: their stacks over the stack's layers,
    of which ``layer``'s are used; ``cfg``: a ModelConfig) and the shared
    expert. h2 [B, T, D] → (y [B, T, D], counts int32 [2] as
    EXPERT_COUNTERS). A dense layer (``experts`` None) is one SwiGLU and
    counts nothing."""
    if experts is None:
        return swiglu(h2, p), jnp.zeros((len(EXPERT_COUNTERS),), jnp.int32)
    B, T, D = h2.shape
    router = {k: p[k] for k in ("router", "bias") if k in p}
    y, held, hit = moe_dropless(
        h2.reshape(B * T, D), {**router, **experts},
        cfg.num_experts_per_tok,
        first_expert=cfg.expert_rank * cfg.experts_held,
        routed_scaling_factor=cfg.routed_scaling_factor, layer=layer,
        scoring=cfg.router_scoring,
    )
    y = y.reshape(B, T, D)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + swiglu(h2, p["shared"])
    return y, jnp.stack([held, hit])
