"""Layers of several kinds in the pair family (models/llama.py dispatches
here for a model with window layers, linear-attention layers, state-space
layers, experts of ``moe_ffn_hidden_size`` or leading dense layers): stacks,
runs, rings and states.

A layer has an attention kind, *window*
(``cfg.layer_types`` "sliding_attention": a query sees its own row and the
``sliding_window`` - 1 before it), *full*, *delta* ("linear_attention": the
gated delta rule with one decay a head, below) or *mamba* ("mamba": the
Mamba-1 selective scan, below), and an FFN kind, *dense* (SwiGLU
of ``ffn_hidden_size``) or *sparse* (``ops/moe.py::expert_ffn``: the sigmoid
or softmax router over all experts, the experts held here, all of them or a
rank's share, through ``moe_dropless``, and the shared expert where the model
has one, as models/mla.py runs it). ``params["layers"]`` is a
sequence of stacks, one for each kind the model has (``stack_kinds``): a
model whose every layer is sparse has sparse stacks only, and nothing here
asks for a dense one. ``layer_order`` / ``with_layer_order`` state and cut
the order as benchmark/README.md sets out (models/kinds.py, which the latent
family's kinds go through too). A forward pass is one ``lax.scan`` for each run
of consecutive layers of one kind, under the scope ``stack.<kind>``, over the
run's indices into its stack: the stack's leaves are read a layer at a time
where they lie, the routed experts' never sliced at all. With ``cfg.qk_norm``
every query and key head is RMS-normed (one gain ``[head_dim]``) before any
rotation (``attn.qk_norm``); with ``cfg.qk_norm_whole`` besides, q and k are
normed over their whole projected width (gains ``[q_dim]``, ``[kv_dim]``)
before the heads are split. ``cfg.norm_placement`` "post" puts a block's two
RMSNorms on the sublayers' outputs, ``x + norm(f(x))``, with nothing in front.

*A delta layer* (``_delta_mixer``; ops/delta.py is the rule, three ways):
``[q~ | k~ | v~] = h·Wqkv`` (H heads of dk, dk and dv), a depthwise causal
convolution of ``linear_conv_kernel`` taps and SiLU on each, q and k
L2-normed a head (q times dk^-½), ``β = sigmoid(h·Wb)`` (times 2 where
``linear_allow_neg_eigval``), ``g = −exp(A_log)·softplus(h·Wa + dt_bias)`` one
number a head, the rule over a float32 state ``[dk, dv]`` a head, then
``RMSNorm_dv(o)·SiLU(h·Wg)`` through ``Wo``. Scope ``attn.delta`` from the
sublayer's input to the residual, inside it ``delta.conv``, ``delta.gates``,
``delta.chunk`` (T > 1), ``delta.state`` (a decode step), ``delta.out``;
counter ``decode_delta_slots`` (the states a decode step updates: live slots
a delta layer). Such a model's cache is four arrays: K and V of the full
layers, the delta layers' states ``[Ld, B, H/p, dk, p·dv]`` float32 whatever
the stream's type (p = ``state_heads_a_row`` heads side by side, so that a
row of lanes is whole 128s; the decode kernel works on it as it lies, a
chunk unpacks its slot's layer in front of the rule and packs it behind),
and their convolutions' tails ``[Ld, B, taps - 1, 2·H·dk + H·dv]``. A state
has no position to mask by afterwards, so which rows and steps may touch it
is ``_delta_mixer``'s word, and it is models/mla.py::``_kda_layer``'s: a
piece's pad rows get β = 0 and g = 0 and the tail kept is the last REAL
row's; a piece at position 0 starts from S = 0 and a zero tail whatever the
slot holds; a decode step leaves a dead slot's state and tail as they are. (Window layers beside delta layers are not built.)

*A Mamba layer* (``_mamba_mixer``; ops/mamba.py is the rule, three ways), E =
``mamba_expand``·D channels of N = ``mamba_d_state`` state numbers, a step rank
R = ``mamba_dt_rank``, K = ``mamba_d_conv`` taps: ``[u | z] = h·W_in``; ``u' =
SiLU(conv_K(u) + b_c)`` depthwise and causal; ``[δ | B | C] = u'·W_x``, each
RMS-normed with a gain of its own where ``mamba_inner_norms``; ``Δ =
softplus(δ·W_dt + b_dt)``; ``A = −exp(A_log)``; ``S[n, c] ← exp(Δ[c]·A[n, c])·S[n,
c] + Δ[c]·B[n]·u'[c]``, ``y[c] = Σ_n S[n, c]·C[n] + D[c]·u'[c]``; ``(y ⊙ SiLU(z))·
W_out``. No heads, keys or values, no matmul form: a scan on the vector unit,
float32 (Δ, the exponent, the sum over n and the state) whatever the stream's
type. Scope ``attn.mamba`` from the sublayer's input to the residual, inside
it ``mamba.in``, ``mamba.conv``, ``mamba.gates`` (W_x, the norms, W_dt, the
softplus), ``mamba.scan`` (T > 1: the Pallas kernel ``mamba_scan`` where the
piece is whole blocks of 128 tokens, ``mamba_chunked`` else), ``mamba.state`` (a
decode step's kernel), ``mamba.out``; counter ``decode_mamba_slots`` (live slots a Mamba layer). Such
a model's cache is four arrays: K and V of the full layers, the Mamba layers'
states ``[Lm, B, N, E]`` float32 (the channels along the lanes: 16 sublanes x
5120 lanes are whole tiles, where ``[.., E, N]`` would store its 16 lanes as
128) and their convolutions' tails ``[Lm, B, (K − 1)·E]`` (the K − 1 rows side by
side along the lanes: as ``[.., K − 1, E]`` their three rows would be stored as
a tile's sixteen). ``A_log`` is held as ``[N, E]``, the state's own order. The
three rules for pad rows, a new tenant's first piece and dead slots are
``_delta_mixer``'s, in ``_mamba_mixer``'s words: a pad row gets Δ = 0, which is
decay 1 and input 0. (Window or delta layers or a sparse FFN beside Mamba
layers are not built.)

*A rotary table a kind of attention layer* (``rope_tables``, made once a
program under ``rope.tables``; a layer turns its q and k by its kind's under
``attn.rope``): window layers by plain RoPE at ``rope_theta``; full layers by
the same table, by one of their own where ``cfg.rope_full_yarn`` states it
(YaRN's blended frequencies, cos and sin times the attention factor), or,
``rope_on_full_layers`` false, not at all.

*The cache of a model with window layers is four arrays*: K and V of the
full layers ``[Lf, B, S, Hkv, D]`` (row s = position s, as models/llama.py has it) and K and V
of the window layers ``[Lw, B, R, Hkv, D]``, rings of R = ``ring_rows(cfg)``
rows, the window rounded up to a power of two: ring row r of a slot holds
the newest position p ≡ r (mod R) that the slot has written. The engine and
the harness hand the tuple back whole; a slot's view of an array is the
array's own row axis whole (``[L, 1, S or R, ...]``, engine/programs.py's
seam), and which rows of it mean what is this module's word alone:

- *Every write into a ring writes real rows only.* A chunk of T rows of
  which ``n`` are real (``row + 1`` where the caller names the last real row,
  else T) writes the last min(n, R) of them, each to ``(start + j) mod R``;
  the pad behind them, which in a whole-context array lands past the
  frontier, would here wrap onto rows still inside the window. A decode
  step writes a slot's row only where the slot is live.
- *A chunk attends before it writes*: over ``[the window - 1 rows before it,
  out of the ring | its own k, v]`` (``ops/attention.py::window_attention``:
  the blocked kernel of ops/prefill_attention.py with a lower bound on the
  key blocks where the route takes it, the einsums of ``band_attention``
  else, and in training).
  The ring therefore never has to hold a chunk and the window before it at
  once, R = window serves a chunk of any length, and the cost is O(T × 2 ×
  window) whatever the context. Sizing R for the largest chunk instead would
  make the ring's bytes, and a decode step's read of it, grow with the
  largest prefill bucket.
- *Decode* writes the slot's row and attends over the ring, masking by the
  position a row holds and never by its index (``ring_decode_attention``, the
  Pallas kernel ``decode_window_attention``): a row that the slot's own
  positions have not reached is the previous tenant's and reads as "before
  position 0". A full layer's call is ``decode_gqa_attention`` as ever.

Not ported to a model of several kinds, and refused by name at engine
construction (engine/family.py): kv_quant, kv_pages, sessions, the prefix
pool, spec_decode, the mixed step, int8 weights, sp, tp/dp > 1 (and for a
model with delta layers it says why a recurrent state cannot have its rows
offloaded, seeded, paged or rolled back).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from omnia_tpu.models import kinds
from omnia_tpu.models.config import ModelConfig
from omnia_tpu.ops import attention as _attention
from omnia_tpu.ops.attention import decode_block_rows, gqa_attention
from omnia_tpu.ops.delta import decode_delta_state, delta_chunked, pack_state, unpack_state
from omnia_tpu.ops.mamba import decode_mamba_state, mamba_chunked, mamba_scan, scan_takes
from omnia_tpu.ops.moe import EXPERT_COUNTERS, expert_ffn, init_ffn, unstack_experts
from omnia_tpu.ops.norms import rms_norm
from omnia_tpu.ops.rope import apply_rope, rope_cos_sin, yarn_scaled_cos_sin

#: Every kind a layer can be, in the order a model's stacks stand in (a
#: stack's seed is its place here: a new kind goes behind the others).
_KINDS = ("dense_window", "dense_full", "sparse_window", "sparse_full", "dense_delta",
          "dense_mamba")

#: The ε inside the square root of a delta head's key and query norms.
_L2_EPS = 1e-6


def is_stacked(cfg: ModelConfig) -> bool:
    """Whether ``params["layers"]`` is a sequence of stacks: a model with
    window layers, a share of the routed experts or leading dense layers
    (or one cut out of such a model)."""
    return bool(cfg.layer_types is not None or cfg.layer_stacks is not None
                or cfg.moe_ffn_hidden_size or cfg.num_dense_layers
                or cfg.norm_placement != "pre")


def decode_counters(cfg: ModelConfig) -> tuple:
    """Counters a decode step sums on the device over its layers, in the
    order ``forward(..., counters=True)`` returns them (engine.metrics keys):
    the expert layer's, for a model that has one, and the states a step
    updates (live slots a delta or Mamba layer), for a model with either."""
    return ((EXPERT_COUNTERS if cfg.moe_ffn_hidden_size else ())
            + ((f"decode_{_state_kind(cfg)}_slots",) if cfg.has_state_layers else ()))


def _state_kind(cfg: ModelConfig) -> str:
    """"mamba" or "delta": the kind of a model's layers that keep a state."""
    return "mamba" if any(kind.endswith("mamba") for kind in
                          cfg.attention_kinds + (cfg.layer_stacks or ())) else "delta"


def cache_kv_heads(cfg: ModelConfig) -> int:
    """KV heads of a cached row as a model of several kinds allocates it:
    ``num_kv_heads``, rounded up to whole eights where there are more than
    eight. The chip lays an array [.., rows, heads, head_dim] out with the
    rows INSIDE the heads where the heads are no whole number of sublane
    tiles (30: its default layout for [3072, 30, 128] is heads-major), and
    every program would then copy the whole cache in and out to hand its
    kernels rows (the chipless compile of a 30-head cache showed two copies
    of K and V a decode chunk). The heads behind ``num_kv_heads`` hold zeros:
    a layer pads its k and v, and its q by as many groups, and drops the
    padded heads' output (``_attention_sublayer``)."""
    H = cfg.num_kv_heads
    return H if H <= 8 else -(-H // 8) * 8


def state_heads_a_row(cfg: ModelConfig) -> int:
    """Delta heads that share a row of lanes in the cache's states, ``p``: the
    smallest divisor of ``linear_num_heads`` for which p heads' values are a
    whole number of 128 lanes; 1 where the value width already is one, or no
    divisor gives one. The chip stores an array's last axis in whole 128-lane
    tiles: a float32 state [96, 192] sits in rows of 256 lanes, and the
    decode kernel's every read and write of it moves a third more than the
    state holds (30 heads of 192: p = 2, [15, 96, 384], three whole tiles).
    From shapes alone; ops/delta.py has the layout and reads p off its
    operands."""
    H, dv = cfg.linear_num_heads, cfg.linear_value_head_dim
    return next((p for p in range(1, H + 1) if H % p == 0 and p * dv % 128 == 0), 1)


def state_shape(cfg: ModelConfig) -> tuple:
    """A slot's state of one delta layer as the cache holds it: (H/p, dk,
    p·dv), ``state_heads_a_row`` heads side by side; of one Mamba layer: (N,
    E), the channels along the lanes."""
    if _state_kind(cfg) == "mamba":
        return (cfg.mamba_d_state, cfg.mamba_channels)
    p = state_heads_a_row(cfg)
    return (cfg.linear_num_heads // p, cfg.linear_key_head_dim,
            p * cfg.linear_value_head_dim)


def conv_width(cfg: ModelConfig) -> int:
    """Columns of a delta layer's ``wqkv``, its taps and its tail: q | k | v."""
    return cfg.linear_num_heads * (2 * cfg.linear_key_head_dim + cfg.linear_value_head_dim)


def tail_shape(cfg: ModelConfig) -> tuple:
    """A slot's convolution tail of one state layer as the cache holds it:
    (taps - 1, ``conv_width``) of a delta layer; of a Mamba layer its K - 1 rows
    of E channels side by side, ((K - 1)·E,): three rows would be stored as a
    tile's sixteen (1.09 GB for 0.20 at 26 layers x 256 slots x 5120)."""
    if _state_kind(cfg) == "mamba":
        return ((cfg.mamba_d_conv - 1) * cfg.mamba_channels,)
    return (cfg.linear_conv_kernel - 1, conv_width(cfg))


def ring_rows(cfg: ModelConfig) -> int:
    """Rows of a window layer's ring: the window rounded up to a power of
    two (the decode kernel masks a row's age with ``& (R - 1)``)."""
    return 1 << (cfg.sliding_window - 1).bit_length()


def decode_window_rows(cfg: ModelConfig, lengths) -> int:
    """Ring rows that a window layer's decode kernel spans for live slots of
    those context ``lengths``: the ring's blocks up to a slot's position, the
    whole ring (and no more, whatever the context) once the position has
    passed it. 0 for a model without rings. (engine.metrics'
    ``decode_window_rows``, beside ``decode_kv_blocks`` for the full layers.)"""
    if not cfg.has_window_layers:
        return 0
    ring = ring_rows(cfg)
    rows = decode_block_rows(ring)
    return sum(min(n // rows + 1, ring // rows) * rows for n in lengths)


def rope_tables(cfg: ModelConfig, positions) -> dict:
    """(cos, sin) [..., head_dim // 2] of ``positions`` for each attention kind
    that rotates, made once a program under ``rope.tables``: "window" by
    plain RoPE; "full", where ``rope_on_full_layers``, the same pair unless
    ``rope_full_yarn`` gives the full layers a table of their own (YaRN's
    blended frequencies, cos and sin times the attention factor)."""
    with jax.named_scope("rope.tables"):
        tables = {"window": rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                         cfg.rope_scaling)}
        if cfg.rope_on_full_layers:
            tables["full"] = tables["window"] if cfg.rope_full_yarn is None else (
                yarn_scaled_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                    cfg.rope_full_yarn))
    return tables


def stack_kinds(cfg: ModelConfig) -> tuple:
    """The kind of each stack of ``params["layers"]``: those of ``_KINDS``
    that the model has a layer of (models/kinds.py)."""
    return kinds.stack_kinds(cfg, _KINDS)


def layer_order(cfg: ModelConfig) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ... (benchmark/README.md,
    "`layers`: one tree, or stacks")."""
    return kinds.layer_order(cfg, _KINDS)


def with_layer_order(cfg: ModelConfig, order) -> ModelConfig:
    """The same model with the layers ``order`` names: its own order over
    the cut stacks, any of which may be left with none."""
    return kinds.with_layer_order(cfg, order, _KINDS)


def _runs(cfg: ModelConfig) -> list:
    return kinds.runs(cfg, _KINDS)


def _init_stacks(cfg: ModelConfig, key: jax.Array, dtype):
    """``init_params`` of a model of several kinds: ``layers`` is a list, a
    stack for each of ``stack_kinds(cfg)`` with its layers on axis 0 (a cut
    model's may have none). Of the routed experts only the held share
    exists; a selection bias is ``mlp/bias`` [E] float32, the midpoints of
    N(0, 0.05)'s equal shares in a seeded order, the same on every rank:
    large enough to change which experts are kept, and neither a rank's
    load nor the count of experts a step hits depends on the seed. A delta
    layer: ``wqkv`` (q | k | v before the convolution), ``conv`` [taps, that
    width] normal(0, taps^-½), ``wa``, ``wb``, the gate's ``wg``, the head norm's
    ``on``, ``wo``, and in float32 ``a_log`` = log U(1, 16) and ``dt_bias`` the
    inverse softplus of a step log-uniform in 1e-3 … 0.1, a head each: a
    token's decay then lies in about 0.2 … 0.999 as a trained model's does
    (near 0 it would empty the state every token). A Mamba layer: ``win`` (u | z),
    ``conv`` [K, E] normal(0, K^-½) and its bias ``conv_b`` uniform in ±K^-½ (the
    published module's), ``wx`` (δ | B | C), the inner norms' gains ``dtn``, ``bn``,
    ``cn``, ``wdt``, ``wo``, and in float32 the published initialisation where a
    normal draw would empty or freeze the state: ``a_log`` [N, E] = log(1 … N) a
    channel, ``dt_bias`` the inverse softplus of a step log-uniform in 1e-3 …
    0.1, ``d`` = 1."""
    if cfg.has_window_layers and cfg.has_state_layers:
        raise NotImplementedError("window layers beside linear-attention or state-space "
                                  "layers in one model are not built (models/stacks.py)")
    if "mamba" in cfg.attention_kinds:
        beside = [what for what, there in (
            ("linear-attention layers", "delta" in cfg.attention_kinds),
            ("a sparse FFN", bool(cfg.moe_ffn_hidden_size)),
            ("a bias on the in and out projections (mamba_proj_bias)", cfg.mamba_proj_bias),
        ) if there]
        if beside:
            raise NotImplementedError(f"{beside[0]} beside state-space layers in one model "
                                      "are not built (models/stacks.py)")
    if cfg.norm_placement not in ("pre", "post"):
        raise ValueError(f"norm_placement {cfg.norm_placement!r}: \"pre\" or \"post\"")
    D, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    out_std = 0.02 / (2 * max(L, 1)) ** 0.5
    counts = kinds.stack_counts(cfg, _KINDS)

    def stack_of(kind, c, key):
        keys = iter(jax.random.split(key, 16))

        def normal(shape, std=0.02, dtype=dtype):
            return (jax.random.normal(next(keys), shape, dtype=jnp.float32) * std).astype(dtype)

        def log_uniform(shape, lo, hi):
            return jnp.exp(jax.random.uniform(next(keys), shape, jnp.float32,
                                              jnp.log(lo), jnp.log(hi)))

        if kind.endswith("mamba"):
            E, N, R, K = (cfg.mamba_channels, cfg.mamba_d_state, cfg.mamba_dt_rank,
                          cfg.mamba_d_conv)
            step = log_uniform((c, E), 1e-3, 0.1)
            attn = {"win": normal((c, D, 2 * E)),
                    "conv": normal((c, K, E), std=K ** -0.5),
                    "wx": normal((c, E, R + 2 * N)), "wdt": normal((c, R, E)),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                        1, N + 1, dtype=jnp.float32))[None, :, None], (c, N, E)),
                    "d": jnp.ones((c, E), jnp.float32),
                    "wo": normal((c, E, D), std=out_std)}
            if cfg.mamba_conv_bias:
                attn["conv_b"] = jax.random.uniform(
                    next(keys), (c, E), jnp.float32, -K ** -0.5, K ** -0.5).astype(dtype)
            if cfg.mamba_inner_norms:
                attn.update(dtn=jnp.ones((c, R), dtype), bn=jnp.ones((c, N), dtype),
                            cn=jnp.ones((c, N), dtype))
        elif kind.endswith("delta"):
            H, dv, taps = cfg.linear_num_heads, cfg.linear_value_head_dim, cfg.linear_conv_kernel
            step = log_uniform((c, H), 1e-3, 0.1)
            attn = {"wqkv": normal((c, D, conv_width(cfg))),
                    "conv": normal((c, taps, conv_width(cfg)), std=taps ** -0.5),
                    "wa": normal((c, D, H)), "wb": normal((c, D, H)),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "a_log": jnp.log(log_uniform((c, H), 1.0, 16.0)),
                    "wg": normal((c, D, H * dv)), "on": jnp.ones((c, dv), dtype),
                    "wo": normal((c, H * dv, D), std=out_std)}
        else:
            attn = {"wq": normal((c, D, cfg.q_dim)), "wk": normal((c, D, cfg.kv_dim)),
                    "wv": normal((c, D, cfg.kv_dim)),
                    "wo": normal((c, cfg.q_dim, D), std=out_std)}
            if cfg.qk_norm:
                q_gain, k_gain = ((cfg.q_dim, cfg.kv_dim) if cfg.qk_norm_whole
                                  else (cfg.head_dim, cfg.head_dim))
                attn["qn"] = jnp.ones((c, q_gain), dtype)
                attn["kn"] = jnp.ones((c, k_gain), dtype)
        mlp = init_ffn(cfg, c, kind.startswith("sparse"), normal, out_std, lambda: next(keys))
        return {"ln1": jnp.ones((c, D), dtype), "ln2": jnp.ones((c, D), dtype),
                "attn": attn, "mlp": mlp}

    k_embed, k_head, k_layers = jax.random.split(key, 3)
    params = {
        "embed": (jax.random.normal(k_embed, (V, D), jnp.float32) * 0.02).astype(dtype),
        "layers": [stack_of(kind, c, jax.random.fold_in(k_layers, _KINDS.index(kind)))
                   for kind, c in zip(stack_kinds(cfg), counts)],
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k_head, (D, V), jnp.float32) * 0.02).astype(dtype)
    return params


def _ring_image(new, start, n, R: int):
    """What a ring of R rows takes from the real rows of ``new`` [B, T, Hkv,
    D] (the first ``n`` [B] of them, at positions ``start[b] + j``): ring row
    r takes the newest real row whose position is ≡ r (mod R). → (rows [B,
    R, Hkv, D], which of them there is such a row for, bool [B, R]). No pad
    row is ever among them (the module docstring)."""
    T = new.shape[1]
    r = jnp.arange(R, dtype=jnp.int32)[None, :]
    last = (start + n - 1)[:, None]                    # the newest real position
    j = (n - 1)[:, None] - ((last - r) & (R - 1))      # [B, R] its row of `new`
    rows = jnp.take_along_axis(new, jnp.clip(j, 0, T - 1)[:, :, None, None], axis=1)
    return rows, j >= 0


def _ring_put(ring, new, start, n, layer):
    """Ring [L, B, R, Hkv, D] ← ``_ring_image`` of a chunk, layer ``layer``;
    a row the chunk has nothing for keeps what it holds."""
    rows, fresh = _ring_image(new, start, n, ring.shape[2])
    old = jax.lax.dynamic_index_in_dim(ring, layer, 0, keepdims=False)
    rows = jnp.where(fresh[:, :, None, None], rows.astype(ring.dtype), old)
    return jax.lax.dynamic_update_slice(ring, rows[None], (layer, 0, 0, 0, 0))


def _ring_put_step(ring, new, position, live, layer):
    """One decode step's write: ring [L, B, R, Hkv, D] ← ``new`` [B, 1, Hkv,
    D] at row ``position[b] mod R`` of each LIVE slot, one in-place update a
    slot (``_write_kv``); a dead slot's ring is left as it is."""
    R = ring.shape[2]
    for b in range(new.shape[0]):
        at = (layer, b, position[b] & (R - 1), 0, 0)
        row = new[b][None, None].astype(ring.dtype)
        if live is not None:
            row = jnp.where(live[b], row, jax.lax.dynamic_slice(ring, at, row.shape))
        ring = jax.lax.dynamic_update_slice(ring, row, at)
    return ring


def _ring_rows_before(ring, start, window: int, layer):
    """The ``window`` rows at positions ``start[b] - window … start[b] - 1``
    out of layer ``layer`` of a ring [L, B, R, Hkv, D] → [B, window, Hkv, D]
    (what lies before position 0 is whatever the ring holds:
    ``window_attention`` masks it)."""
    R = ring.shape[2]
    rows = (start[:, None] - window + jnp.arange(window, dtype=jnp.int32)[None, :]) & (R - 1)
    held = jax.lax.dynamic_index_in_dim(ring, layer, 0, keepdims=False)
    return jnp.take_along_axis(held, rows[:, :, None, None], axis=1)


def _delta_mixer(h, a, cfg: ModelConfig, cache, cache_layer, write_start, n_real, live):
    """A delta layer between its input ``h`` [B, T, D] and what it adds to the
    residual (the module docstring has the mathematics), ``cache_layer`` its
    index among the delta layers. ``cache``: (states [Ld, B, *``state_shape``]
    float32, tails [Ld, B, taps - 1, ``conv_width``]) whole, or None for a
    fresh chunk, which starts from zero and gets its (state, tail) back
    instead, the state packed as the cache holds it. Which rows and steps
    may touch a state is said here alone:
    - of a chunk's T rows the first ``n_real`` [B] count; the pad behind them
      gets β = 0 and g = 0, which leaves S as it is, and the tail kept is the
      last REAL row's;
    - a chunk (T > 1) at position 0 is a new tenant's first: it starts from
      S = 0 and a zero tail whatever the slot holds;
    - a decode step (T == 1) leaves a slot that is not ``live`` as it is.

    → (out [B, T, D], (states, tails) or (state, tail), states updated int32)."""
    B, T, _ = h.shape
    H, dk, dv, taps = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                       cfg.linear_value_head_dim, cfg.linear_conv_kernel)
    f32 = jnp.float32
    step = T == 1 and cache is not None
    pre = jnp.dot(h, a["wqkv"])                                    # q | k | v
    with jax.named_scope("delta.conv"):
        if cache is None:
            tail = jnp.zeros((B, taps - 1, pre.shape[-1]), pre.dtype)
        else:
            states, tails = cache
            tail = jax.lax.dynamic_index_in_dim(tails, cache_layer, 0, keepdims=False)
            if not step:
                fresh = write_start == 0
                tail = jnp.where(fresh[:, None, None], 0, tail)
        rows = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
        u = jax.nn.silu(sum(a["conv"][j].astype(f32) * rows[:, j:j + T].astype(f32)
                            for j in range(taps)))
        q, k, v = (t.reshape(B, T, H, -1) for t in jnp.split(u, (H * dk, 2 * H * dk), axis=-1))
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + _L2_EPS) * dk ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + _L2_EPS)
        # What the next chunk's first taps see: the rows before the last
        # real one, never the pad's.
        kept = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(r, n, taps - 1, 0))(
            rows, n_real)
        if step and live is not None:
            kept = jnp.where(live[:, None, None], kept, tail.astype(kept.dtype))
    with jax.named_scope("delta.gates"):
        g = -jnp.exp(a["a_log"].astype(f32)) * jax.nn.softplus(
            jnp.dot(h, a["wa"], preferred_element_type=f32) + a["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(jnp.dot(h, a["wb"], preferred_element_type=f32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        gate = jax.nn.silu(jnp.dot(h, a["wg"], preferred_element_type=f32)).reshape(B, T, H, dv)
        real = jnp.arange(T, dtype=jnp.int32)[None, :] < n_real[:, None]
        g = jnp.where(real[:, :, None], g, 0.0)
        beta = jnp.where(real[:, :, None], beta, 0.0)
    if step:
        with jax.named_scope("delta.state"):
            o, states = decode_delta_state(
                states, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], cache_layer, live,
                kernel=_attention._kernel_on(),
                interpret=_attention._pallas_decode_mode() == "interpret")
            o = o[:, None]
        updated = jnp.sum(live, dtype=jnp.int32) if live is not None else jnp.int32(B)
    else:
        with jax.named_scope("delta.chunk"):
            # The rule works on [B, H, dk, dv]: the cache's packed layout is
            # undone in front of it and made behind it, a copy each way.
            p = state_heads_a_row(cfg)
            S = jnp.zeros((B, H, dk, dv), f32)
            if cache is not None:
                S = unpack_state(
                    jax.lax.dynamic_index_in_dim(states, cache_layer, 0, keepdims=False), p)
                S = jnp.where(fresh[:, None, None, None], 0.0, S)
            o, S = delta_chunked(q, k, v, g, beta, S)
            S = pack_state(S, p)
            if cache is not None:
                states = jax.lax.dynamic_update_slice_in_dim(states, S[None], cache_layer, 0)
        updated = jnp.int32(0)
    if cache is not None:
        with jax.named_scope("delta.conv"):
            tails = jax.lax.dynamic_update_slice_in_dim(
                tails, kept.astype(tails.dtype)[None], cache_layer, 0)
    with jax.named_scope("delta.out"):
        y = rms_norm(o, a["on"], cfg.rms_norm_eps) * gate
        out = jnp.dot(y.astype(h.dtype).reshape(B, T, H * dv), a["wo"])
    return out, ((states, tails) if cache is not None else (S, kept)), updated


def _mamba_mixer(h, a, cfg: ModelConfig, cache, cache_layer, write_start, n_real, live):
    """A Mamba layer between its input ``h`` [B, T, D] and what it adds to the
    residual (the module docstring has the mathematics), ``cache_layer`` its
    index among the Mamba layers. ``cache``: (states [Lm, B, N, E] float32,
    tails [Lm, B, (K - 1)·E]) whole, or None for a fresh chunk, which starts
    from zero and gets its (state, tail) back instead. Which rows and steps
    may touch a state is said here alone, in ``_delta_mixer``'s words:
    - of a chunk's T rows the first ``n_real`` [B] count; the pad behind them
      gets Δ = 0 (decay 1, input 0), which leaves S as it is, and the tail
      kept is the last REAL row's;
    - a chunk (T > 1) at position 0 is a new tenant's first: it starts from
      S = 0 and a zero tail whatever the slot holds;
    - a decode step (T == 1) leaves a slot that is not ``live`` as it is.

    → (out [B, T, D], (states, tails) or (state, tail), states updated int32)."""
    B, T, _ = h.shape
    E, N, R, K = cfg.mamba_channels, cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    f32 = jnp.float32
    step = T == 1 and cache is not None
    with jax.named_scope("mamba.in"):
        u, z = jnp.split(jnp.dot(h, a["win"]), 2, axis=-1)          # [B, T, E] each
    with jax.named_scope("mamba.conv"):
        taps = a["conv"].astype(f32)
        if step:
            # One row a slot: the taps meet the tail's rows where they lie,
            # side by side along the lanes, and the tail moves up by one.
            states, tails = cache
            tail = jax.lax.dynamic_index_in_dim(tails, cache_layer, 0, keepdims=False)
            pre = taps[K - 1] * u[:, 0].astype(f32) + sum(
                taps[j] * tail[:, j * E:(j + 1) * E].astype(f32) for j in range(K - 1))
            pre = pre[:, None]
            kept = jnp.concatenate([tail[:, E:], u[:, 0].astype(tail.dtype)], axis=1)
            if live is not None:
                kept = jnp.where(live[:, None], kept, tail)
        else:
            if cache is None:
                tail = jnp.zeros((B, K - 1, E), u.dtype)
            else:
                states, tails = cache
                tail = jax.lax.dynamic_index_in_dim(
                    tails, cache_layer, 0, keepdims=False).reshape(B, K - 1, E)
                fresh = write_start == 0
                tail = jnp.where(fresh[:, None, None], 0, tail)
            rows = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
            pre = sum(taps[j] * rows[:, j:j + T].astype(f32) for j in range(K))
            # What the next chunk's first taps see: the rows before the last
            # real one, never the pad's.
            kept = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(r, n, K - 1, 0))(
                rows, n_real).reshape(B, (K - 1) * E)
        if cfg.mamba_conv_bias:
            pre = pre + a["conv_b"].astype(f32)
        u = jax.nn.silu(pre)                                         # u' [B, T, E] f32
    with jax.named_scope("mamba.gates"):
        dbc = jnp.dot(u.astype(h.dtype), a["wx"], preferred_element_type=f32)
        dl, Bv, Cv = jnp.split(dbc, (R, R + N), axis=-1)
        if cfg.mamba_inner_norms:
            dl, Bv, Cv = (rms_norm(t, a[gain], cfg.rms_norm_eps)
                          for t, gain in ((dl, "dtn"), (Bv, "bn"), (Cv, "cn")))
        dt = jax.nn.softplus(jnp.dot(dl.astype(h.dtype), a["wdt"], preferred_element_type=f32)
                             + a["dt_bias"].astype(f32))
        real = jnp.arange(T, dtype=jnp.int32)[None, :] < n_real[:, None]
        dt = jnp.where(real[:, :, None], dt, 0.0)
        A = -jnp.exp(a["a_log"].astype(f32))
    if step:
        with jax.named_scope("mamba.state"):
            y, states = decode_mamba_state(
                states, u[:, 0], dt[:, 0], Bv[:, 0], Cv[:, 0], A, a["d"], cache_layer, live,
                kernel=_attention._kernel_on(),
                interpret=_attention._pallas_decode_mode() == "interpret")
            y = y[:, None]
        updated = jnp.sum(live, dtype=jnp.int32) if live is not None else jnp.int32(B)
    else:
        with jax.named_scope("mamba.scan"):
            S = jnp.zeros((B, N, E), f32)
            if cache is not None:
                S = jax.lax.dynamic_index_in_dim(states, cache_layer, 0, keepdims=False)
                S = jnp.where(fresh[:, None, None], 0.0, S)
            if _attention._kernel_on() and scan_takes(T, N, E):  # whole blocks of tokens
                y, S = mamba_scan(u, dt, Bv, Cv, A, a["d"], S,
                                  interpret=_attention._pallas_decode_mode() == "interpret")
            else:
                y, S = mamba_chunked(u, dt, Bv, Cv, A, a["d"], S)
            if cache is not None:
                states = jax.lax.dynamic_update_slice_in_dim(states, S[None], cache_layer, 0)
        updated = jnp.int32(0)
    if cache is not None:
        with jax.named_scope("mamba.conv"):
            tails = jax.lax.dynamic_update_slice_in_dim(
                tails, kept.astype(tails.dtype)[None], cache_layer, 0)
    with jax.named_scope("mamba.out"):
        out = jnp.dot((y * jax.nn.silu(z.astype(f32))).astype(h.dtype), a["wo"])
    return out, ((states, tails) if cache is not None else (S, kept)), updated


def _stack_layer(x, p, experts, at, kind, cfg: ModelConfig, rope, q_positions,
                 cache, cache_layer, write_start, n_real, mesh, live, attn_fn=None):
    """One block of a model of several kinds: ``kind`` its stack's, ``at`` its
    index in the stack (its experts' too), ``cache_layer`` its index into the
    cache arrays of its attention kind, ``rope`` the program's ``rope_tables``
    (a kind that is not among them is not rotated). ``cache``: the whole
    tuple (the module docstring), or None for a chunk on its own (training, a fresh
    prefill), which gets its rows back instead: (k, v) [B, T, Hkv, D] of a
    full layer, [B, R, Hkv, D] of a window layer, (state, tail) of a delta
    or Mamba layer. ``attn_fn`` overrides a full
    layer's attention over a chunk on its own (training: the einsums), and
    with one a window layer takes the einsum band. ``cfg.norm_placement``:
    ``ln1`` and ``ln2`` in front of the sublayers ("pre") or on their outputs.
    → (x, cache or rows, counts int32 as EXPERT_COUNTERS, and behind them the
    states updated for a model with delta layers)."""
    B, T, _ = x.shape
    attention = kind.split("_")[1]
    pre = cfg.norm_placement == "pre"
    eps = cfg.rms_norm_eps
    if attention in ("delta", "mamba"):
        mixer = _delta_mixer if attention == "delta" else _mamba_mixer
        # delta.conv/gates/chunk/state/out, mamba.in/conv/gates/scan/state/out inside
        with jax.named_scope(f"attn.{attention}"):
            out, kept, updated = mixer(
                rms_norm(x, p["ln1"], eps) if pre else x, p["attn"], cfg,
                None if cache is None else cache[-2:], cache_layer, write_start, n_real, live)
            x = x + (out if pre else rms_norm(out, p["ln1"], eps))
        if cache is not None:
            kept = (*cache[:-2], *kept)
    else:
        updated = jnp.int32(0)
        x, kept = _attention_sublayer(x, p, attention, cfg, rope, q_positions, cache,
                                      cache_layer, write_start, n_real, mesh, live, attn_fn)
    with jax.named_scope("mlp"):  # moe.route/sort/experts/combine/shared inside
        y, counts = expert_ffn(rms_norm(x, p["ln2"], eps) if pre else x, p["mlp"],
                               experts, at, cfg)
        if not pre:
            y = rms_norm(y, p["ln2"], eps)
    if cfg.has_state_layers:
        counts = jnp.concatenate([counts, updated[None]])
    return x + y, kept, counts


def _attention_sublayer(x, p, attention, cfg: ModelConfig, rope, q_positions, cache,
                        cache_layer, write_start, n_real, mesh, live, attn_fn):
    """A window or full layer's attention with its residual: → (x, cache or
    the chunk's rows), as ``_stack_layer`` sets out."""
    B, T, _ = x.shape
    window = cfg.sliding_window if attention == "window" else 0
    pre = cfg.norm_placement == "pre"
    a = p["attn"]
    with jax.named_scope("attn.qkv"):
        h = rms_norm(x, p["ln1"], cfg.rms_norm_eps) if pre else x
        whole = cfg.qk_norm and cfg.qk_norm_whole

        def heads(t, n, gain=None):  # a whole-width norm comes before the split
            if whole and gain:
                with jax.named_scope("attn.qk_norm"):
                    t = rms_norm(t, a[gain], cfg.rms_norm_eps)
            return t.reshape(B, T, n, cfg.head_dim)

        q = heads(jnp.dot(h, a["wq"]), cfg.num_heads, "qn")
        k = heads(jnp.dot(h, a["wk"]), cfg.num_kv_heads, "kn")
        v = heads(jnp.dot(h, a["wv"]), cfg.num_kv_heads)
    if cfg.qk_norm and not whole:
        with jax.named_scope("attn.qk_norm"):
            q = rms_norm(q, a["qn"], cfg.rms_norm_eps)
            k = rms_norm(k, a["kn"], cfg.rms_norm_eps)
    if attention in rope:
        cos, sin = rope[attention]
        with jax.named_scope("attn.rope"):
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    spare = cache_kv_heads(cfg) - cfg.num_kv_heads
    if spare:  # zero heads up to the cache's count, a whole group of queries each
        def more(t, n):
            return jnp.pad(t, ((0, 0), (0, 0), (0, n), (0, 0)))

        q = more(q, spare * (cfg.num_heads // cfg.num_kv_heads))
        k, v = more(k, spare), more(v, spare)

    with jax.named_scope("attn.decode" if T == 1 else "attn.prefill"):
        if window and cache is None:
            with jax.named_scope("attn.window"):
                attn = (_attention.band_attention(q, k, v, None, None, None, window)
                        if attn_fn else
                        _attention.window_attention(q, k, v, None, None, None, window, mesh))
            # A fresh chunk starts at position 0: its rows as a ring that
            # held nothing takes them.
            kept = tuple(_ring_image(rows, jnp.zeros((B,), jnp.int32), n_real,
                                     ring_rows(cfg))[0] for rows in (k, v))
        elif window and T == 1:
            *full, rk, rv = cache
            with jax.named_scope("kv.update"):
                rk = _ring_put_step(rk, k, write_start, live, cache_layer)
                rv = _ring_put_step(rv, v, write_start, live, cache_layer)
            with jax.named_scope("attn.window"):
                attn = _attention.ring_decode_attention(q, rk, rv, q_positions, cache_layer, live, window)
            kept = (*full, rk, rv)
        elif window:
            *full, rk, rv = cache
            with jax.named_scope("attn.window"):
                attn = _attention.window_attention(
                    q, k, v, _ring_rows_before(rk, write_start, window, cache_layer),
                    _ring_rows_before(rv, write_start, window, cache_layer),
                    write_start, window, mesh)
            with jax.named_scope("kv.update"):
                rk = _ring_put(rk, k, write_start, n_real, cache_layer)
                rv = _ring_put(rv, v, write_start, n_real, cache_layer)
            kept = (*full, rk, rv)
        elif cache is None:
            with jax.named_scope("attn.full"):
                attn = (attn_fn(q, k, v, q_positions) if attn_fn else
                        gqa_attention(q, k, v, q_positions, mesh=mesh))
            kept = (k, v)
        else:
            from omnia_tpu.models.llama import _write_kv  # (it imports this module)

            ck, cv, *others = cache
            with jax.named_scope("kv.update"):
                ck = _write_kv(ck, k, write_start, cache_layer)
                cv = _write_kv(cv, v, write_start, cache_layer)
            with jax.named_scope("attn.full"):
                attn = gqa_attention(q, ck, cv, q_positions, mesh=mesh,
                                     layer=cache_layer, live=live)
            kept = (ck, cv, *others)
    with jax.named_scope("attn.out"):
        out = jnp.dot(attn[:, :, :cfg.num_heads].reshape(B, T, -1) if spare
                      else attn.reshape(B, T, -1), a["wo"])
        x = x + (out if pre else rms_norm(out, p["ln1"], cfg.rms_norm_eps))
    return x, kept


def _run_stacks(params, cfg: ModelConfig, x, rope, q_positions, cache, write_start,
                row, mesh, live, attn_fn=None):
    """Every layer of a model of several kinds, a scan a run (``_runs``)
    under ``stack.<kind>``; ``rope`` is ``rope_tables``, of which a layer
    takes its kind's. With a cache (the whole tuple) it is the carry and
    comes back; without one the chunk's rows come back in its place, an
    array for each cache array ([L of the kind, B, T or R, Hkv, D]; a delta
    or Mamba layer's state and tail). → (x, cache or chunks, counts summed over the
    layers: ``_stack_layer``'s)."""
    B, T, _ = x.shape
    n_real = jnp.broadcast_to(T if row is None else row + 1, (B,)).astype(jnp.int32)
    counts = jnp.zeros((len(EXPERT_COUNTERS) + cfg.has_state_layers,), jnp.int32)
    chunks = {"full": [], "window": [], "delta": [], "mamba": []}
    for stack, kind, first, length, cache_first in _runs(cfg):
        layers = params["layers"][stack]
        scanned, experts = (unstack_experts(layers) if kind.startswith("sparse")
                            else (layers, None))

        def body(carry, i, scanned=scanned, experts=experts, kind=kind, first=first,
                 cache_first=cache_first):
            x, cache, counts = carry
            # The layer's leaves where they lie in the stack, as a scan over
            # the stack itself would read them: a run is part of a stack.
            p = jax.tree_util.tree_map(
                lambda leaf: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False), scanned)
            x, kept, c = _stack_layer(
                x, p, experts, i, kind, cfg, rope, q_positions, cache,
                cache_first + i - first, write_start, n_real, mesh, live, attn_fn)
            return ((x, kept, counts + c), None) if cache is not None else (
                (x, None, counts + c), kept)

        with jax.named_scope(f"stack.{kind}"):
            (x, cache, counts), rows = jax.lax.scan(
                body, (x, cache, counts), first + jnp.arange(length, dtype=jnp.int32))
        if rows is not None:
            chunks[kind.split("_")[1]].append(rows)
    if cache is not None:
        return x, cache, counts

    def whole(kind, rows):  # the runs' rows, in the order of the kind's cache
        if len(rows) == 1:
            return rows[0]
        if rows:
            return tuple(jnp.concatenate(each, axis=0) for each in zip(*rows))
        if kind in ("delta", "mamba"):  # a cut model without a layer of the kind: no layers
            return (jnp.zeros((0, B, *state_shape(cfg)), jnp.float32),
                    jnp.zeros((0, B, *tail_shape(cfg)), x.dtype))
        shape = (0, B, ring_rows(cfg) if kind == "window" else T,
                 cache_kv_heads(cfg), cfg.head_dim)
        return jnp.zeros(shape, x.dtype), jnp.zeros(shape, x.dtype)

    have = (["full"] + ["window"] * cfg.has_window_layers
            + [_state_kind(cfg)] * cfg.has_state_layers)
    if have == ["full"]:
        return x, whole("full", chunks["full"]), counts
    return x, tuple(a for kind in have for a in whole(kind, chunks[kind])), counts
