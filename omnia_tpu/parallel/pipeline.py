"""Pipeline parallelism: microbatched layer-pipelining over a "pp" mesh axis.

Why pp exists (SURVEY §2.13): tensor parallelism's per-layer all-reduces
need ICI bandwidth — across hosts they ride DCN and serialize every layer.
The standard cross-host cut for a 70B+ flagship is to split the *layer
stack* instead: each pp stage holds L/pp contiguous layers, activations
cross the host boundary once per stage per microbatch ([B/M, T, D] bytes,
thousands of times less than TP's per-layer all-reduce volume over the
same link), and microbatching keeps every stage busy outside the fill/
drain bubble (GPipe schedule; bubble fraction = (S-1)/(M+S-1)).
docs/serving.md carries the roofline arithmetic.

TPU-first shape of the implementation:

- Params stay the stacked-[L] pytree the rest of the framework uses;
  ``llama.param_specs_pp`` shards the leading layer axis over "pp", so a
  stage's local shard is just layers [s·L/S, (s+1)·L/S) — no per-stage
  parameter surgery, checkpoints stay layout-identical.
- ONE ``shard_map`` region, manual over "pp" only (``axis_names={"pp"}``):
  "dp"/"tp" stay automatic, so GSPMD still inserts the tensor-parallel
  collectives *inside* each stage — pp composes with dp×tp rather than
  re-implementing them.
- The schedule is a differentiable ``lax.scan`` over M+S-1 ticks; each
  tick runs the local stage (itself a ``lax.scan`` over local layers) and
  rotates activations one stage forward via ``ppermute`` — the same
  neighbor-hop collective the ring-attention path uses, and the only
  cross-stage communication in the program.
- Static shapes throughout: microbatch index selection and output/KV
  capture are clamped ``dynamic_index/update`` + masks, never Python
  control flow on traced values.

The reference has no analog (its scaling is K8s replicas of stateless
relays, internal/controller/autoscaling.go); pp is part of the mesh
vocabulary replacing that (mesh.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from omnia_tpu.models.config import ModelConfig


def _stage_scan(layers_local, x, cfg, cos, sin, qpos):
    """Run this stage's local layer shard over activations x [mb, T, D]."""
    from omnia_tpu.models.llama import _layer
    from omnia_tpu.ops.attention import einsum_attention

    def body(x, p):
        # Under the "pp" mesh, and differentiated through in training: the
        # einsums whatever the kernel route (ops/attention.py).
        x, k, v = _layer(x, p, cfg, cos, sin, qpos, None, None, None,
                         attn_fn=einsum_attention)
        return x, (k, v)

    return lax.scan(body, x, layers_local)


def _pp_local(layers_local, x_mb, pos_mb, cfg: ModelConfig, S: int, M: int):
    """Per-device pipeline schedule (manual over "pp").

    layers_local: layer pytree, leading axis L/S (this stage's layers)
    x_mb: [M, mb, T, D] embedded microbatches (same on every stage)
    pos_mb: [M, mb, T] int32 positions
    Returns (out [M, mb, T, D] — final-stage activations, replicated via
    psum; k/v [L/S, M·mb, T, Hkv, Dh] — this stage's KV chunk).
    """
    from omnia_tpu.ops.rope import rope_cos_sin

    s = lax.axis_index("pp")
    mb, T = x_mb.shape[1], x_mb.shape[2]

    def tick(carry, t):
        state, out, kbuf, vbuf = carry
        # Stage s works on microbatch t-s at tick t (clamped while the
        # pipeline fills/drains; the mask below voids those ticks).
        mb_idx = jnp.clip(t - s, 0, M - 1)
        valid = (t - s >= 0) & (t - s < M)
        inject = lax.dynamic_index_in_dim(x_mb, mb_idx, 0, keepdims=False)
        x_in = jnp.where(s == 0, inject, state)
        qpos = lax.dynamic_index_in_dim(pos_mb, mb_idx, 0, keepdims=False)
        cos, sin = rope_cos_sin(
            qpos, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
        )
        y, (k, v) = _stage_scan(layers_local, x_in, cfg, cos, sin, qpos)
        # Capture this stage's KV rows for the microbatch it just ran.
        kbuf, vbuf = jax.tree.map(
            lambda buf, new: jnp.where(
                valid,
                lax.dynamic_update_index_in_dim(buf, new, mb_idx, 0),
                buf,
            ),
            (kbuf, vbuf), (k, v),
        )
        # The LAST stage's activations are the model output.
        out = jnp.where(
            valid & (s == S - 1),
            lax.dynamic_update_index_in_dim(out, y, mb_idx, 0),
            out,
        )
        # Rotate activations one stage forward (stage S-1's output is
        # dropped — there is no (S-1)→0 edge in a GPipe schedule).
        state = lax.ppermute(y, "pp", [(i, i + 1) for i in range(S - 1)])
        return (state, out, kbuf, vbuf), None

    Ll = jax.tree.leaves(layers_local)[0].shape[0]
    state0 = jnp.zeros_like(x_mb[0])
    out0 = jnp.zeros_like(x_mb)
    kv_shape = (M, Ll, mb, T, cfg.num_kv_heads, cfg.head_dim)
    kbuf0 = jnp.zeros(kv_shape, x_mb.dtype)
    vbuf0 = jnp.zeros(kv_shape, x_mb.dtype)
    (_, out, kbuf, vbuf), _ = lax.scan(
        tick, (state0, out0, kbuf0, vbuf0), jnp.arange(M + S - 1)
    )
    # Replicate the final-stage output across stages (out is zeros on
    # stages < S-1, so the psum is a select, not a sum). The reduction
    # runs in f32: XLA:CPU miscompiles a bf16 cross-replica all-reduce
    # under partial-manual shard_map ("Invalid binary instruction opcode
    # copy" fatal), and f32 is what the logits head wants anyway.
    out = lax.psum(
        jnp.where(s == S - 1, out, jnp.zeros_like(out)).astype(jnp.float32),
        "pp",
    ).astype(x_mb.dtype)
    # [M, Ll, mb, T, H, D] -> [Ll, M*mb, T, H, D] (microbatches back to batch)
    def unmb(buf):
        return jnp.moveaxis(buf, 0, 1).reshape(Ll, M * mb, T, *buf.shape[4:])

    return out, unmb(kbuf), unmb(vbuf)


def pipeline_forward(
    params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    q_positions: jnp.ndarray,
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
):
    """Pipelined fresh-prefill / training forward over the mesh's "pp" axis.

    Contract matches ``llama.forward_prefill``: tokens/q_positions int32
    [B, T] → (logits [B, T, V] f32, k_chunk, v_chunk [L, B, T, Hkv, Dh])
    — so the serving engine can use it as a drop-in prefill program and
    the trainer can differentiate through it (the tick schedule is a
    ``lax.scan``; every collective is differentiable).

    B must divide by num_microbatches (default: pp size, the smallest M
    that keeps every stage busy at steady state). Params must be sharded
    with ``llama.param_specs_pp`` so each stage holds its layer shard.
    """
    from omnia_tpu.models.llama import _logits

    S = mesh.shape["pp"]
    M = num_microbatches or S
    B, T = tokens.shape
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    if cfg.num_layers % S:
        raise ValueError(f"{cfg.num_layers} layers not divisible by pp={S}")

    x = params["embed"][tokens]  # [B, T, D]
    mb = B // M
    x_mb = x.reshape(M, mb, T, x.shape[-1])
    pos_mb = q_positions.reshape(M, mb, T)

    from omnia_tpu.parallel.compat import shard_map

    fn = shard_map(
        functools.partial(_pp_local, cfg=cfg, S=S, M=M),
        mesh,
        in_specs=(P("pp"), P(), P()),
        out_specs=(P(), P("pp"), P("pp")),
        manual_axes={"pp"},
    )
    out, k_chunk, v_chunk = fn(params["layers"], x_mb, pos_mb)
    out = out.reshape(B, T, -1)
    return _logits(params, cfg, out), k_chunk, v_chunk
