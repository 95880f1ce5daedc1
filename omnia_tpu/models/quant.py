"""int8 weight quantization for serving flagship models on one chip.

The reference platform never holds model weights — a Provider CR names a
model and a SaaS API owns the capacity (reference
api/v1alpha1/provider_types.go:322-412). Here HBM capacity is ours to
manage: a v5e chip has 16 GB, Llama-3-8B is ~16 GB in bf16, so the
north-star model only fits single-chip with 8-bit weights.

Two modes, both symmetric per-output-channel:

- ``int8`` (W8A16, weight-only): weights stored int8 + f32 scale per
  output channel; the matmul runs as a mixed bf16×int8 ``dot_general``
  and the scale applies to the *output* — valid because a per-output-
  channel scale commutes with the contraction:
  ``h @ (q * s[None, :]) == (h @ q) * s[None, :]``. Near-lossless
  (round-trip error ~0.4% per weight); HBM weight traffic halves.
- ``int8-dynamic`` (W8A8, dynamic activation quant): activations are
  quantized per token (row absmax) on the fly and the matmul runs
  int8×int8 → int32 on the MXU's double-rate int8 path. Measured on the
  attached v5e: 1.59× faster than the bf16 matmul at decode batch sizes
  (95.6 µs → 60.3 µs for the 4096×14336 MLP projection). Accuracy is
  SmoothQuant-class W8A8 — fine for serving, looser than weight-only.

Quantized leaves are ``{"w8"|"w8d": int8 [..., K, N], "s": f32 [..., N]}``
dicts (the key encodes the mode, so dispatch in ``qdot`` is pytree-
structural and trace-time — no flags threaded through the forward).
Layer-stacked weights quantize per (layer, channel); ``lax.scan`` carries
the dict subtree and slices both members per layer. MoE experts are not
quantized (Mixtral-8x7B exceeds one chip even at int8; EP sharding is the
path for it — parallel/mesh.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

QUANT_MODES = ("int8", "int8-dynamic")

_MODE_KEY = {"int8": "w8", "int8-dynamic": "w8d"}


def _key_for(mode: str) -> str:
    if mode not in _MODE_KEY:
        raise ValueError(f"unknown quant mode {mode!r}; have {sorted(_MODE_KEY)}")
    return _MODE_KEY[mode]


def is_quantized(w) -> bool:
    """True if ``w`` is a quantized-weight dict (either mode)."""
    return isinstance(w, dict) and ("w8" in w or "w8d" in w)


def _probe(params):
    """The query projection the int8 path would have quantized, or None:
    ``params["layers"]`` is one tree or a sequence of stacks (models/mla.py)."""
    layers = params.get("layers", {})
    stacks = layers if isinstance(layers, (list, tuple)) else (layers,)
    return next((s["attn"]["wq"] for s in stacks if "wq" in s.get("attn", {})), None)


def params_quantized(params) -> bool:
    """True if the param pytree already carries quantized matmul weights."""
    return is_quantized(_probe(params))


def detect_mode(params) -> Optional[str]:
    """The quant mode a pre-quantized tree was built with (None if dense)."""
    wq = _probe(params)
    if not is_quantized(wq):
        return None
    return "int8" if "w8" in wq else "int8-dynamic"


# ---------------------------------------------------------------------------
# Quantize
# ---------------------------------------------------------------------------


def quantize_weight(w, mode: str = "int8"):
    """w [..., K, N] → quantized dict; scales are per output channel N
    (absmax over the contraction axis K, symmetric, int8 in [-127, 127])."""
    key = _key_for(mode)
    wf = jnp.asarray(w, jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2), 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / s[..., None, :]), -127, 127).astype(jnp.int8)
    return {key: q, "s": s}


def quantize_np(w: np.ndarray, mode: str = "int8"):
    """Host (numpy) twin of ``quantize_weight`` — the checkpoint loader
    quantizes each stacked tensor on host before device_put, so the
    full-precision tree never lands in HBM."""
    key = _key_for(mode)
    wf = np.asarray(w, np.float32)
    s = (np.maximum(np.max(np.abs(wf), axis=-2), 1e-8) / 127.0).astype(np.float32)
    q = np.clip(np.rint(wf / s[..., None, :]), -127, 127).astype(np.int8)
    return {key: q, "s": s}


def _map_quant_leaves(tree: dict, is_moe: bool, fn):
    """Apply ``fn`` to the matmul-weight leaves the int8 path covers:
    attention projections, dense-MLP projections, and lm_head. Embedding
    (gather, and tied-logits transpose), norms, and MoE routers/experts
    stay full precision."""
    out = dict(tree)
    layers = dict(tree["layers"])
    layers["attn"] = {k: fn(v) for k, v in tree["layers"]["attn"].items()}
    if not is_moe:
        layers["mlp"] = {k: fn(v) for k, v in tree["layers"]["mlp"].items()}
    out["layers"] = layers
    if "lm_head" in tree:
        out["lm_head"] = fn(tree["lm_head"])
    return out


def quantize_params(params, cfg, mode: str = "int8"):
    """Quantize a full-precision param pytree (models/llama.py layout).

    Intended for models small enough that both trees coexist in memory;
    flagship checkpoints should quantize through the loader instead
    (models/checkpoint.py ``load_params(quant=...)``) or init directly
    quantized (``init_params_quantized``)."""
    _key_for(mode)
    return _map_quant_leaves(
        params, cfg.is_moe, lambda w: quantize_weight(w, mode)
    )


def quantize_param_specs(specs, cfg, mode: str = "int8"):
    """Transform the ``llama.param_specs`` pytree to match quantized
    params: the int8 tensor keeps the weight's spec; the scale drops the
    contraction axis (index ndim-2) from it.

    Specs must be FULL-LENGTH (one entry per array dim). A shortened
    PartitionSpec is legal in JAX (trailing dims implicitly replicated)
    but would silently misalign the contraction/output slicing below, so
    it is rejected here (ADVICE r2). Quantized leaves are stacked
    [L, in, out] (ndim 3) everywhere except lm_head [in, out] (ndim 2)."""
    key = _key_for(mode)

    def make_leaf(expect_ndim: int):
        def leaf(spec):
            entries = tuple(spec)
            if len(entries) != expect_ndim:
                raise ValueError(
                    f"quantized weight spec {spec} has {len(entries)} entries, "
                    f"expected {expect_ndim}; shortened PartitionSpecs would "
                    "misalign the scale's contraction-axis slicing"
                )
            return {key: spec, "s": P(*entries[: len(entries) - 2], entries[-1])}

        return leaf

    stacked = {k: v for k, v in specs.items() if k != "lm_head"}
    out = _map_quant_leaves(stacked, cfg.is_moe, make_leaf(3))
    if "lm_head" in specs:
        out["lm_head"] = make_leaf(2)(specs["lm_head"])
    return out


def init_params_quantized(cfg, key: jax.Array, mode: str = "int8", dtype=jnp.bfloat16):
    """Random params born quantized (no full-precision intermediate — for
    flagship sizes the bf16 tree would not fit beside the int8 one).
    Mirrors ``llama.init_params`` structure; scales are set so the
    dequantized std matches init_params' 0.02."""
    if cfg.is_moe:
        raise ValueError("int8 quantization does not cover MoE experts")
    qkey = _key_for(mode)
    L, D, F, V = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden_size, cfg.vocab_size
    keys = iter(jax.random.split(key, 16))

    def normal(key, shape, std=0.02):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * std).astype(dtype)

    def qleaf(key, shape, std=0.02):
        # uniform int8 in [-127, 127] has std ≈ 127/√3; scale recovers `std`.
        q = jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)
        s = jnp.full(shape[:-2] + shape[-1:], std * (3.0**0.5) / 127.0, jnp.float32)
        return {qkey: q, "s": s}

    wo_std = 0.02 / (2 * L) ** 0.5
    params = {
        "embed": normal(next(keys), (V, D)),
        "layers": {
            "ln1": jnp.ones((L, D), dtype=dtype),
            "ln2": jnp.ones((L, D), dtype=dtype),
            "attn": {
                "wq": qleaf(next(keys), (L, D, cfg.q_dim)),
                "wk": qleaf(next(keys), (L, D, cfg.kv_dim)),
                "wv": qleaf(next(keys), (L, D, cfg.kv_dim)),
                "wo": qleaf(next(keys), (L, cfg.q_dim, D), std=wo_std),
            },
            "mlp": {
                "wg": qleaf(next(keys), (L, D, F)),
                "wu": qleaf(next(keys), (L, D, F)),
                "wd": qleaf(next(keys), (L, F, D), std=wo_std),
            },
        },
        "final_norm": jnp.ones((D,), dtype=dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = qleaf(next(keys), (D, V))
    return params


# ---------------------------------------------------------------------------
# Quantized matmul
# ---------------------------------------------------------------------------


def qdot(h, w):
    """``jnp.dot`` that accepts quantized-weight dicts transparently.

    h: [..., K] activations; w: [K, N] array or quantized dict. The
    forward pass calls this at every projection site, so a single param
    pytree swap turns quantization on — no model-code branching.
    """
    if not is_quantized(w):
        return jnp.dot(h, w)
    s = w["s"]
    if "w8" in w:
        # W8A16: mixed-precision dot; per-output-channel scale applied to
        # the output (commutes with the contraction).
        q = w["w8"]
        out = lax.dot_general(
            h, q,
            (((h.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (out * s).astype(h.dtype)
    # W8A8: dynamic per-token activation quant → int8×int8 MXU path.
    q = w["w8d"]
    amax = jnp.max(jnp.abs(h.astype(jnp.float32)), axis=-1, keepdims=True)
    s_in = jnp.maximum(amax, 1e-8) / 127.0
    hq = jnp.clip(jnp.round(h.astype(jnp.float32) / s_in), -127, 127).astype(jnp.int8)
    out = lax.dot_general(
        hq, q,
        (((h.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (out.astype(jnp.float32) * s_in * s).astype(h.dtype)


def validate_mode(mode: Optional[str]) -> Optional[str]:
    """None passthrough + mode-string validation (EngineConfig surface)."""
    if mode is None:
        return None
    _key_for(mode)
    return mode
