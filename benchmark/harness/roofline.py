"""Published peaks and the bytes a decode step must move. Jax-free.

The least time of one decode step is bound by HBM: every weight the step
reads once, plus the K and V rows of the live contexts once. The bytes come
from shapes here, never from the program.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(have {sorted(table)}); add its published peaks with a source"
        )
    return table[device_kind]


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Weight bytes one decode step reads, over all chips. `m` holds the
    model's sizes under the configuration file's own keys. The embedding
    table is gathered (a row per slot), not streamed, so it is left out;
    the output head is read whole. A Mixtral decode step through
    `moe_dense` reads every expert, and with 32 slots x top-2 of 8 experts
    nearly every expert is needed anyway, so all experts count."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hd = m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    experts = m.get("num_local_experts", 0)
    mlp = (experts or 1) * 3 * d * f + d * experts
    per_layer = attn + mlp + 2 * d
    head = d * m["vocab_size"]  # tied or not, one [D, V] table is read whole
    return (m["num_hidden_layers"] * per_layer + head + d) * itemsize


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """K and V bytes of one cached token over all layers."""
    return (m["num_hidden_layers"] * m["num_key_value_heads"] * m["head_dim"]
            * 2 * itemsize)


def decode_step_floor_s(m: dict, live_context_tokens: float, chips: int,
                        hbm_bytes_per_s: float) -> float:
    """Least seconds for one decode step: (weights once + live K/V rows
    once) / (chips x HBM bandwidth). Bound by HBM."""
    total = decode_weight_bytes(m) + live_context_tokens * kv_bytes_per_token(m)
    return total / (chips * hbm_bytes_per_s)
