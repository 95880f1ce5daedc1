"""Published peaks and the least time of a decode step. Jax-free.

The least time of one decode step is bound by HBM: every weight the step
reads once, plus the cached rows of the live contexts once. The bytes come
from shapes, never from the program: by the module under `decode_bytes/`
that the configuration names (`"decode_bytes"` in its file; the default
is `harness/manifest.py`'s), where `m` is that file's keys.
"""

from __future__ import annotations

import json
import os

from harness.manifest import load_decode_bytes

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


def kv_bytes_per_token(m: dict) -> int:
    """Cache bytes of one live token over all layers, by the byte-count
    module the configuration names."""
    return load_decode_bytes(m).kv_bytes_per_token(m)


def decode_step_floor_s(m: dict, live_context_tokens: float, chips: int,
                        hbm_bytes_per_s: float) -> float:
    """Least seconds for one decode step: (weights once + the live tokens'
    cached rows once) / (chips x HBM bandwidth). Bound by HBM."""
    counts = load_decode_bytes(m)
    total = counts.decode_weight_bytes(m) + live_context_tokens * counts.kv_bytes_per_token(m)
    return total / (chips * hbm_bytes_per_s)
