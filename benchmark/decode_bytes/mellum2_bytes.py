"""What a decode step of the Mellum 2 configuration (`omnia_tpu/models/llama.py`
with sparse stacks only; `mellum2-12b-a2p5b`) must move, from shapes.
Jax-free. `m` holds the model's sizes under the configuration file's own
keys: every one of the `num_hidden_layers` holds all `num_experts` routed
experts and no other FFN; `num_full_attention_layers` cache whole contexts
and `num_window_attention_layers` a ring of `sliding_window` rows.
"""

from __future__ import annotations


def _attention_params(m: dict) -> int:
    d, dh = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * dh, m["num_key_value_heads"] * dh
    return d * q + 2 * d * kv + q * d + 2 * dh      # wq, wk, wv, wo, qn, kn


def expert_bytes(m: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Weight bytes one decode step reads: attention, the norms, the router
    and all `num_experts` experts of every layer; the final norm and the
    head. The embedding table is gathered (a row a slot), not streamed, so
    it is left out.

    Every expert is counted: the chip holds them all, and a step's 48 live
    tokens choosing 8 of 64 leave one out with probability (1 - 8/64)^48 =
    0.16 %, so 63.9 of 64 are hit a layer a step. The experts' own roofline
    counts those really hit (`moe_experts_hit`)."""
    d = m["hidden_size"]
    layer = ((_attention_params(m) + 2 * d + d * m["num_experts"]) * itemsize
             + m["num_experts"] * expert_bytes(m, itemsize))
    head = (d * m["vocab_size"] + d) * itemsize
    return int(m["num_hidden_layers"] * layer + head)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The cached K and V rows of one live token over the FULL layers only:
    the readers that multiply this by the live context tokens
    (`batch.decode_step_roofline`, `batch.decode_gqa_attention_roofline`)
    would count a window layer's rows for every token of the context, where
    its ring holds `sliding_window` of them whatever the context. So the
    window layers' rows (up to 6 x 1024 a slot, against 2 x 2,200 in the two
    full layers at the cell's mean context: 0.6 GB of a step's 8.3 GB, 7 %)
    are left out here, and the step's roofline reads that much low. The
    full layers' kernel reads exactly these; the window layers' has a reader
    of its own, which counts the ring rows its kernel spans
    (`window_row_bytes` times the program's `decode_window_rows`)."""
    return m["num_full_attention_layers"] * full_row_bytes(m, itemsize)


def full_row_bytes(m: dict, itemsize: int = 2) -> int:
    """K and V of one cached row of one layer."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def window_row_bytes(m: dict, itemsize: int = 2) -> int:
    """K and V of one ring row a slot over every window layer: what
    `decode_window_attention` reads for one row of the program's counter
    `decode_window_rows` (counted a window layer: the ring's blocks up to a
    slot's position, the whole ring once the position has passed it)."""
    return m["num_window_attention_layers"] * full_row_bytes(m, itemsize)


def decode_attention_row(m: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of either decode kernel for one live cached row
    of one layer: every query head scores the row (head_dim multiply-adds)
    and weighs its value (head_dim more); K and V are read once for the
    H / Hkv heads that share them."""
    return {"flops": 2 * m["num_attention_heads"] * 2 * m["head_dim"],
            "bytes": full_row_bytes(m, itemsize)}
