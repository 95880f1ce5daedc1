"""What a decode step of the K-EXAONE family (`omnia_tpu/models/llama.py`
with layers of several kinds; configuration `k-exaone-236b-a23b`) must move,
from shapes. Jax-free. `m` holds the model's sizes under the configuration
file's own keys: of the `num_hidden_layers`, `first_k_dense_replace` are dense
and the rest hold `num_experts` routed experts (this chip's share of
`num_experts_source`); `num_full_attention_layers` cache whole contexts and
`num_window_attention_layers` a ring of `sliding_window` rows.
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
    """Weight bytes one decode step reads: attention and the norms of every
    layer, the dense layers' FFN, and of every sparse layer the router with
    its selection bias (float32), the shared expert and the held experts
    that a token chose; the final norm and the head's slice. The embedding
    table is gathered (a row a slot), not streamed, so it is left out.

    A step reads only the experts that got a token, and here that is not
    nearly all of them: the file's `expected_experts_hit` says how many of
    the held ones a step's live tokens hit a layer (32 tokens choosing 8 of
    128: 1 - (1 - 8/128)^32 = 87.3 % of 16 held, 13.97), and that many are
    counted. Counting all 16 would put `batch.decode_step_roofline` a tenth
    too high. The experts' own roofline counts those really hit
    (`moe_experts_hit`)."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    layers, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    every = (_attention_params(m) + 2 * d) * itemsize
    dense_ffn = 3 * d * m["intermediate_size"] * itemsize
    sparse_ffn = (m["expected_experts_hit"] * expert_bytes(m, itemsize)
                  + m.get("num_shared_experts", 0) * 3 * d * f * itemsize
                  + d * m["num_experts_source"] * itemsize + m["num_experts_source"] * 4)
    head = (d * m["vocab_size"] + d) * itemsize
    return int(layers * every + dense * dense_ffn + (layers - dense) * sparse_ffn + head)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The cached K and V rows of one live token over the FULL layers only:
    the readers that multiply this by the live context tokens
    (`batch.decode_step_roofline`, `batch.decode_gqa_attention_roofline`)
    would count a window layer's rows for every token of the context, where
    its ring holds `sliding_window` of them whatever the context. So the
    window layers' rows (at most 128 a slot a layer: 4 x 128 of 1 x 6144 + 4
    x 128 rows at a context of 6 k tokens, 8 % of the bytes) are left out,
    and the step's roofline reads that much low. The full layers' kernel
    reads exactly these; the window layers' has a reader of its own."""
    return m["num_full_attention_layers"] * full_row_bytes(m, itemsize)


def full_row_bytes(m: dict, itemsize: int = 2) -> int:
    """K and V of one cached row of one layer."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def window_row_bytes(m: dict, itemsize: int = 2) -> int:
    """K and V of one ring row a slot over every window layer: what
    `decode_window_attention` reads for one row of the program's counter
    `decode_window_rows` (counted a window layer)."""
    return m["num_window_attention_layers"] * full_row_bytes(m, itemsize)


def decode_attention_row(m: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of either decode kernel for one live cached row
    of one layer: every query head scores the row (head_dim multiply-adds)
    and weighs its value (head_dim more); K and V are read once for the
    H / Hkv heads that share them."""
    return {"flops": 2 * m["num_attention_heads"] * 2 * m["head_dim"],
            "bytes": full_row_bytes(m, itemsize)}
