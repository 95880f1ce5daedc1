"""What a decode step of the Olmo-Hybrid configuration (`omnia_tpu/models/
llama.py` with linear-attention stacks; `olmo-hybrid-7b`) must move, from
shapes. Jax-free. `m` holds the model's sizes under the configuration file's
own keys: of the `num_hidden_layers`, `num_linear_attention_layers` have the
gated delta rule (a recurrent state a slot, `linear_num_key_heads` heads of
`linear_key_head_dim` x `linear_value_head_dim`) and
`num_full_attention_layers` cache K and V rows of whole contexts; every
layer's FFN is a dense SwiGLU of `intermediate_size`.
"""

from __future__ import annotations


def _key(m: dict, key: str):
    """A key of the file, or of its `assumed` where the source lacks it."""
    return m[key] if key in m else m["assumed"][key]


def linear_attention_params(m: dict) -> int:
    d, h = m["hidden_size"], m["linear_num_key_heads"]
    dk, dv, taps = m["linear_key_head_dim"], m["linear_value_head_dim"], m["linear_conv_kernel_dim"]
    width = h * (2 * dk + dv)                          # q | k | v
    return ((d + taps) * width                         # wqkv, conv
            + 2 * d * h + 2 * h                        # wa, wb, a_log, dt_bias
            + d * h * dv + dv + h * dv * d)            # wg, on, wo


def full_attention_params(m: dict) -> int:
    d, dh = m["hidden_size"], _key(m, "head_dim")
    q, kv = m["num_attention_heads"] * dh, m["num_key_value_heads"] * dh
    return d * q + 2 * d * kv + q * d + q + kv         # wq, wk, wv, wo, qn, kn


def state_bytes(m: dict) -> int:
    """The recurrent state of one slot in one linear-attention layer, read
    once and written once by a decode step: heads x key width x value width
    float32 (the configuration's `assumed.state_dtype`), twice. What
    `decode_delta_state` moves for one of the program's counter
    `decode_delta_slots` (counted a layer a step). The step vectors beside
    it (five rows a head) are a twentieth of it and left out; so is what the
    chip's tiles add to 192 lanes (a state's rows are laid out in 256)."""
    return (2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
            * m["linear_value_head_dim"] * 4)


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes one decode step reads that do not grow with the live context:
    attention of both kinds, the two norms and the SwiGLU of every layer, the
    final norm and the head; AND the linear-attention layers' states of
    `expected_live_slots` slots, read and written (`state_bytes`): a state
    costs the same at any context, so `harness/roofline.py`, which multiplies
    `kv_bytes_per_token` by the live context tokens, cannot carry it
    (`kimi_linear_bytes.py`'s precedent). The embedding table is gathered (a
    row a slot), not streamed, and left out."""
    d = m["hidden_size"]
    layers = m["num_hidden_layers"]
    attention = (m["num_linear_attention_layers"] * linear_attention_params(m)
                 + m["num_full_attention_layers"] * full_attention_params(m))
    weights = (attention + layers * (2 * d + 3 * d * m["intermediate_size"])
               + d * m["vocab_size"] + d) * itemsize
    states = m["expected_live_slots"] * m["num_linear_attention_layers"] * state_bytes(m)
    return int(weights + states)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The cached K and V rows of one live token over the FULL layers only
    (30,720 B at two of them): the linear-attention layers cache no row."""
    return m["num_full_attention_layers"] * full_row_bytes(m, itemsize)


def full_row_bytes(m: dict, itemsize: int = 2) -> int:
    """K and V of one cached row of one layer."""
    return 2 * m["num_key_value_heads"] * _key(m, "head_dim") * itemsize


def decode_attention_row(m: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of the full layers' decode kernel for one live
    cached row of one layer: every query head scores the row (head_dim
    multiply-adds) and weighs its value (head_dim more); K and V are read
    once, by the one query head that owns them (no grouping)."""
    return {"flops": 2 * m["num_attention_heads"] * 2 * _key(m, "head_dim"),
            "bytes": full_row_bytes(m, itemsize)}
