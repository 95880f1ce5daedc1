"""What a decode step of a Llama-family model (dense GQA, or Mixtral's
experts) must move, from shapes. Jax-free. The default of a configuration's
`"decode_bytes"`; a model of another family brings a module of its own
beside this one, with the same two functions.

`m` holds the model's sizes under the configuration file's own keys.
"""

from __future__ import annotations


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Weight bytes one decode step reads, over all chips. The embedding
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
