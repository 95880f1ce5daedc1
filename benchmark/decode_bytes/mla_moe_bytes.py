"""What a decode step of the latent-attention family with a share of the
routed experts (`omnia_tpu/models/mla.py`, configuration
`mistral-small-4`) must move, from shapes. Jax-free. `m` holds the model's
sizes under the configuration file's own keys: `n_routed_experts` is how
many routed experts this chip holds, `n_routed_experts_source` the
router's width.
"""

from __future__ import annotations


def _attention_params(m: dict) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    rq, r = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (d * rq + rq + rq * h * (dn + dr)      # wqa, qn, wqb
            + d * (r + dr) + r                     # wkva, kvn
            + r * h * (dn + dv) + h * dv * d)      # wkvb, wo


def expert_bytes(m: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Weight bytes one decode step reads: attention, the shared expert,
    the router and the norms of every layer, ALL held experts, and the
    head's slice. The embedding table is gathered (a row a slot), not
    streamed, so it is left out.

    A step reads only the experts that got a token. With 96 live tokens
    choosing 4 of 128, a held expert is hit with probability 1 − (1 −
    4/128)^96 = 95.3 %, so counting all of them overstates the step's
    bytes by at most 4 % (the experts are 94 % of a layer's bytes), and
    `batch.decode_step_roofline` reads that much high; the experts' own
    roofline counts the experts hit (`moe_experts_hit`)."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    routed = m["n_routed_experts"] * 3 * d * f
    shared = m.get("n_shared_experts", 0) * 3 * d * f
    router = d * m["n_routed_experts_source"]
    per_layer = _attention_params(m) + routed + shared + router + 2 * d
    head = d * m["vocab_size"]
    return (m["num_hidden_layers"] * per_layer + head + d) * itemsize


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The cached row of one token over all layers as published: [c |
    k_rope], kv_lora_rank + qk_rope_head_dim values a layer (640 B in
    bfloat16). The program pads the row to the 128-lane tile (384 lanes,
    768 B); the pad is counted as roofline lost, not as bytes due."""
    return m["num_hidden_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def decode_attention_row(m: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of the decode kernel (`decode_mla_attention`)
    for one live cached row of one layer: every head scores the row
    (kv_lora_rank + qk_rope_head_dim multiply-adds) and weighs its latent
    (kv_lora_rank more); the row is read once for both."""
    r, dr, h = m["kv_lora_rank"], m["qk_rope_head_dim"], m["num_attention_heads"]
    return {"flops": 2 * h * ((r + dr) + r), "bytes": (r + dr) * itemsize}
