"""What a decode step of the Xing4.0 family (`omnia_tpu/models/mla.py` with
leading dense layers and a residual of several copies; configuration
`xing4-29b-a4b`) must move, from shapes. Jax-free. `m` holds the model's sizes under the configuration file's
own keys: `first_k_dense_replace` of the `num_hidden_layers` are dense, the
rest hold all `n_routed_experts` (`ep_size` 1).
"""

from __future__ import annotations


def _attention_params(m: dict) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    rq, r = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (d * rq + rq + rq * h * (dn + dr)      # wqa, qn, wqb
            + d * (r + dr) + r                     # wkva, kvn
            + r * h * (dn + dv) + h * dv * d)      # wkvb, wo


def hc_map_bytes(m: dict, itemsize: int = 2) -> int:
    """The maps of one layer's two sublayers: Phi [n*D, 2n + n^2] in the
    served type, bias [2n + n^2] and alpha [3] in float32."""
    n = m["hc_mult"]
    outs = 2 * n + n * n
    return 2 * (n * m["hidden_size"] * outs * itemsize + (outs + 3) * 4)


def expert_bytes(m: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Weight bytes one decode step reads: attention, the norms and the
    hyper-connection maps of every layer, the dense layers' FFN, and of
    every sparse layer the router with its selection bias (float32), the
    shared expert and ALL routed experts; the final norm and the head. The
    embedding table is gathered (a row a slot), not streamed, so it is left
    out.

    A step reads only the experts that got a token. With 48 live tokens
    choosing 4 of 64, an expert is hit with probability 1 - (1 - 4/64)^48 =
    95.5 %, so counting all of them overstates the step's bytes by at most
    4 % (the routed experts are 87 % of the step's bytes), and
    `batch.decode_step_roofline` reads that much high; the experts' own
    roofline counts the experts hit (`moe_experts_hit`)."""
    d, f, e = m["hidden_size"], m["moe_intermediate_size"], m["n_routed_experts"]
    layers, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    every = (_attention_params(m) + 2 * d) * itemsize + hc_map_bytes(m, itemsize)
    dense_ffn = 3 * d * m["intermediate_size"] * itemsize
    sparse_ffn = (e * expert_bytes(m, itemsize)
                  + m.get("n_shared_experts", 0) * 3 * d * f * itemsize
                  + d * e * itemsize + e * 4)      # router, selection bias
    head = (d * m["vocab_size"] + d) * itemsize
    return layers * every + dense * dense_ffn + (layers - dense) * sparse_ffn + head


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The cached row of one token over all layers as published: [c |
    k_rope], kv_lora_rank + qk_rope_head_dim = 576 values a layer (1152 B in
    bfloat16). The program pads the row to the 128-lane tile (640 lanes,
    1280 B); the pad is counted as roofline lost, not as bytes due."""
    return m["num_hidden_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def decode_attention_row(m: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of the decode kernel (`decode_mla_attention`)
    for one live cached row of one layer: every head scores the row
    (kv_lora_rank + qk_rope_head_dim multiply-adds) and weighs its latent
    (kv_lora_rank more); the row is read once for both."""
    r, dr, h = m["kv_lora_rank"], m["qk_rope_head_dim"], m["num_attention_heads"]
    return {"flops": 2 * h * ((r + dr) + r), "bytes": (r + dr) * itemsize}
