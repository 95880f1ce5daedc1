"""What a decode step of the Kimi-Linear family (`omnia_tpu/models/mla.py`
with layers of several kinds; configuration `kimi-linear-48b-a3b`) must move,
from shapes. Jax-free. `m` holds the model's sizes under the configuration
file's own keys: of the `num_hidden_layers`, `num_kda_layers` have linear
attention (a recurrent state a slot) and `num_mla_layers` latent attention (a
cached row a token); `first_k_dense_replace` are dense, and the rest hold
`num_experts` routed experts (this chip's share of `num_experts_source`).
"""

from __future__ import annotations


def _key(m: dict, key: str):
    """A key of the file, or of its `assumed` where the source lacks it."""
    return m[key] if key in m else m["assumed"][key]


def _kda_params(m: dict) -> int:
    d, a = m["hidden_size"], m["linear_attn_config"]
    hd, taps = a["num_heads"] * a["head_dim"], a["short_conv_kernel_size"]
    rank = _key(m, "kda_gate_rank")
    return (d * 3 * hd + taps * 3 * hd                 # wqkv, conv
            + 2 * (d * rank + rank * hd)               # wfa, wfb, wga, wgb
            + hd + a["num_heads"] + d * a["num_heads"]  # dt_bias, a_log, wb
            + a["head_dim"] + hd * d)                  # on, wo


def _mla_params(m: dict) -> int:
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (d * h * (dn + dr)                          # wq (no query rank)
            + d * (r + dr) + r                         # wkva, kvn
            + r * h * (dn + dv) + h * dv * d)          # wkvb, wo


def expert_bytes(m: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize


def state_bytes(m: dict) -> int:
    """The recurrent state of one slot in one linear-attention layer, read
    once and written once by a decode step: heads x head_dim x head_dim
    float32 (the configuration's `assumed.state_dtype`), twice. What
    `decode_kda_state` moves for one of the program's counter
    `decode_kda_slots` (counted a layer a step). The step vectors beside it
    (five rows of 128 a head) are a hundredth of it and left out."""
    a = m["linear_attn_config"]
    return 2 * a["num_heads"] * a["head_dim"] * a["head_dim"] * 4


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes one decode step reads that do not grow with the live context:
    attention and the norms of every layer, the dense layers' FFN, and of
    every sparse layer the router with its selection bias (float32), the
    shared expert and the held experts that a token chose
    (`expected_experts_hit` of the file: an even router's 64 tokens choosing
    8 of 256 hit 1 - (1 - 8/256)^64 = 86.9 % of the 64 held, 55.6, the stated
    deployment's count and not a reading of the program; the file's
    `moe_experts_hit_read` beside it says what the program's counter read on
    the chip as the configuration is seeded, 31.0, so this floor is 2.4 GB
    high for the seeded model; the experts' own roofline counts those
    really hit, `moe_experts_hit`); the final norm and the
    head's slice; AND the linear-attention layers' states of
    `expected_live_slots` slots, read and written (`state_bytes`): a state
    costs the same at any context, so `harness/roofline.py`, which
    multiplies `kv_bytes_per_token` by the live context tokens, cannot carry
    it, and a step of this model moves about a sixth of its bytes there. The
    embedding table is gathered (a row a slot), not streamed, and left out."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    layers, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    attention = (m["num_kda_layers"] * _kda_params(m) + m["num_mla_layers"] * _mla_params(m)
                 + layers * 2 * d) * itemsize
    dense_ffn = 3 * d * m["intermediate_size"] * itemsize
    sparse_ffn = (m["expected_experts_hit"] * expert_bytes(m, itemsize)
                  + m.get("num_shared_experts", 0) * 3 * d * f * itemsize
                  + d * m["num_experts_source"] * itemsize + m["num_experts_source"] * 4)
    head = (d * m["vocab_size"] + d) * itemsize
    states = m["expected_live_slots"] * m["num_kda_layers"] * state_bytes(m)
    return int(attention + dense * dense_ffn + (layers - dense) * sparse_ffn + head + states)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The cached row of one token over the LATENT layers only, as
    published: [c | k_rope], kv_lora_rank + qk_rope_head_dim = 576 values a
    layer (1152 B in bfloat16). The program pads the row to the 128-lane tile
    (640 lanes, 1280 B); the pad is counted as roofline lost, not as bytes
    due. The linear-attention layers cache no row."""
    return m["num_mla_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def decode_attention_row(m: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of the latent decode kernel
    (`decode_mla_attention`) for one live cached row of one layer."""
    r, dr, h = m["kv_lora_rank"], m["qk_rope_head_dim"], m["num_attention_heads"]
    return {"flops": 2 * h * ((r + dr) + r), "bytes": (r + dr) * itemsize}
