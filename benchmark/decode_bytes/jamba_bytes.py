"""What a decode step of the Jamba configuration (`omnia_tpu/models/llama.py`
with state-space stacks; `jamba2-3b`) must move, from shapes. Jax-free. `m`
holds the model's sizes under the configuration file's own keys: of the
`num_hidden_layers`, `num_mamba_layers` are Mamba-1 selective-state-space
layers (a recurrent state a slot, `mamba_d_state` numbers for each of
`mamba_expand` x `hidden_size` channels) and `num_attention_layers` cache K and
V rows of whole contexts (`num_key_value_heads` heads); every layer's FFN is
a dense SwiGLU of `intermediate_size`; the head is the embedding table
(`tie_word_embeddings`), which a step therefore streams once.
"""

from __future__ import annotations


def _key(m: dict, key: str):
    """A key of the file, or of its `assumed` where the source lacks it."""
    return m[key] if key in m else m["assumed"][key]


def channels(m: dict) -> int:
    return m["mamba_expand"] * m["hidden_size"]


def mamba_params(m: dict) -> int:
    d, e, n, r = m["hidden_size"], channels(m), m["mamba_d_state"], m["mamba_dt_rank"]
    return (d * 2 * e + (m["mamba_d_conv"] + bool(m["mamba_conv_bias"])) * e   # in, conv
            + e * (r + 2 * n) + (r + 2 * n) * bool(_key(m, "mamba_inner_norms"))  # x, norms
            + r * e + e + n * e + e + e * d)                      # dt, its bias, A, D, out


def attention_params(m: dict) -> int:
    d, dh = m["hidden_size"], _key(m, "head_dim")
    q, kv = m["num_attention_heads"] * dh, m["num_key_value_heads"] * dh
    return d * q + 2 * d * kv + q * d


def param_count(m: dict) -> int:
    """Every parameter of the model as the file describes it (3,029,337,472
    at the published sizes): both kinds of mixer, every layer's two norms and
    SwiGLU, the final norm and the table, which is the head too."""
    d = m["hidden_size"]
    return (m["num_mamba_layers"] * mamba_params(m)
            + m["num_attention_layers"] * attention_params(m)
            + m["num_hidden_layers"] * (2 * d + 3 * d * m["intermediate_size"])
            + d + d * m["vocab_size"] * (1 if m["tie_word_embeddings"] else 2))


def state_bytes(m: dict) -> int:
    """The recurrent state of one slot in one Mamba layer, read once and
    written once by a decode step: state numbers x channels float32 (the
    configuration's `assumed.state_dtype`), twice: 2 x 327,680 B. What
    `decode_mamba_state` moves for one of the program's counter
    `decode_mamba_slots` (counted a layer a step)."""
    return 2 * m["mamba_d_state"] * channels(m) * 4


def step_vector_bytes(m: dict) -> int:
    """What the state kernel moves beside a slot's state: its rows of delta
    and delta u' in, its row of y out (channels float32 each) and its row of B
    and C (128 float32 lanes): 61,952 B, a tenth of `state_bytes`."""
    return 3 * channels(m) * 4 + 128 * 4


def scan_token_bytes(m: dict) -> int:
    """What the scan kernel (`mamba_scan`) must move for one token of a piece
    in one Mamba layer: its rows of delta and delta u' in and its row of y out
    (channels float32 each), and its B and C, each number spread over a tile's
    128 lanes as the kernel reads them: 77,824 B. Every block of channels
    reads B and C again (four times at 5120 channels); counted once, so the
    floor is a floor."""
    return 3 * channels(m) * 4 + 2 * m["mamba_d_state"] * 128 * 4


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes one decode step reads that do not grow with the live context:
    the mixers of both kinds, the two norms and the SwiGLU of every layer, the
    final norm and the tied head; AND the Mamba layers' states of
    `expected_live_slots` slots, read and written (`state_bytes`): a state
    costs the same at any context, so `harness/roofline.py`, which multiplies
    `kv_bytes_per_token` by the live context tokens, cannot carry it
    (`kimi_linear_bytes.py`'s and `olmo_hybrid_bytes.py`'s precedent). The
    embedding table is gathered (a row a slot) AND streamed whole as the
    head: counted once, as the head. `A_log`, `dt_bias` and `D` are float32
    (4 bytes whatever `itemsize`)."""
    e, n = channels(m), m["mamba_d_state"]
    float32 = m["num_mamba_layers"] * (n * e + 2 * e)
    weights = (param_count(m) - float32) * itemsize + float32 * 4
    states = m["expected_live_slots"] * m["num_mamba_layers"] * state_bytes(m)
    return int(weights + states)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The cached K and V rows of one live token over the ATTENTION layers
    only (1,024 B at two of them with one KV head): a Mamba layer caches no
    row."""
    return m["num_attention_layers"] * attention_row_bytes(m, itemsize)


def attention_row_bytes(m: dict, itemsize: int = 2) -> int:
    """K and V of one cached row of one layer."""
    return 2 * m["num_key_value_heads"] * _key(m, "head_dim") * itemsize


def decode_attention_row(m: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of the attention layers' decode kernel for one
    live cached row of one layer: every query head scores the row (head_dim
    multiply-adds) and weighs its value (head_dim more); K and V are read
    once for the whole group of query heads on their KV head."""
    return {"flops": 2 * m["num_attention_heads"] * 2 * _key(m, "head_dim"),
            "bytes": attention_row_bytes(m, itemsize)}
