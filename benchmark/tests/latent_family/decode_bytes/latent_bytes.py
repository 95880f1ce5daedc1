"""What a decode step of the fixture family must move, from shapes, under
the fixture configuration's own keys. One latent row a token a layer; a
step reads every expert (all are evaluated)."""


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    d, f, r = m["hidden_size"], m["mlp_width"], m["latent_dim"]
    q = m["num_attention_heads"] * r
    experts = m["n_routed_experts"]
    mlp = (experts or 1) * 3 * d * f + d * experts
    per_layer = d * q + d * r + q * d + mlp + 2 * d
    return (m["num_hidden_layers"] * per_layer + d * m["vocab_size"] + d) * itemsize


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    return m["num_hidden_layers"] * m["latent_dim"] * itemsize
