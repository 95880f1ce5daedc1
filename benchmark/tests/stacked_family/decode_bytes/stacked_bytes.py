"""What a decode step of the fixture family of stacks must move, from
shapes, under the fixture configuration's own keys: each kind of layer its
own weights, times the layers of that kind; one latent row a token a layer;
a step reads every expert (all are evaluated)."""


def decode_weight_bytes(m: dict, itemsize: int = 2) -> int:
    d, r, n = m["hidden_size"], m["latent_dim"], m["residual_copies"]
    q = m["num_attention_heads"] * r
    shared = d * q + d * r + q * d + 2 * d + 2 * (2 * n + n * n)     # attention, norms, two maps
    dense = shared + 3 * d * m["mlp_width"]
    sparse = (shared + d * m["n_routed_experts"]
              + (m["n_routed_experts"] + 1) * 3 * d * m["expert_width"])
    kinds = m["layer_kinds"]
    layers = kinds.count("dense") * dense + kinds.count("sparse") * sparse
    return (layers + d * m["vocab_size"] + d) * itemsize


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    return m["num_hidden_layers"] * m["latent_dim"] * itemsize
