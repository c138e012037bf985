"""Bytes a decode step of a latent-attention (MLA) decoder with routed
experts has to read, from shapes alone (`m` is the configuration's
`model_kwargs`, the constructor's own names). Kept with the benchmark, beside
`flops.py` and by its rules: nothing here counts padding, copies or a weight
read twice, so a program that moves more than this reads as a lower share,
never a higher one.

A step reads every weight outside the routed experts once (attention, the
dense layers, the shared expert and the router of each expert layer, the
norms, the head; of the embedding table only the step's rows, which are left
out), the three matrices of each expert that got at least one token, and one
cached latent row per attended position and layer.
"""
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def itemsize(m):
    """Bytes of one weight or cached value: the configuration's dtype."""
    return ITEMSIZE[m["dtype"]]


def mla_params(m):
    """Weights of one layer's latent attention with its two low-rank norms."""
    d, H = m["hidden_size"], m["num_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    r, q = m["kv_lora_rank"], m["q_lora_rank"]
    return (d * q + q + q * H * qk                       # W_qa, its norm, W_qb
            + d * (r + m["qk_rope_head_dim"]) + r        # W_kva, its norm
            + r * H * (m["qk_nope_head_dim"] + m["v_head_dim"])      # W_kvb
            + H * m["v_head_dim"] * d)                               # W_o


def expert_params(m):
    """One SwiGLU expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_layers(m):
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def fixed_decode_params(m):
    """Every weight a decode step multiplies by whatever the router says."""
    d = m["hidden_size"]
    dense = 3 * d * m["intermediate_size"]
    routed_layer = (m["n_shared_experts"] * expert_params(m)
                    + d * m["n_routed_experts"])       # shared expert, router
    return (m["num_hidden_layers"] * (mla_params(m) + 2 * d)   # two norms
            + m["first_k_dense_replace"] * dense
            + expert_layers(m) * routed_layer
            + d + d * m["vocab_size"])                 # final norm, head


def expert_bytes(m):
    return expert_params(m) * itemsize(m)


def latent_row_bytes(m):
    """One cached position of ONE layer: [c_kv | k_r]."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize(m)


def decode_step_bytes(m, experts_hit, latent_rows):
    """`experts_hit`: distinct experts with a token, summed over the expert
    layers; `latent_rows`: attended positions, summed over the live slots
    (each is read in every layer)."""
    return (fixed_decode_params(m) * itemsize(m)
            + expert_bytes(m) * experts_hit
            + latent_row_bytes(m) * m["num_hidden_layers"] * latent_rows)
