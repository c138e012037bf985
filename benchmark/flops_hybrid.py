"""Bytes a decode step and operations a prefill call of a hybrid decoder
(a Mamba-2 state-space mixer beside grouped-query attention in every block,
then a gated MLP) have to move and do, from shapes alone (`m` is the
configuration's `model_kwargs`, the constructor's own names). Kept with the
benchmark, beside `flops.py` and by its rules: nothing here counts padding,
copies or a weight read twice, so a program that moves or computes more than
this reads as a lower share, never a higher one.

A decode step reads every weight outside the embedding table once (of the
table only the step's rows, which are left out), reads AND writes the mixer
state of each live slot (float32, whatever the model's dtype), and reads one
K and one V row per attended position and layer. The convolution's window
(a hundredth of the state) is left out.

A prefill of n real tokens multiplies each token by the blocks' matrices,
does causal attention's two products over n(n+1)/2 pairs, the chunked scan's
four products a chunk as published (`mamba_chunk_size`), and the head once.
"""
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def itemsize(m):
    """Bytes of one weight or cached K/V value: the configuration's dtype."""
    return ITEMSIZE[m["dtype"]]


def attention_params(m):
    d, D = m["hidden_size"], m["head_dim"]
    return d * D * (2 * m["num_heads"] + 2 * m["num_key_value_heads"])


def conv_dim(m):
    return m["mamba_d_ssm"] + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def mixer_matmul_params(m):
    """The in- and out-projection: what every token is multiplied by."""
    d, ds = m["hidden_size"], m["mamba_d_ssm"]
    return d * (ds + conv_dim(m) + m["mamba_n_heads"]) + ds * d


def mixer_small_params(m):
    """Convolution weights and bias, the gated norm (the model's dtype)."""
    return conv_dim(m) * (m["mamba_d_conv"] + 1) + m["mamba_d_ssm"]


def mlp_params(m):
    return 3 * m["hidden_size"] * m["intermediate_size"]


def decode_weight_bytes(m):
    """Every weight a decode step multiplies by: the blocks, the final norm
    and the untied head. `A_log`, `dt_bias` and `D` are float32."""
    d = m["hidden_size"]
    layer = (attention_params(m) + mixer_matmul_params(m)
             + mixer_small_params(m) + mlp_params(m) + 2 * d)
    return (itemsize(m) * (m["num_hidden_layers"] * layer
                           + d + d * m["vocab_size"])
            + 4 * 3 * m["mamba_n_heads"] * m["num_hidden_layers"])


def state_bytes(m):
    """One slot's mixer state, all layers: float32 [H, P, N] a layer."""
    return (4 * m["num_hidden_layers"] * m["mamba_n_heads"]
            * m["mamba_d_head"] * m["mamba_d_state"])


def kv_row_bytes(m):
    """One cached position of ONE layer: a K and a V row of the K/V heads."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize(m)


def decode_step_bytes(m, state_slots, kv_rows):
    """`state_slots`: live slots (each state read and written once);
    `kv_rows`: attended positions summed over the live slots (each is read
    in every layer)."""
    return (decode_weight_bytes(m) + 2 * state_bytes(m) * state_slots
            + kv_row_bytes(m) * m["num_hidden_layers"] * kv_rows)


def scan_flops_per_token(m):
    """The chunked scan's own products for one token of one layer, chunk Q:
    C B^T (2 Q G N), its application (2 Q H P), the chunk's state and the
    contribution of the state before (2 H P N each)."""
    Q, H, P = m["mamba_chunk_size"], m["mamba_n_heads"], m["mamba_d_head"]
    G, N = m["mamba_n_groups"], m["mamba_d_state"]
    return 2 * Q * G * N + 2 * Q * H * P + 4 * H * P * N


def prefill_flops(m, tokens, requests):
    """`requests` prompts of `tokens` real tokens in all. Attention's pairs
    are counted as if the prompts were equally long, (tokens / requests)^2 /
    2 each, which is the least that any split of `tokens` gives."""
    if requests <= 0 or tokens <= 0:
        return 0.0
    L = m["num_hidden_layers"]
    per_token = 2 * (attention_params(m) + mixer_matmul_params(m)
                     + mlp_params(m)) + scan_flops_per_token(m)
    pairs = tokens * tokens / (2.0 * requests)
    attention = 4 * m["num_heads"] * m["head_dim"] * pairs
    head = 2 * m["hidden_size"] * m["vocab_size"] * requests
    return L * (per_token * tokens + attention) + head
