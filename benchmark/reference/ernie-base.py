"""Plain reference of the `ernie-base` configuration: the BERT-style
post-LayerNorm encoder of ERNIE 2.0 (arXiv:1907.12412) with the MLM head
over tied embeddings and the NSP head, and their summed loss, in
straightforward float32 jax.numpy at "highest" matmul precision. No kernel,
no dropout (it is compared with the program in eval mode), nothing of
paddle_tpu: only the names of the program's parameters are read here, in
`weights`.

Departures of the program's model that the reference follows, so that the
two can agree: LayerNorm epsilon is 1e-12 in the embeddings and the MLM
head and 1e-5 inside the encoder layers (published: 1e-12 throughout;
the difference is far below the tolerance), GELU is the exact erf form.
"""
import jax
import jax.numpy as jnp


def weights(state):
    """The program's state_dict (name -> array) as float32 jax arrays."""
    return {k: jnp.asarray(getattr(v, "_value", v), jnp.float32)
            for k, v in state.items()}


def _ln(x, w, b, eps):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * w + b


def forward(W, ids, num_heads):
    """(MLM logits [B, S, V], NSP logits [B, 2]) for token ids [B, S]."""
    with jax.default_matmul_precision("highest"):
        B, S = ids.shape
        e = "ernie.embeddings."
        x = (W[e + "word_embeddings.weight"][ids]
             + W[e + "position_embeddings.weight"][jnp.arange(S)][None]
             + W[e + "token_type_embeddings.weight"][0][None, None])
        x = _ln(x, W[e + "layer_norm.weight"], W[e + "layer_norm.bias"],
                1e-12)
        i = 0
        while f"ernie.encoder.layers.{i}.linear1.weight" in W:
            p = f"ernie.encoder.layers.{i}."

            def lin(name, t):
                return t @ W[p + name + ".weight"] + W[p + name + ".bias"]

            def heads(t):
                return t.reshape(B, S, num_heads, -1).transpose(0, 2, 1, 3)
            q, k, v = (heads(lin("self_attn." + n, x))
                       for n in ("q_proj", "k_proj", "v_proj"))
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
            a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            a = a.transpose(0, 2, 1, 3).reshape(B, S, -1)
            x = _ln(x + lin("self_attn.out_proj", a), W[p + "norm1.weight"],
                    W[p + "norm1.bias"], 1e-5)
            f = lin("linear2", jax.nn.gelu(lin("linear1", x),
                                           approximate=False))
            x = _ln(x + f, W[p + "norm2.weight"], W[p + "norm2.bias"], 1e-5)
            i += 1
        pooled = jnp.tanh(x[:, 0] @ W["ernie.pooler.dense.weight"]
                          + W["ernie.pooler.dense.bias"])
        h = jax.nn.gelu(x @ W["mlm_transform.weight"]
                        + W["mlm_transform.bias"], approximate=False)
        h = _ln(h, W["mlm_norm.weight"], W["mlm_norm.bias"], 1e-12)
        logits = h @ W[e + "word_embeddings.weight"].T + W["mlm_bias"]
        return logits, pooled @ W["nsp.weight"] + W["nsp.bias"]


def loss(W, ids, mlm_labels, nsp_labels, num_heads):
    """Mean cross-entropy over the labelled (not -100) positions plus mean
    cross-entropy of the NSP head."""
    logits, nsp = forward(W, ids, num_heads)
    logp = jax.nn.log_softmax(logits, -1)
    valid = mlm_labels != -100
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, mlm_labels, 0)[..., None], -1)[..., 0]
    mlm = -(picked * valid).sum() / valid.sum()
    nlogp = jax.nn.log_softmax(nsp, -1)
    return mlm - jnp.take_along_axis(nlogp, nsp_labels[:, None], -1).mean()
