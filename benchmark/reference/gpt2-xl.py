"""Plain reference of the `gpt2-xl` configuration: the GPT-2 decoder
(pre-LayerNorm blocks, learned positions, tied head; Radford et al. 2019) as
one full causal forward pass in straightforward float32 jax.numpy at
"highest" matmul precision. No cache, no pages, no batching of requests,
nothing of paddle_tpu: only the names of the program's parameters are read
here, in `weights`.

Departure of the program's model that the reference follows: the
feed-forward activation is the exact erf GELU, where the published model
uses the tanh approximation `gelu_new` (listed under `assumed` in the
configuration file). With seeded random weights either is as good.

One block is one jitted call, made once per layer with that layer's weights:
48 unrolled layers in one program would take minutes to compile and a
stacked copy of the weights would not fit beside the engine's.
"""
import functools

import jax
import jax.numpy as jnp


def weights(state):
    """The program's state_dict (name -> array) as float32 jax arrays."""
    return {k: jnp.asarray(getattr(v, "_value", v), jnp.float32)
            for k, v in state.items()}


def _ln(x, w, b):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + 1e-5) * w + b


@functools.partial(jax.jit, static_argnames="num_heads")
def _block(x, w, num_heads):
    with jax.default_matmul_precision("highest"):
        B, S, _ = x.shape

        def lin(name, t):
            return t @ w[name + ".weight"] + w[name + ".bias"]

        def heads(t):
            return t.reshape(B, S, num_heads, -1).transpose(0, 2, 1, 3)
        h = _ln(x, w["ln1.weight"], w["ln1.bias"])
        q, k, v = (heads(lin("attn." + n, h))
                   for n in ("q_proj", "k_proj", "v_proj"))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        x = x + lin("attn.out_proj", a.transpose(0, 2, 1, 3).reshape(B, S, -1))
        h = _ln(x, w["ln2.weight"], w["ln2.bias"])
        return x + lin("mlp.2", jax.nn.gelu(lin("mlp.0", h),
                                            approximate=False))


@jax.jit
def _head(x, lnw, lnb, wte):
    with jax.default_matmul_precision("highest"):
        return _ln(x, lnw, lnb) @ wte.T


def forward(W, ids, num_heads):
    """Logits [B, S, V] of a full causal pass over token ids [B, S].
    Right padding is harmless: no real position attends to it."""
    x = W["gpt.wte.weight"][ids] + W["gpt.wpe.weight"][:ids.shape[1]][None]
    i = 0
    while f"gpt.blocks.{i}.ln1.weight" in W:
        p = f"gpt.blocks.{i}."
        x = _block(x, {k[len(p):]: v for k, v in W.items()
                       if k.startswith(p)}, num_heads)
        i += 1
    return _head(x, W["gpt.ln_f.weight"], W["gpt.ln_f.bias"],
                 W["gpt.wte.weight"])


def shortfalls(W, sequences, prompt_lens, num_heads, pad_to=128):
    """For each sequence (prompt + generated ids) the amount by which each
    generated token's reference logit falls short of the reference's best
    logit at that position: 0 where the token is the reference's argmax."""
    width = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    ids = jnp.zeros((len(sequences), width), jnp.int32)
    for r, s in enumerate(sequences):
        ids = ids.at[r, :len(s)].set(jnp.asarray(s, jnp.int32))
    logits = forward(W, ids, num_heads)[:, :-1]
    short = logits.max(-1) - jnp.take_along_axis(
        logits, ids[:, 1:, None], -1)[..., 0]
    short = jax.device_get(short)
    return [short[r, p - 1:len(s) - 1]
            for r, (s, p) in enumerate(zip(sequences, prompt_lens))]
