"""Plain reference of the `glm-4.7-flash` configuration: GLM-4.7-Flash
(`glm4_moe_lite`) as one full causal forward pass in straightforward float32
jax.numpy at "highest" matmul precision. Latent attention in its EXPANDED
form (per-head keys and values from c_kv . W_kvb; never the absorbed form
the engine decodes with), the routed experts as a plain loop over ALL of
them with a 0 weight where a token did not choose the expert, no cache, no
pages, no batching of requests, nothing of paddle_tpu: only the names of the
program's parameters are read here.

The equations (no bias anywhere; RMSNorm(x; w) = w x / sqrt(mean x^2 + eps)):

    h <- h + MLA(RMSNorm(h; w_in));  h <- h + FFN_l(RMSNorm(h; w_post))
    MLA: c_q = RMSNorm(x W_qa; w_qa); q = c_q W_qb -> H x [q_nope | q_rope]
         [c_kv | k_r] = x W_kva; c_kv <- RMSNorm(c_kv; w_kva)
         [k_nope_h | v_h] = c_kv W_kvb; RoPE (rotate-half, theta) on q_rope_h
         and on k_r, which the heads share;
         score = (q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope), causal
    FFN_0 = (silu(x W_g) * x W_u) W_d
    FFN_l = sum_{i in top-k of s + b} g_i E_i(x) + E_shared(x),
         s = sigmoid(x W_r), g_i = scale s_i / (sum_topk s_j + 1e-20)

What the shapes do not give is the published value: 4 experts a token,
scale 1.8, theta 1e6, eps 1e-5 (keyword arguments, for the tests' sizes).

The state_dict's leaves are taken AS THEY ARE (bfloat16 as served: a float32
copy of 4.5 B weights does not fit beside the engine's) and upcast inside
each jitted piece, one layer — inside the expert loop one expert — at a
time, which is exact. The head is applied to blocks of positions: float32
logits of [4, 4096, 154880] are 10 GB and never exist.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

TOP_K, ROUTED_SCALE, ROPE_THETA, EPS = 4, 1.8, 1e6, 1e-5
HEAD_BLOCK = 512     # positions a head call; 512 x 154880 x 4 B = 317 MB


def weights(state):
    """The program's state_dict (name -> array), leaves as they are."""
    return {k: jnp.asarray(getattr(v, "_value", v)) for k, v in state.items()}


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, ..., n] at positions 0..S-1, rotate-half pairing."""
    S, n = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (n // 2,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    return x * cos + jnp.concatenate([-x[..., n // 2:], x[..., :n // 2]],
                                     -1) * sin


@functools.partial(jax.jit, static_argnames=("num_heads", "theta", "eps"))
def _attention(x, w, num_heads, theta, eps):
    """x [S, d] -> x + MLA(RMSNorm(x)), expanded form."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        S, H = x.shape[0], num_heads
        r = w["self_attn.kv_a_layernorm.weight"].shape[0]
        xn = _rms(x, w["input_layernorm.weight"], eps)
        c_q = _rms(xn @ w["self_attn.q_a_proj.weight"],
                   w["self_attn.q_a_layernorm.weight"], eps)
        q = (c_q @ w["self_attn.q_b_proj.weight"]).reshape(S, H, -1)
        kv = xn @ w["self_attn.kv_a_proj_with_mqa.weight"]
        c_kv = _rms(kv[:, :r], w["self_attn.kv_a_layernorm.weight"], eps)
        rope = kv.shape[1] - r
        nope = q.shape[-1] - rope
        k_r = _rope(kv[:, r:], theta)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
        kvb = (c_kv @ w["self_attn.kv_b_proj.weight"]).reshape(S, H, -1)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        s = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope)
             + jnp.einsum("qhe,ke->hqk", q_rope, k_r)) / (nope + rope) ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        o = jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, -1), v)
        return x + o.reshape(S, -1) @ w["self_attn.o_proj.weight"]


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


@functools.partial(jax.jit, static_argnames="eps")
def _dense_ffn(x, w, eps):
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        xn = _rms(x, w["post_attention_layernorm.weight"], eps)
        return x + _swiglu(xn, w["mlp.gate_proj.weight"],
                           w["mlp.up_proj.weight"], w["mlp.down_proj.weight"])


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "eps"))
def _moe_ffn(x, w, top_k, scale, eps):
    with jax.default_matmul_precision("highest"):
        xn = _rms(x, w["post_attention_layernorm.weight"]
                  .astype(jnp.float32), eps)
        s = jax.nn.sigmoid(xn @ w["mlp.gate.weight"].astype(jnp.float32))
        E = s.shape[-1]
        _, idx = jax.lax.top_k(
            s + w["mlp.gate.e_score_correction_bias"].astype(jnp.float32),
            top_k)
        chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)    # [S, E]
        g = scale * s * chosen / (jnp.sum(s * chosen, -1, keepdims=True)
                                  + 1e-20)

        def expert(y, e):
            wg, wu, wd, g_e = e          # ONE expert's weights, upcast here
            return y + g_e[:, None] * _swiglu(
                xn, wg.astype(jnp.float32), wu.astype(jnp.float32),
                wd.astype(jnp.float32)), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(xn), (
            w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
            w["mlp.experts.down_proj"], g.T))
        sh = _f32({k: w[f"mlp.shared_experts.{k}_proj.weight"]
                   for k in ("gate", "up", "down")})
        return x + y + _swiglu(xn, sh["gate"], sh["up"], sh["down"])


@functools.partial(jax.jit, static_argnames="eps")
def _head(x, norm_w, head_w, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm_w.astype(jnp.float32), eps) \
            @ head_w.astype(jnp.float32)


def hidden(W, ids, num_heads, top_k=TOP_K, scale=ROUTED_SCALE,
           theta=ROPE_THETA, eps=EPS):
    """Hidden states [S, d] (before the final norm) of ONE sequence ids
    [S]. Right padding is harmless: no real position attends to it."""
    x = W["model.embed_tokens.weight"][ids].astype(jnp.float32)
    i = 0
    while f"model.layers.{i}.input_layernorm.weight" in W:
        p = f"model.layers.{i}."
        w = {k[len(p):]: v for k, v in W.items() if k.startswith(p)}
        x = _attention(x, {k: v for k, v in w.items()
                           if not k.startswith(("mlp.", "post_"))},
                       num_heads, theta, eps)
        w = {k: v for k, v in w.items() if k.startswith(("mlp.", "post_"))}
        x = (_moe_ffn(x, w, top_k, scale, eps) if "mlp.gate.weight" in w
             else _dense_ffn(x, w, eps))
        i += 1
    return x


def forward(W, ids, num_heads, **kw):
    """Logits [B, S, V] of a full causal pass over token ids [B, S]: for
    the tests' sizes (a real vocabulary goes through `shortfalls`)."""
    eps = kw.get("eps", EPS)
    return jnp.stack([
        _head(hidden(W, jnp.asarray(row, jnp.int32), num_heads, **kw),
              W["model.norm.weight"], W["lm_head.weight"], eps)
        for row in ids])


def token_shortfalls(W, sequences, prompt_lens, num_heads, pad_to=512, **kw):
    """For each sequence (prompt + generated ids) the amount by which each
    generated token's reference logit falls short of the reference's best
    logit at that position: 0 where the token is the reference's argmax.
    One sequence at a time, all padded to one width (one compile), the
    head over blocks of HEAD_BLOCK positions."""
    eps = kw.get("eps", EPS)
    width = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    out = []
    for seq, p in zip(sequences, prompt_lens):
        ids = jnp.zeros((width,), jnp.int32).at[:len(seq)].set(
            jnp.asarray(seq, jnp.int32))
        x = hidden(W, ids, num_heads, **kw)
        short = []
        # position t predicts token t + 1: the generated ones are p..len-1
        for lo in range(p - 1, len(seq) - 1, HEAD_BLOCK):
            hi = min(lo + HEAD_BLOCK, len(seq) - 1)
            block = jnp.zeros((HEAD_BLOCK, x.shape[1]), x.dtype).at[
                :hi - lo].set(x[lo:hi])
            logits = _head(block, W["model.norm.weight"],
                           W["lm_head.weight"], eps)[:hi - lo]
            took = jnp.take_along_axis(logits, ids[lo + 1:hi + 1, None],
                                       -1)[:, 0]
            short.append(logits.max(-1) - took)
        out.append(jax.device_get(jnp.concatenate(short)))
    return out


def limits():
    """The two limits of this configuration's comparison, from its file."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "configs", "glm-4.7-flash.json")
    with open(path) as f:
        run = json.load(f)["run"]
    return run["near_margin"], run["disagree_limit"]


def shortfalls(W, sequences, prompt_lens, num_heads, **kw):
    """What `drivers/serve.py` holds to `near_margin`: it takes the LARGEST
    entry of what this returns. The entries are every generated token's
    shortfall (`token_shortfalls`; a token unrelated to the reference's
    logits falls 3-5 short of its best, and one such token fails the run)
    and, last, ONE entry for the second limit, for which the driver has no
    argument: the share of the checked tokens that are NOT the reference's
    argmax, scaled so that it passes `near_margin` exactly when the share
    passes `disagree_limit`. A lower precision, a dropped expert, a wrong
    gate or a wrong page moves every position a little and no position far:
    they show in that share and not in the largest shortfall (both limits,
    and the readings on the chip they lie between: `near_margin_why` in the
    configuration's file, PERF.md section 6)."""
    out = token_shortfalls(W, sequences, prompt_lens, num_heads, **kw)
    flat = np.concatenate(out)
    margin, disagree_limit = limits()
    disagree = float(np.mean(flat > 0))
    print(f"[reference] {flat.size} tokens: {disagree:.4f} are not the "
          f"reference's argmax (limit {disagree_limit}), largest shortfall "
          f"{flat.max():.4f} (limit {margin}); mean {flat.mean():.5f}, p90 "
          f"{np.quantile(flat, 0.9):.4f}, p99 {np.quantile(flat, 0.99):.4f}",
          flush=True)
    return out + [np.asarray([margin * disagree / disagree_limit],
                             flat.dtype)]
