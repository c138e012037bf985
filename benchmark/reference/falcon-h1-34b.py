"""Plain reference of the `falcon-h1-34b` configuration: Falcon-H1
(`falcon_h1`) as one full causal forward pass in straightforward float32
jax.numpy at "highest" matmul precision. The state-space recurrence is a plain
`lax.scan` over POSITIONS (never the chunked form the engine prefills with),
the convolution four shifted adds, attention with the K/V heads repeated for
their query heads, no cache, no pages, no slot state, no batching of requests,
nothing of paddle_tpu: only the names of the program's parameters are read.

The equations (no bias but the convolution's; RMSNorm(x; w) = w x /
sqrt(mean x^2 + eps); the multipliers are the configuration file's):

    h0 = E[ids] * embedding_multiplier
    u = RMSNorm(h; w_in)
    h <- h + ssm_out_multiplier * Mixer(ssm_in_multiplier * u)
          + attention_out_multiplier * Attn(attention_in_multiplier * u)
    h <- h + [silu(mlp_multipliers[0] * x W_g) * (x W_u)] W_d * mlp_multipliers[1],
          x = RMSNorm(h; w_ff)
    logits = lm_head_multiplier * RMSNorm(h; w_f) W_head

    Attn:  q = x W_q; k = key_multiplier * x W_k; v = x W_v; rotate-half RoPE
           (theta) on q and k over the whole head; causal softmax(q k^T /
           sqrt(D)) v, query head i reads KV head i // (Hq / Hkv); (.) W_o
    Mixer: [z | xBC | dt] = (x W_in) * m (ssm_multipliers over z, x, B, C, dt)
           xBC_t <- silu(b_c + sum_j w_c[:, j] xBC_{t-K+1+j})
           [x | B | C] = xBC; head i reads group i // (H / G)
           dt_t = softplus(dt_t + dt_bias); A = -exp(A_log)
           S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, S_0 = 0
           y_t = S_t C_t + D x_t
           y <- GroupRMSNorm(y * silu(z); w_n) over each of the G groups; y W_out

What the shapes do not give comes from the configuration's file (the
multipliers, the number of groups, theta, eps; keyword arguments override
them for the tests' sizes).

The state_dict's leaves are taken AS THEY ARE (bfloat16 as served: a float32
copy of 5.25 B weights does not fit beside the engine's) and upcast inside
each jitted piece — the MLP an eighth of its width at a time, the head a
block of positions by a block of the vocabulary at a time (float32 logits of
512 x 261,120 are 535 MB; the float32 head itself would be 5.3 GB), which is
exact.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 512        # positions a head call
VOCAB_BLOCK = 16384     # most columns of the head a call: 5120 x 16320 x 4 B = 334 MB
MLP_PARTS = 8           # the MLP in eighths of its width: 3 x 13.8 M x 4 B = 165 MB each
CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                      "configs", "falcon-h1-34b.json")
KEYS = ("embedding_multiplier", "lm_head_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
        "ssm_multipliers", "mlp_multipliers", "mamba_n_groups", "rope_theta",
        "rms_norm_eps")


@functools.lru_cache(maxsize=None)
def _file():
    with open(CONFIG) as f:
        return json.load(f)


def settings(**kw):
    """What the shapes do not give: the file's, `kw` over them. Hashable,
    so that the jitted pieces take it as a static argument."""
    cfg = {k: _file()[k] for k in KEYS}
    cfg.update(kw)
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in sorted(cfg.items()))


def weights(state):
    """The program's state_dict (name -> array), leaves as they are."""
    return {k: jnp.asarray(getattr(v, "_value", v)) for k, v in state.items()}


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, H, n] at positions 0..S-1, rotate-half pairing."""
    S, n = x.shape[0], x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv[None, None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    return x * cos + jnp.concatenate([-x[..., n // 2:], x[..., :n // 2]],
                                     -1) * sin


@functools.partial(jax.jit, static_argnames=("num_heads", "cfg"))
def _attention(u, w, num_heads, cfg):
    """Attn(attention_in_multiplier * u) for u [S, d]."""
    c = dict(cfg)
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        S, Hq = u.shape[0], num_heads
        x = u * c["attention_in_multiplier"]
        q = (x @ w["q_proj.weight"]).reshape(S, Hq, -1)
        D = q.shape[-1]
        k = ((x @ w["k_proj.weight"]) * c["key_multiplier"]).reshape(S, -1, D)
        v = (x @ w["v_proj.weight"]).reshape(S, -1, D)
        rep = Hq // k.shape[1]
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / D ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        return o.reshape(S, -1) @ w["o_proj.weight"]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _mixer(u, w, cfg, upto):
    """Mixer(ssm_in_multiplier * u) for u [S, d]: the recurrence one
    position at a time. Returns (the mixer's output [S, d], the state
    [H, P, N] after position `upto`)."""
    c = dict(cfg)
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        S = u.shape[0]
        H = w["scalars.A_log"].shape[0]
        ds = w["norm.weight"].shape[0]
        C, K = w["conv1d.weight"].shape
        G = c["mamba_n_groups"]
        N = (C - ds) // (2 * G)
        P = ds // H
        mz, mx, mb, mc, mdt = c["ssm_multipliers"]
        m = jnp.concatenate([jnp.full((n,), v, jnp.float32) for n, v in (
            (ds, mz), (ds, mx), (G * N, mb), (G * N, mc), (H, mdt))])
        zxd = ((u * c["ssm_in_multiplier"]) @ w["in_proj.weight"]) * m
        z, xbc, dt = zxd[:, :ds], zxd[:, ds:ds + C], zxd[:, ds + C:]
        # the causal depthwise convolution: K shifted adds
        pad = jnp.concatenate([jnp.zeros((K - 1, C), jnp.float32), xbc], 0)
        conv = w["conv1d.bias"][None]
        for j in range(K):
            conv = conv + pad[j:j + S] * w["conv1d.weight"][:, j][None]
        xbc = jax.nn.silu(conv)
        x = xbc[:, :ds].reshape(S, H, P)
        B = jnp.repeat(xbc[:, ds:ds + G * N].reshape(S, G, N), H // G, 1)
        Cm = jnp.repeat(xbc[:, ds + G * N:].reshape(S, G, N), H // G, 1)
        dt = jax.nn.softplus(dt + w["scalars.dt_bias"][None])     # [S, H]
        A = -jnp.exp(w["scalars.A_log"])

        def step(carry, inp):
            s, kept = carry
            xt, bt, ct, dtt, t = inp
            s = (jnp.exp(dtt * A)[:, None, None] * s
                 + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
            return ((s, jnp.where(t == upto, s, kept)),
                    jnp.einsum("hpn,hn->hp", s, ct))

        zero = jnp.zeros((H, P, N), jnp.float32)
        (_, kept), y = jax.lax.scan(step, (zero, zero),
                                    (x, B, Cm, dt, jnp.arange(S)))
        y = y + w["scalars.D"][None, :, None] * x
        y = y.reshape(S, ds) * jax.nn.silu(z)
        yg = y.reshape(S, G, ds // G)
        yg = yg / jnp.sqrt(jnp.mean(yg * yg, -1, keepdims=True)
                           + c["rms_norm_eps"])
        return ((yg.reshape(S, ds) * w["norm.weight"])
                @ w["out_proj.weight"]), kept


@functools.partial(jax.jit, static_argnames=("cfg", "width"))
def _mlp_part(y, x, wg, wu, wd, start, cfg, width):
    """y + one part of the MLP's width: `width` columns of W_g and W_u and
    rows of W_d from `start` (traced: one compile serves every part)."""
    m_gate = dict(cfg)["mlp_multipliers"][0]
    with jax.default_matmul_precision("highest"):
        f = jnp.float32
        wg, wu = (jax.lax.dynamic_slice_in_dim(w, start, width, 1).astype(f)
                  for w in (wg, wu))
        wd = jax.lax.dynamic_slice_in_dim(wd, start, width, 0).astype(f)
        return y + (jax.nn.silu((x @ wg) * m_gate) * (x @ wu)) @ wd


@functools.partial(jax.jit, static_argnames=("cfg",))
def _normed(h, w, cfg):
    return _rms(h, w.astype(jnp.float32), dict(cfg)["rms_norm_eps"])


@jax.jit
def _add(h, a, ca, b, cb):
    return h + ca * a + cb * b


def _mlp(h, w, cfg):
    c = dict(cfg)
    x = _normed(h, w["pre_ff_layernorm.weight"], cfg)
    wg, wu, wd = (w[f"feed_forward.{k}_proj.weight"]
                  for k in ("gate", "up", "down"))
    f = wg.shape[1]
    width = f // MLP_PARTS if f % MLP_PARTS == 0 else f
    y = jnp.zeros_like(h)
    for start in range(0, f, width):
        y = _mlp_part(y, x, wg, wu, wd, jnp.int32(start), cfg, width)
    return _add(h, y, c["mlp_multipliers"][1], y, 0.0)


@jax.jit
def _embed(table, ids, multiplier):
    return table[ids].astype(jnp.float32) * multiplier


def hidden(W, ids, num_heads, states_at=None, **kw):
    """Hidden states [S, d] (before the final norm) of ONE sequence ids
    [S]. Right padding is harmless: nothing here looks ahead. With
    `states_at` (a position) also every layer's mixer state after that
    position: (h, [L, H, P, N])."""
    states = []
    upto = jnp.int32(-1 if states_at is None else states_at)
    cfg = settings(**kw)
    c = dict(cfg)
    h = _embed(W["model.embed_tokens.weight"], ids,
               c["embedding_multiplier"])
    i = 0
    while f"model.layers.{i}.input_layernorm.weight" in W:
        p = f"model.layers.{i}."
        w = {k[len(p):]: v for k, v in W.items() if k.startswith(p)}

        def sub(prefix):
            return {k[len(prefix):]: v for k, v in w.items()
                    if k.startswith(prefix)}
        u = _normed(h, w["input_layernorm.weight"], cfg)
        mixed, state = _mixer(u, sub("mamba."), cfg, upto)
        states.append(state)
        h = _add(h, mixed, c["ssm_out_multiplier"],
                 _attention(u, sub("self_attn."), num_heads, cfg),
                 c["attention_out_multiplier"])
        h = _mlp(h, w, cfg)
        i += 1
    return h if states_at is None else (h, jnp.stack(states))


def _vocab_block(V):
    """Columns of the head a call: the widest divisor of V that is no wider
    than VOCAB_BLOCK (261,120 = 16 x 16,320), so that every block has one
    shape."""
    n = -(-V // VOCAB_BLOCK)
    while V % n:
        n += 1
    return V // n


@functools.partial(jax.jit, static_argnames=("cfg", "width"))
def _head(x, norm_w, head_w, start, cfg, width):
    """Logits of positions x [T, d] over `width` columns of the head from
    `start` (traced: one compile serves every block)."""
    c = dict(cfg)
    with jax.default_matmul_precision("highest"):
        w = jax.lax.dynamic_slice_in_dim(head_w, start, width, 1)
        return (_rms(x, norm_w.astype(jnp.float32), c["rms_norm_eps"])
                @ w.astype(jnp.float32)) * c["lm_head_multiplier"]


def _head_blocks(W, x, cfg):
    """The logits of x [T, d], a block of the vocabulary at a time."""
    head = W["lm_head.weight"]
    width = _vocab_block(head.shape[1])
    for lo in range(0, head.shape[1], width):
        yield lo, _head(x, W["model.final_layernorm.weight"], head,
                        jnp.int32(lo), cfg, width)


@functools.partial(jax.jit, static_argnames=("cfg", "width"))
def _block_short(x, ids, lo, norm_w, head_w, start, best, took, cfg, width):
    """HEAD_BLOCK positions from `lo` of ONE sequence against `width`
    columns of the head from `start`: the running best logit of each
    position, and the logit of the token that follows it where that token
    lies in these columns. Every shape is fixed, `lo` and `start` are
    traced: one compile serves every sequence, block and run."""
    pad = jnp.zeros((HEAD_BLOCK, x.shape[1]), x.dtype)
    xb = jax.lax.dynamic_slice_in_dim(jnp.concatenate([x, pad]), lo,
                                      HEAD_BLOCK, 0)
    want = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([ids, jnp.zeros((HEAD_BLOCK + 1,), ids.dtype)]),
        lo + 1, HEAD_BLOCK, 0)
    lg = _head(xb, norm_w, head_w, start, cfg, width)
    here = (want >= start) & (want < start + width)
    got = jnp.take_along_axis(
        lg, jnp.clip(want - start, 0, width - 1)[:, None], -1)[:, 0]
    return jnp.maximum(best, lg.max(-1)), jnp.where(here, got, took)


def forward(W, ids, num_heads, **kw):
    """Logits [B, S, V] of a full causal pass over token ids [B, S]: for
    the tests' sizes and chip_smoke's positions (a window's tokens go
    through `shortfalls`)."""
    cfg = settings(**kw)
    return jnp.stack([
        jnp.concatenate([lg for _, lg in _head_blocks(
            W, hidden(W, jnp.asarray(row, jnp.int32), num_heads, **kw),
            cfg)], -1)
        for row in ids])


def logits_at(W, ids, positions, num_heads, states_at=None, **kw):
    """Logits [len(positions), V] of ONE sequence ids [S] at `positions`;
    with `states_at` also the layers' mixer states after that position."""
    cfg = settings(**kw)
    x = hidden(W, jnp.asarray(ids, jnp.int32), num_heads,
               states_at=states_at, **kw)
    x, states = x if states_at is not None else (x, None)
    x = x[jnp.asarray(positions)]
    logits = jnp.concatenate([lg for _, lg in _head_blocks(W, x, cfg)], -1)
    return logits if states_at is None else (logits, states)


def token_shortfalls(W, sequences, prompt_lens, num_heads, pad_to=1536,
                     **kw):
    """For each sequence (prompt + generated ids) the amount by which each
    generated token's reference logit falls short of the reference's best
    logit at that position: 0 where the token is the reference's argmax.
    One sequence at a time, all padded to ONE width, a multiple of `pad_to`
    (the cell's sequences hold at most 1,536 tokens: one compile of each
    piece, whatever the seed draws), the head over blocks of HEAD_BLOCK
    positions by VOCAB_BLOCK columns."""
    cfg = settings(**kw)
    width = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    head = W["lm_head.weight"]
    vocab = _vocab_block(head.shape[1])
    out = []
    for seq, p in zip(sequences, prompt_lens):
        ids = np.zeros((width,), np.int32)      # on the host: no program
        ids[:len(seq)] = seq                    # of the sequence's length
        ids = jnp.asarray(ids)
        x = hidden(W, ids, num_heads, **kw)
        short = []
        # position t predicts token t + 1: the generated ones are p..len-1
        for lo in range(p - 1, len(seq) - 1, HEAD_BLOCK):
            hi = min(lo + HEAD_BLOCK, len(seq) - 1)
            best = jnp.full((HEAD_BLOCK,), -jnp.inf, jnp.float32)
            took = jnp.zeros((HEAD_BLOCK,), jnp.float32)
            for start in range(0, head.shape[1], vocab):
                best, took = _block_short(
                    x, ids, jnp.int32(lo), W["model.final_layernorm.weight"],
                    head, jnp.int32(start), best, took, cfg, vocab)
            short.append(np.asarray(best - took)[:hi - lo])
        out.append(np.concatenate(short))
    return out


def limits():
    """The two limits of this configuration's comparison, from its file."""
    run = _file()["run"]
    return run["near_margin"], run["mean_limit"]


def shortfalls(W, sequences, prompt_lens, num_heads, **kw):
    """What `drivers/serve.py` holds to `near_margin`: it takes the LARGEST
    entry of what this returns. The entries are every generated token's
    shortfall (`token_shortfalls`) and, last, ONE entry for the second
    limit, for which the driver has no argument (as
    benchmark/reference/glm-4.7-flash.py does it): the MEAN shortfall of the
    checked tokens, scaled so that it passes `near_margin` exactly when the
    mean passes `mean_limit`. A window or a state that is wrong for a
    position or two (padding run through the scan, a window forgotten)
    throws single tokens far, and the largest shortfall shows it. What moves
    every position a little and few far shows in the mean, which grows with
    the SQUARE of the noise in the logits (a token leaves the reference's
    argmax in proportion to the noise, and by as much): a lower precision of
    what is cached (the state held in bfloat16, pages and window in float8),
    a neighbour's state, pages or K/V head. The share of tokens that are not
    the reference's argmax grows only in proportion and is printed, not
    held. Both limits and the readings they lie between: `near_margin_why`
    in the configuration's file, PERF.md section 6."""
    out = token_shortfalls(W, sequences, prompt_lens, num_heads, **kw)
    flat = np.concatenate(out)
    margin, mean_limit = limits()
    mean = float(flat.mean())
    print(f"[reference] {flat.size} tokens: mean shortfall {mean:.3e} "
          f"(limit {mean_limit}), largest {flat.max():.6f} (limit {margin}); "
          f"{np.mean(flat > 0):.4f} are not the reference's argmax, p99 "
          f"{np.quantile(flat, 0.99):.6f}, p99.9 "
          f"{np.quantile(flat, 0.999):.6f}", flush=True)
    return out + [np.asarray([margin * mean / mean_limit], flat.dtype)]
