"""Falcon-H1 (`falcon_h1`, tiiuae): a decoder whose every block runs a Mamba-2
state-space mixer and grouped-query attention IN PARALLEL on one normed
input, then a gated MLP; served by `serving.GenerationEngine` as its third
decode family (`serving/hybrid_family.py`).

No bias anywhere except the convolution's. `RMSNorm(x; w) = w x /
sqrt(mean x^2 + eps)`. The twelve muP multipliers are the configuration's
(names as published):

    h0 = E[ids] * embedding_multiplier
    per block:  u = RMSNorm(h; w_in)
                h <- h + ssm_out_multiplier * Mixer(ssm_in_multiplier * u)
                      + attention_out_multiplier * Attn(attention_in_multiplier * u)
                h <- h + MLP(RMSNorm(h; w_ff))
    logits = lm_head_multiplier * RMSNorm(h; w_f) W_head

    Attn:  q = x W_q -> Hq heads x D;  k = key_multiplier * x W_k -> Hkv x D;
           v = x W_v -> Hkv x D;  rotate-half RoPE over all D dims on q and k;
           causal softmax(q k^T / sqrt(D)) v, query head i reads KV head
           i // (Hq / Hkv);  out = (.) W_o
    MLP:   y = [silu(mlp_multipliers[0] * x W_g) * (x W_u)] W_d * mlp_multipliers[1]
    Mixer (Mamba-2; d_ssm = H x P, G groups, state N, convolution K wide):
           [z | xBC | dt] = (x W_in) * m;  m is `ssm_multipliers` spread over
           the segments z, x, B, C, dt
           xBC_t <- silu(b_c + sum_j w_c[:, j] xBC_{t-K+1+j})   (causal, depthwise)
           [x | B | C] = xBC, widths d_ssm | G N | G N; head i reads group i // (H / G)
           dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)       (per head)
           S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
           y <- GroupRMSNorm(y * silu(z); w_n)  (gate first: `mamba_norm_before_gate`
                false; the mean of squares over each of the G groups)
           out = y W_out

ONE expression of the block (`_block_pass`) serves the Layer's forward, the
engine's prefill and its decode step; what differs is the two hooks that own
the per-sequence state: `attend` (causal over the batch's own rows, or over
paged K/V) and `mix` (the chunked scan `ops/ssm_ops.ssd_chunked_scan` from a
zero state, or one step `ssm_decode_update` over a slot's carried state and
convolution window). Numerics: weights and activations in the parameters'
dtype (bfloat16 as served), every product accumulated in float32; norms,
softmax, softplus, decays, running sums and the state in float32. `A_log`,
`dt_bias` and `D` are float32 parameters.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import apply_op
from ..nn import initializer as I
from ..ops.ssm_ops import causal_conv_window, ssd_chunked_scan
from .glm_moe import _mm, _products, _residual, _rms, rope_rotate

__all__ = ["FalconH1Config", "FalconH1ForCausalLM", "fh1_logits",
           "fh1_prefill", "fh1_decode_step", "fh1_forward",
           "fh1_weight_shapes", "fh1_ssm_inputs"]


class FalconH1Config:
    """Defaults are the published Falcon-H1-34B-Instruct config.json; `tiny`
    is the CPU tests' size."""

    def __init__(self, vocab_size=261120, hidden_size=5120,
                 num_hidden_layers=72, num_heads=20, num_key_value_heads=4,
                 head_dim=128, intermediate_size=21504, mamba_d_ssm=4096,
                 mamba_n_heads=32, mamba_d_head=128, mamba_n_groups=2,
                 mamba_d_state=256, mamba_d_conv=4, mamba_chunk_size=128,
                 rms_norm_eps=1e-5, rope_theta=1e11,
                 max_position_embeddings=262144,
                 embedding_multiplier=5.656854249492381,
                 lm_head_multiplier=0.0078125,
                 attention_in_multiplier=1.0,
                 attention_out_multiplier=0.0375,
                 key_multiplier=0.011048543456039804,
                 ssm_in_multiplier=0.25,
                 ssm_out_multiplier=0.08838834764831845,
                 ssm_multipliers=(0.3535533905932738, 0.25,
                                  0.1767766952966369, 0.5,
                                  0.3535533905932738),
                 mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
                 initializer_range=0.02, mamba_in_proj_range=None,
                 mamba_d_init=1.0, mamba_dt_range=(1e-3, 1e-1),
                 dtype="bfloat16"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_heads = num_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.mamba_d_ssm = mamba_d_ssm
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_n_groups = mamba_n_groups
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_chunk_size = mamba_chunk_size
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)    # 1e11 as published: no int32
        self.max_position_embeddings = max_position_embeddings
        self.embedding_multiplier = embedding_multiplier
        self.lm_head_multiplier = lm_head_multiplier
        self.attention_in_multiplier = attention_in_multiplier
        self.attention_out_multiplier = attention_out_multiplier
        self.key_multiplier = key_multiplier
        self.ssm_in_multiplier = ssm_in_multiplier
        self.ssm_out_multiplier = ssm_out_multiplier
        self.ssm_multipliers = tuple(ssm_multipliers)
        self.mlp_multipliers = tuple(mlp_multipliers)
        self.initializer_range = initializer_range
        # three draws no config.json gives (`_Mamba`): the spread of the
        # mixer's in-projection (None: `initializer_range`), D, and the
        # range softplus(dt_bias) is log-uniform over
        self.mamba_in_proj_range = mamba_in_proj_range
        self.mamba_d_init = mamba_d_init
        self.mamba_dt_range = tuple(mamba_dt_range)
        self.dtype = dtype
        if head_dim % 2:
            raise ValueError("head_dim must be even (rotate-half)")
        if num_heads % num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        if mamba_n_heads * mamba_d_head != mamba_d_ssm:
            raise ValueError("mamba_d_ssm must be heads x head width")
        if mamba_n_heads % mamba_n_groups:
            raise ValueError("mixer heads must be a multiple of its groups")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has 5 entries (z, x, B, C, dt),"
                             " mlp_multipliers 2 (gate, down)")

    @property
    def conv_dim(self):
        """Channels of the convolution: [x | B | C]."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self):
        """[z | xBC | dt]."""
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    @property
    def state_shape(self):
        """One sequence's mixer state of ONE layer: [H, P, N]."""
        return (self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=384, hidden_size=64, num_hidden_layers=2,
                    num_heads=4, num_key_value_heads=2, head_dim=16,
                    intermediate_size=96, mamba_d_ssm=64, mamba_n_heads=8,
                    mamba_d_head=8, mamba_n_groups=2, mamba_d_state=16,
                    mamba_chunk_size=8, max_position_embeddings=512,
                    dtype="float32")
        base.update(kw)
        return cls(**base)


# -- the functional math (Layer forward AND the engine's programs) ----------


def _mup_vector(cfg):
    """`ssm_multipliers` spread over the in-projection's columns."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return np.concatenate([np.full((w,), m, np.float32)
                           for w, m in zip(widths, cfg.ssm_multipliers)])


def fh1_embed(W, ids, cfg):
    with jax.named_scope("embed"):
        e = W["embed"][ids]
        return (e.astype(jnp.float32) * cfg.embedding_multiplier).astype(
            e.dtype)


def fh1_logits(W, h, cfg):
    """Final RMSNorm, the untied head and its multiplier over hidden states
    `h` [..., d]: float32 logits."""
    with jax.named_scope("lm_head"), _products(h.dtype):
        return (_mm(_rms(h, W["norm"], cfg.rms_norm_eps), W["head"])
                * cfg.lm_head_multiplier)


def fh1_ssm_inputs(lw, xbc, dt, cfg, live=None):
    """What the recurrence reads of the convolved, activated `xbc` [T, C]
    and the raw `dt` [T, H]: (x [T, H, P], B [T, G, N], C [T, G, N], dt
    [T, H] float32 after its bias and softplus — 0 where `live` [T] is
    False, which makes those positions exact no-ops — and A [H] float32)."""
    H, P = cfg.mamba_n_heads, cfg.mamba_d_head
    G, N = cfg.mamba_n_groups, cfg.mamba_d_state
    T = xbc.shape[0]
    x = xbc[:, :H * P].reshape(T, H, P)
    B = xbc[:, H * P:H * P + G * N].reshape(T, G, N)
    C = xbc[:, H * P + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + lw["dt_bias"].astype(jnp.float32)[None])
    if live is not None:
        dt = jnp.where(live[:, None], dt, 0.0)
    A = -jnp.exp(lw["A_log"].astype(jnp.float32))
    return x, B, C, dt, A


def _mixer(i, lw, u, mix, cfg):
    """Mixer(ssm_in_multiplier * u) for rows u [T, d]: in-projection, the
    hook `mix(layer, lw, xbc [T, C] pre-activation, dt [T, H] raw) ->
    y [T, H, P] float32` (convolution, activation, recurrence, + D x: the
    hook owns window and state), gated group norm, out-projection."""
    T, dt_ = u.shape[0], u.dtype
    ds, C = cfg.mamba_d_ssm, cfg.conv_dim
    G = cfg.mamba_n_groups
    with jax.named_scope(f"layer_{i}/ssm"):
        with jax.named_scope("in_proj"):
            x = (u.astype(jnp.float32) * cfg.ssm_in_multiplier).astype(dt_)
            zxd = (_mm(x, lw["in_proj"]) * _mup_vector(cfg)[None]).astype(dt_)
            z, xbc, dt = zxd[:, :ds], zxd[:, ds:ds + C], zxd[:, ds + C:]
        y = mix(i, lw, xbc, dt)                      # [T, H, P] float32
        with jax.named_scope("gate_norm"):
            y = y.reshape(T, ds) * jax.nn.silu(z.astype(jnp.float32))
            yg = y.reshape(T, G, ds // G)
            yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                                    + cfg.rms_norm_eps)
            y = (yg.reshape(T, ds)
                 * lw["ssm_norm"].astype(jnp.float32)).astype(dt_)
        with jax.named_scope("out_proj"):
            return _mm(y, lw["out_proj"])            # float32


def _attention(i, lw, u, pos, attend, cfg):
    """Attn(attention_in_multiplier * u): projections, RoPE, the hook
    `attend(layer, q [T, Hq, D], k [T, Hkv, D], v [T, Hkv, D]) ->
    [T, Hq * D]` (it owns the cache and the softmax scale), out-projection."""
    T, dt_ = u.shape[0], u.dtype
    Hq, Hkv, D = cfg.num_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope(f"layer_{i}/attn"):
        with jax.named_scope("qkv"):
            x = u
            if cfg.attention_in_multiplier != 1:
                x = (u.astype(jnp.float32)
                     * cfg.attention_in_multiplier).astype(dt_)
            q = _mm(x, lw["q"]).astype(dt_).reshape(T, Hq, D)
            k = (_mm(x, lw["k"]) * cfg.key_multiplier).astype(dt_).reshape(
                T, Hkv, D)
            v = _mm(x, lw["v"]).astype(dt_).reshape(T, Hkv, D)
        with jax.named_scope("rope"):
            q = rope_rotate(q, pos, cfg.rope_theta)
            k = rope_rotate(k, pos, cfg.rope_theta)
        o = attend(i, q, k, v)
        with jax.named_scope("out"):
            return _mm(o.astype(dt_), lw["o"])       # float32


def _mlp(i, lw, h, cfg):
    m_gate, m_down = cfg.mlp_multipliers
    with jax.named_scope(f"layer_{i}/mlp"):
        x = _rms(h, lw["ln_ff"], cfg.rms_norm_eps)
        a = (jax.nn.silu(_mm(x, lw["gate"]) * m_gate)
             * _mm(x, lw["up"])).astype(x.dtype)
        return _residual(h, _mm(a, lw["down"]) * m_down)


def _block_pass(W, h, pos, attend, mix, cfg):
    """Every block over tokens h [T, d] at positions pos [T]; `attend` and
    `mix` are the hooks of `_attention` and `_mixer`."""
    with _products(h.dtype):
        for i, lw in enumerate(W["layers"]):
            u = _rms(h, lw["ln_in"], cfg.rms_norm_eps)
            delta = (cfg.ssm_out_multiplier * _mixer(i, lw, u, mix, cfg)
                     + cfg.attention_out_multiplier
                     * _attention(i, lw, u, pos, attend, cfg))
            h = _mlp(i, lw, _residual(h, delta), cfg)
    return h


def _causal_attend(cfg, S):
    """Causal grouped-query attention within ONE sequence of S rows."""
    Hq, Hkv, D = cfg.num_heads, cfg.num_key_value_heads, cfg.head_dim
    scale = D ** -0.5

    def attend(layer, q, k, v):
        with jax.named_scope("attend"):
            qg = q.reshape(S, Hkv, Hq // Hkv, D)
            s = jnp.einsum("qgrd,kgd->grqk", qg, k,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            o = jnp.einsum("grqk,kgd->qgrd", p, v,
                           preferred_element_type=jnp.float32)
            return o.reshape(S, Hq * D)
    return attend


def fh1_prefill(W, ids, cfg, length=None):
    """One causal pass over ONE prompt, ids [S] padded on the right to a
    multiple of the scan's chunk (or shorter than one chunk); `length` (a
    traced scalar; None: S) is the number of real positions. Padding is
    exact: attention is causal, `dt` is 0 past `length` (decay 1, input 0),
    and the window is taken at `length`. Returns (h [S, d] before the final
    norm, K and V [L, Hkv, S, D] for the caller's pages, state
    [L, H, P, N] float32 after position `length - 1`, window [L, K, C]: the
    last K pre-activation convolution rows before `length`)."""
    S = ids.shape[0]
    live = None if length is None else jnp.arange(S) < length
    ks, vs, states, windows = [], [], [], []
    inner = _causal_attend(cfg, S)

    def attend(layer, q, k, v):
        ks.append(jnp.moveaxis(k, 0, 1))
        vs.append(jnp.moveaxis(v, 0, 1))
        return inner(layer, q, k, v)

    def mix(layer, lw, xbc, dt):
        with jax.named_scope("conv"):
            conv, window = causal_conv_window(xbc, lw["conv_w"],
                                              lw["conv_b"], length)
            act = jax.nn.silu(conv.astype(jnp.float32)).astype(xbc.dtype)
        with jax.named_scope("scan"):
            x, B, C, dtp, A = fh1_ssm_inputs(lw, act, dt, cfg, live)
            y, state = ssd_chunked_scan(x, dtp, A, B, C,
                                        chunk=cfg.mamba_chunk_size)
            y = y + (lw["D"].astype(jnp.float32)[None, :, None]
                     * x.astype(jnp.float32))
        windows.append(window)
        states.append(state)
        return y

    h = _block_pass(W, fh1_embed(W, ids, cfg), jnp.arange(S), attend, mix,
                    cfg)
    return (h, jnp.stack(ks), jnp.stack(vs), jnp.stack(states),
            jnp.stack(windows))


def fh1_decode_step(W, tok, pos, cache, write_kv, attend_kv, conv_step,
                    state_step, cfg, live=None):
    """One position for each of M rows against an abstract cache.

    tok [M] int32; pos [M] int32 (THIS token's position: its K/V row is
    written before attending, so attention covers t <= pos). The cache is
    an opaque pytree threaded through the hooks:

        write_kv(cache, layer, k [M, Hkv, D], v, pos) -> cache
        attend_kv(cache, layer, q [M, Hq, D], pos) -> [M, Hq, D]
        conv_step(cache, layer, lw, xbc [M, C]) -> (cache, conv [M, C])
            the row's window shifted, the K-term sum with its bias
        state_step(cache, layer, decay [M, H], dtx [M, H, P], B, C
                   [M, G, N]) -> (cache, y [M, H, P] float32 = S C)

    `live` [M] marks the rows that hold a request: a dead row's state and
    window are left as they are (decay 1, input 0; the hooks see `live`
    through their closure for the window). Returns (logits [M, V] float32,
    cache)."""
    box = [cache]
    M = tok.shape[0]

    def attend(layer, q, k, v):
        box[0] = write_kv(box[0], layer, k, v, pos)
        with jax.named_scope("attend"):
            return attend_kv(box[0], layer, q, pos).reshape(M, -1)

    def mix(layer, lw, xbc, dt):
        with jax.named_scope("conv"):
            box[0], conv = conv_step(box[0], layer, lw, xbc)
            act = jax.nn.silu(conv.astype(jnp.float32)).astype(xbc.dtype)
        with jax.named_scope("state_update"):
            x, B, C, dtp, A = fh1_ssm_inputs(lw, act, dt, cfg, live)
            decay = jnp.exp(dtp * A[None])                  # 1 where dead
            dtx = dtp[..., None] * x.astype(jnp.float32)    # 0 where dead
            box[0], y = state_step(box[0], layer, decay, dtx, B, C)
            return y + (lw["D"].astype(jnp.float32)[None, :, None]
                        * x.astype(jnp.float32))

    h = _block_pass(W, fh1_embed(W, tok, cfg), pos, attend, mix, cfg)
    return fh1_logits(W, h, cfg), box[0]


def fh1_forward(W, ids, cfg):
    """Logits [B, S, V] (float32) of a full causal pass over ids [B, S]:
    the Layer's forward. Sequences are independent, so it is `fh1_prefill`
    once a sequence, padded on the right to whole chunks of the scan."""
    S = ids.shape[1]
    Q = cfg.mamba_chunk_size
    pad = (-S) % Q if S > Q else 0

    def one(row):
        row = jnp.pad(row, (0, pad))
        h = fh1_prefill(W, row, cfg, length=S if pad else None)[0]
        return fh1_logits(W, h[:S], cfg)
    return jnp.stack([one(ids[b]) for b in range(ids.shape[0])])


def fh1_weight_shapes(cfg):
    """The pytree `FalconH1ForCausalLM.decode_weights()` returns, as
    `jax.ShapeDtypeStruct` leaves: for counting parameters and for compiling
    the programs at the published sizes without the weights."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    Hq, Hkv, D = cfg.num_heads, cfg.num_key_value_heads, cfg.head_dim
    H = cfg.mamba_n_heads

    def a(*shape, dtype=cfg.dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    def layer():
        return {"ln_in": a(d), "q": a(d, Hq * D), "k": a(d, Hkv * D),
                "v": a(d, Hkv * D), "o": a(Hq * D, d),
                "in_proj": a(d, cfg.in_proj_dim),
                "conv_w": a(cfg.conv_dim, cfg.mamba_d_conv),
                "conv_b": a(cfg.conv_dim),
                "dt_bias": a(H, dtype="float32"),
                "A_log": a(H, dtype="float32"), "D": a(H, dtype="float32"),
                "ssm_norm": a(cfg.mamba_d_ssm),
                "out_proj": a(cfg.mamba_d_ssm, d),
                "ln_ff": a(d), "gate": a(d, f), "up": a(d, f),
                "down": a(f, d)}

    return {"embed": a(cfg.vocab_size, d), "norm": a(d),
            "head": a(d, cfg.vocab_size),
            "layers": [layer() for _ in range(cfg.num_hidden_layers)]}


# -- the Layer: parameters, state_dict, forward ------------------------------


class _Mapped(I.Initializer):
    """`fn` of another initializer's float32 draw."""

    def __init__(self, base, fn):
        self.base, self.fn = base, fn

    def __call__(self, shape, dtype="float32"):
        return self.fn(self.base(shape, "float32")).astype(dtype)


class _Weights(nn.Layer):
    """A holder of named parameters; the math is functional (above).
    `shapes`: name -> (shape, initializer or None for N(0, range), dtype or
    None for the model's)."""

    def __init__(self, cfg, shapes):
        super().__init__()
        for name, (shape, init, dtype) in shapes.items():
            setattr(self, name, self.create_parameter(
                list(shape), dtype=dtype or cfg.dtype,
                default_initializer=init
                or I.Normal(0.0, cfg.initializer_range)))


def _linear(cfg, d_in, d_out, spread=None):
    init = None if spread is None else I.Normal(0.0, spread)
    return _Weights(cfg, {"weight": ((d_in, d_out), init, None)})


def _norm(cfg, d):
    return _Weights(cfg, {"weight": ((d,), I.Constant(1.0), None)})


class _Attention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        d, D = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(cfg, d, cfg.num_heads * D)
        self.k_proj = _linear(cfg, d, cfg.num_key_value_heads * D)
        self.v_proj = _linear(cfg, d, cfg.num_key_value_heads * D)
        self.o_proj = _linear(cfg, cfg.num_heads * D, d)


class _Mamba(nn.Layer):
    """The Mamba-2 convention for what a config never gives: A = -uniform
    [1, 16], dt = softplus(dt_bias) log-uniform in [0.001, 0.1], D = 1, the
    convolution uniform(+-1/2) = 1/sqrt(K): decays of 0.2-0.999 a step, so
    a state carries memory over hundreds of positions. Three of the draws
    are the configuration's to change (`mamba_in_proj_range`, `mamba_d_init`,
    `mamba_dt_range`): under the convention's values and the published muP
    multipliers `D x` is nine tenths of `y`, and the state a sequence
    carries hardly reaches its logits (benchmark/configs/falcon-h1-34b.json
    `assumed` has the draws the benchmark serves and why)."""

    def __init__(self, cfg):
        super().__init__()
        H, K = cfg.mamba_n_heads, cfg.mamba_d_conv
        self.in_proj = _linear(cfg, cfg.hidden_size, cfg.in_proj_dim,
                               cfg.mamba_in_proj_range)
        bound = 1.0 / math.sqrt(K)
        self.conv1d = _Weights(cfg, {
            "weight": ((cfg.conv_dim, K), I.Uniform(-bound, bound), None),
            "bias": ((cfg.conv_dim,), I.Uniform(-bound, bound), None)})
        lo, hi = (math.log(v) for v in cfg.mamba_dt_range)
        self.scalars = _Weights(cfg, {
            "A_log": ((H,), _Mapped(I.Uniform(1.0, 16.0), jnp.log),
                      "float32"),
            # the inverse of softplus at dt: dt + log(-expm1(-dt))
            "dt_bias": ((H,), _Mapped(
                I.Uniform(lo, hi),
                lambda u: jnp.exp(u) + jnp.log(-jnp.expm1(-jnp.exp(u)))),
                "float32"),
            "D": ((H,), I.Constant(cfg.mamba_d_init), "float32")})
        self.norm = _norm(cfg, cfg.mamba_d_ssm)
        self.out_proj = _linear(cfg, cfg.mamba_d_ssm, cfg.hidden_size)


class _MLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        d, f = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _linear(cfg, d, f)
        self.up_proj = _linear(cfg, d, f)
        self.down_proj = _linear(cfg, f, d)


class _Block(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.input_layernorm = _norm(cfg, cfg.hidden_size)
        self.self_attn = _Attention(cfg)
        self.mamba = _Mamba(cfg)
        self.pre_ff_layernorm = _norm(cfg, cfg.hidden_size)
        self.feed_forward = _MLP(cfg)

    def leaves(self):
        a, m, f = self.self_attn, self.mamba, self.feed_forward
        return {"ln_in": self.input_layernorm.weight._value,
                "q": a.q_proj.weight._value, "k": a.k_proj.weight._value,
                "v": a.v_proj.weight._value, "o": a.o_proj.weight._value,
                "in_proj": m.in_proj.weight._value,
                "conv_w": m.conv1d.weight._value,
                "conv_b": m.conv1d.bias._value,
                "dt_bias": m.scalars.dt_bias._value,
                "A_log": m.scalars.A_log._value, "D": m.scalars.D._value,
                "ssm_norm": m.norm.weight._value,
                "out_proj": m.out_proj.weight._value,
                "ln_ff": self.pre_ff_layernorm.weight._value,
                "gate": f.gate_proj.weight._value,
                "up": f.up_proj.weight._value,
                "down": f.down_proj.weight._value}


class _Decoder(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.embed_tokens = _linear(cfg, cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([_Block(cfg) for _
                                    in range(cfg.num_hidden_layers)])
        self.final_layernorm = _norm(cfg, cfg.hidden_size)


class FalconH1ForCausalLM(nn.Layer):
    def __init__(self, cfg: FalconH1Config = None, **kwargs):
        super().__init__()
        self.config = cfg = cfg or FalconH1Config(**kwargs)
        self.model = _Decoder(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)
        self._forward_jit = None

    def decode_weights(self):
        """The weight pytree of the functional math: the parameters' own
        arrays (no copy: a second copy of 10.5 GB does not fit beside the
        first), value-fresh because they ride jitted programs as
        ARGUMENTS."""
        m = self.model
        return {"embed": m.embed_tokens.weight._value,
                "norm": m.final_layernorm.weight._value,
                "head": self.lm_head.weight._value,
                "layers": [blk.leaves() for blk in m.layers]}

    def decode_family(self):
        """What `serving.GenerationEngine` asks a model for."""
        from ..serving.hybrid_family import HybridFamily
        return HybridFamily(self)

    def forward(self, input_ids):
        """Logits [B, S, V], float32: one op over the functional pass,
        compiled once a shape (inference; the parameters enter as values,
        so it is value-fresh)."""
        if self._forward_jit is None:
            cfg = self.config
            self._forward_jit = jax.jit(
                lambda W, ids: fh1_forward(W, ids.astype(jnp.int32), cfg))
        W = self.decode_weights()
        return apply_op("falcon_h1_forward",
                        lambda ids: self._forward_jit(W, ids),
                        (input_ids,), {})
