"""GLM-4.7-Flash (`glm4_moe_lite`, zai-org): a decoder with latent (MLA)
attention and routed + shared experts, served by `serving.GenerationEngine`
as its second decode family (`serving/latent_family.py`).

For a token with residual `h`, no bias anywhere:

    RMSNorm(x; w) = w * x / sqrt(mean(x^2) + eps)                 (float32)
    h <- h + MLA(RMSNorm(h; w_in));  h <- h + FFN_l(RMSNorm(h; w_post))
    logits = RMSNorm(h; w_f) . W_head            (embedding and head untied)

    MLA:  c_q = RMSNorm(x.W_qa; w_qa);  q = c_q.W_qb -> H x [q_nope | q_rope]
          [c_kv | k_r] = x.W_kva;  c_kv <- RMSNorm(c_kv; w_kva)
          [k_nope_h | v_h] = c_kv.W_kvb per head
          q_rope_h, k_r rotated by RoPE (rotate-half, all rope dims); k_r is
          ONE vector shared by the heads
          score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_r(s))
                          / sqrt(nope + rope), causal, softmax in float32
          out = concat_h(sum_s p.v_h(s)) . W_o
    cached per token and layer: [c_kv after its norm | k_r after RoPE]
    decode absorbs W_kvb = [W_UK_h | W_UV_h]: q~_h = q_nope_h.W_UK_h^T,
          score = (q~_h.c_kv(s) + q_rope_h.k_r(s)) / sqrt(nope + rope),
          o~_h = sum_s p.c_kv(s),  o_h = o~_h.W_UV_h
    FFN_l, l < first_k_dense:  (silu(x.W_g) * x.W_u).W_d
    FFN_l otherwise:  s = sigmoid(x.W_r) (float32); the k experts are the
          top-k of s + b; g_i = scale * s_i / (sum_topk s_j + 1e-20);
          y = sum_i g_i E_i(x) + E_shared(x), every E a SwiGLU

ONE expression of the block (`_block_pass`) serves the Layer's forward, the
engine's prefill and its decode step: what differs is the attention hook —
expanded and causal over the batch's own rows (prefill), or absorbed over a
cache the caller owns (decode) — as `models/gpt.py` does it. Numerics:
weights and activations in the parameters' dtype (bfloat16 as served),
every product accumulated in float32, RMSNorm / softmax / router in float32.

Not built: the multi-token-prediction module (`num_nextn_predict_layers`);
the family's published inference code leaves it out of the forward pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor, apply_op
from ..nn import initializer as I
from ..ops.moe_ops import moe_grouped_experts, moe_route

__all__ = ["GlmMoeLiteConfig", "GlmMoeLiteForCausalLM", "glm_embed",
           "glm_logits", "glm_prefill", "glm_decode_step", "glm_forward",
           "glm_absorb", "glm_weight_shapes", "rope_rotate"]


class GlmMoeLiteConfig:
    """Defaults are the published GLM-4.7-Flash config.json; `tiny` is the
    CPU tests' size."""

    def __init__(self, vocab_size=154880, hidden_size=2048,
                 num_hidden_layers=47, num_heads=20, q_lora_rank=768,
                 kv_lora_rank=512, qk_nope_head_dim=192,
                 qk_rope_head_dim=64, v_head_dim=256,
                 intermediate_size=10240, moe_intermediate_size=1536,
                 n_routed_experts=64, n_shared_experts=1,
                 num_experts_per_tok=4, routed_scaling_factor=1.8,
                 first_k_dense_replace=1, rms_norm_eps=1e-5,
                 rope_theta=1e6, max_position_embeddings=202752,
                 initializer_range=0.02, router_bias_std=0.01,
                 dtype="bfloat16"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_heads = num_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.first_k_dense_replace = first_k_dense_replace
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.router_bias_std = router_bias_std
        self.dtype = dtype
        if qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotate-half)")
        if num_experts_per_tok > n_routed_experts:
            raise ValueError("more experts per token than experts")

    @property
    def latent_dim(self):
        """Width of one cached row: [c_kv | k_r]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def num_expert_layers(self):
        return max(0, self.num_hidden_layers - self.first_k_dense_replace)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=384, hidden_size=64, num_hidden_layers=3,
                    num_heads=4, q_lora_rank=24, kv_lora_rank=32,
                    qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
                    intermediate_size=160, moe_intermediate_size=48,
                    n_routed_experts=8, num_experts_per_tok=2,
                    max_position_embeddings=256, dtype="float32")
        base.update(kw)
        return cls(**base)


# -- the functional math (Layer forward AND the engine's programs) ----------


def _mm(a, w):
    """Product in the operands' dtype, accumulated in float32."""
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)


def _products(dtype):
    """The matmul precision the math is traced under. The framework pins
    "highest" (float32 models run true-float32 products); with bfloat16
    operands every product is exact at one pass and the pin only breaks
    XLA:TPU's grouped-matmul kernel (Mosaic: "Bad lhs type" for a bf16
    operand under an fp32 contract precision — compiled for the v5e,
    PR 27), so a non-float32 model is traced under "default". The router
    asks for "highest" itself, on operands it upcasts."""
    return jax.default_matmul_precision(
        "highest" if jnp.dtype(dtype) == jnp.float32 else "default")


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope_rotate(x, pos, theta):
    """RoPE over ALL of x's last axis, rotate-half pairing (dim i with
    i + n/2), float32. x [T, ..., n]; pos [T]."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [T, n/2]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., n // 2:], x32[..., :n // 2]], -1)
    return (x32 * cos + half * sin).astype(x.dtype)


def _swiglu(x, wg, wu, wd):
    return _mm((jax.nn.silu(_mm(x, wg)) * _mm(x, wu)).astype(x.dtype), wd)


def glm_embed(W, ids):
    with jax.named_scope("embed"):
        return W["embed"][ids]


def glm_logits(W, h, cfg):
    """Final RMSNorm + the untied head over hidden states `h` [..., d]:
    float32 logits."""
    with jax.named_scope("lm_head"), _products(h.dtype):
        return _mm(_rms(h, W["norm"], cfg.rms_norm_eps), W["head"])


def glm_absorb(lw, q_nope, q_rope, cfg):
    """The decode query of the absorbed form: per head
    `[q_nope . W_UK^T | q_rope]`, as wide as a cached row. q_nope
    [T, H, nope]; q_rope [T, H, rope]."""
    with jax.named_scope("absorb"):
        r, H = cfg.kv_lora_rank, cfg.num_heads
        w_uk = lw["kv_b"].reshape(r, H, -1)[..., :cfg.qk_nope_head_dim]
        q_lat = jnp.einsum("thn,rhn->thr", q_nope, w_uk,
                           preferred_element_type=jnp.float32)
        return jnp.concatenate([q_lat.astype(q_nope.dtype), q_rope], -1)


def _unabsorb(lw, o_lat, cfg):
    """o~ [T, H, r] (sums of cached c_kv rows) through W_UV: [T, H*v]."""
    with jax.named_scope("absorb"):
        r, H = cfg.kv_lora_rank, cfg.num_heads
        w_uv = lw["kv_b"].reshape(r, H, -1)[..., cfg.qk_nope_head_dim:]
        o = jnp.einsum("thr,rhv->thv", o_lat.astype(w_uv.dtype), w_uv,
                       preferred_element_type=jnp.float32)
        return o.reshape(o.shape[0], -1).astype(w_uv.dtype)


def _block_pass(W, h, pos, attend, cfg, live=None):
    """Every layer over tokens h [T, d] at positions pos [T].

        attend(layer, lw, q_nope [T,H,nope], q_rope [T,H,rope],
               row [T, r + rope]) -> [T, H*v]

    owns the attention (and the cache, in decode): `row` is what is
    cached for the token, `[c_kv after its norm | k_r after RoPE]`.
    `live` [T] marks rows that are real tokens for the experts' count
    (None: all). Returns (h, experts_hit int32: distinct experts that got
    a live row, summed over the expert layers)."""
    hit = jnp.zeros((), jnp.int32)
    with _products(h.dtype):
        for i, lw in enumerate(W["layers"]):
            h = _attention(i, lw, h, pos, attend, cfg)
            h, n = _ffn(i, lw, h, cfg, live)
            hit = hit + n
    return h, hit


def _residual(h, delta):
    return (h.astype(jnp.float32) + delta).astype(h.dtype)


def _attention(i, lw, h, pos, attend, cfg):
    H, nope = cfg.num_heads, cfg.qk_nope_head_dim
    r, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    T = h.shape[0]
    # scopes are names on the device (`layer_3/mla/rope` in a profiler
    # trace), nothing else: tools/trace_report.py groups by them
    with jax.named_scope(f"layer_{i}/mla"):
        x = _rms(h, lw["ln_in"], eps)
        with jax.named_scope("q_latent"):
            c_q = _rms(_mm(x, lw["q_a"]).astype(x.dtype), lw["q_norm"], eps)
            q = _mm(c_q, lw["q_b"]).astype(x.dtype).reshape(T, H, -1)
        with jax.named_scope("kv_latent"):
            kv = _mm(x, lw["kv_a"]).astype(x.dtype)
            c_kv = _rms(kv[:, :r], lw["kv_norm"], eps)
        with jax.named_scope("rope"):
            q_rope = rope_rotate(q[..., nope:], pos, cfg.rope_theta)
            k_r = rope_rotate(kv[:, r:], pos, cfg.rope_theta)
        row = jnp.concatenate([c_kv, k_r], -1)
        o = attend(i, lw, q[..., :nope], q_rope, row)
        with jax.named_scope("out"):
            return _residual(h, _mm(o, lw["o"]))


def _ffn(i, lw, h, cfg, live):
    """(h + FFN_i(RMSNorm(h)), experts hit in this layer)."""
    x = _rms(h, lw["ln_post"], cfg.rms_norm_eps)
    f = lw["ffn"]
    if "router" not in f:
        with jax.named_scope(f"layer_{i}/mlp"):
            return (_residual(h, _swiglu(x, f["gate"], f["up"], f["down"])),
                    jnp.zeros((), jnp.int32))
    with jax.named_scope(f"layer_{i}/moe"):
        idx, gates = moe_route(x, f["router"], f["bias"],
                               cfg.num_experts_per_tok,
                               cfg.routed_scaling_factor)
        y, n = moe_grouped_experts(x, idx, gates, f["gate"], f["up"],
                                   f["down"], live=live)
        with jax.named_scope("shared"):
            y = y + _swiglu(x, f["s_gate"], f["s_up"], f["s_down"])
        return _residual(h, y), n


def _expanded_attend(cfg, S):
    """Causal attention within ONE sequence of S rows, in the expanded
    form: per-head keys `[k_nope_h | k_r]` and values from c_kv . W_kvb."""
    H, nope, r = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    scale = (nope + cfg.qk_rope_head_dim) ** -0.5

    def attend(layer, lw, q_nope, q_rope, row):
        with jax.named_scope("latent_attend"):
            kvb = _mm(row[:, :r], lw["kv_b"]).astype(row.dtype)
            kvb = kvb.reshape(S, H, -1)
            k_nope, v = kvb[..., :nope], kvb[..., nope:]
            s = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("qhe,ke->hqk", q_rope, row[:, r:],
                              preferred_element_type=jnp.float32)) * scale
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            o = jnp.einsum("hqk,khv->qhv", p, v,
                           preferred_element_type=jnp.float32)
            return o.reshape(S, -1).astype(v.dtype)
    return attend


def glm_prefill(W, ids, cfg, live=None):
    """One causal pass over ONE prompt, ids [S] (right padding is harmless:
    no real position attends to it). Returns (h [S, d] before the final
    norm, rows [L, S, r + rope] for the caller's cache, experts_hit)."""
    S = ids.shape[0]
    rows = []
    inner = _expanded_attend(cfg, S)

    def attend(layer, lw, q_nope, q_rope, row):
        rows.append(row)
        return inner(layer, lw, q_nope, q_rope, row)

    h, hit = _block_pass(W, glm_embed(W, ids), jnp.arange(S), attend, cfg,
                         live=live)
    return h, jnp.stack(rows), hit


def glm_decode_step(W, tok, pos, cache, write_row, attend_rows, cfg,
                    live=None):
    """One position for each of B rows against an abstract latent cache.

    tok [B] int32; pos [B] int32 (THIS token's position: its row is written
    before attending, so attention covers t <= pos). The cache is an
    opaque pytree threaded through the hooks:

        write_row(cache, layer, row [B, r + rope], pos) -> cache
        attend_rows(cache, layer, q [B, H, r + rope], pos) -> [B, H, r]

    `attend_rows` gets the absorbed query and owns the softmax scale.
    Returns (logits [B, V] float32, cache, experts_hit)."""
    box = [cache]

    def attend(layer, lw, q_nope, q_rope, row):
        box[0] = write_row(box[0], layer, row, pos)
        q = glm_absorb(lw, q_nope, q_rope, cfg)
        o_lat = attend_rows(box[0], layer, q, pos)
        return _unabsorb(lw, o_lat, cfg)

    h, hit = _block_pass(W, glm_embed(W, tok), pos, attend, cfg, live=live)
    return glm_logits(W, h, cfg), box[0], hit


def glm_forward(W, ids, cfg):
    """Logits [B, S, V] (float32) of a full causal pass over ids [B, S]:
    the Layer's forward. Sequences are independent, so it is `glm_prefill`
    once a sequence."""
    def one(row_ids):
        h, _, _ = glm_prefill(W, row_ids, cfg)
        return glm_logits(W, h, cfg)
    return jnp.stack([one(ids[b]) for b in range(ids.shape[0])])


def glm_weight_shapes(cfg):
    """The pytree `GlmMoeLiteForCausalLM.decode_weights()` returns, as
    `jax.ShapeDtypeStruct` leaves: for counting parameters and for
    compiling the programs at the published sizes without the weights."""
    d, H, f = cfg.hidden_size, cfg.num_heads, cfg.moe_intermediate_size
    E, r = cfg.n_routed_experts, cfg.kv_lora_rank

    def a(*shape, dtype=cfg.dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    def swiglu(width, prefix=""):
        return {prefix + "gate": a(d, width), prefix + "up": a(d, width),
                prefix + "down": a(width, d)}

    def layer(i):
        ffn = (swiglu(cfg.intermediate_size)
               if i < cfg.first_k_dense_replace else
               {"router": a(d, E), "bias": a(E, dtype="float32"),
                "gate": a(E, d, f), "up": a(E, d, f), "down": a(E, f, d),
                **swiglu(f * cfg.n_shared_experts, "s_")})
        return {"ln_in": a(d), "q_a": a(d, cfg.q_lora_rank),
                "q_norm": a(cfg.q_lora_rank),
                "q_b": a(cfg.q_lora_rank, H * (cfg.qk_nope_head_dim
                                               + cfg.qk_rope_head_dim)),
                "kv_a": a(d, cfg.latent_dim), "kv_norm": a(r),
                "kv_b": a(r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "o": a(H * cfg.v_head_dim, d), "ln_post": a(d), "ffn": ffn}

    return {"embed": a(cfg.vocab_size, d), "norm": a(d),
            "head": a(d, cfg.vocab_size),
            "layers": [layer(i) for i in range(cfg.num_hidden_layers)]}


# -- the Layer: parameters, state_dict, forward ------------------------------


class _Weights(nn.Layer):
    """A holder of named parameters; the math is functional (above)."""

    def __init__(self, cfg, shapes, ones=()):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        for name, shape in shapes.items():
            setattr(self, name, self.create_parameter(
                list(shape), dtype=cfg.dtype,
                default_initializer=I.Constant(1.0) if name in ones
                else init))


class _Linear(_Weights):
    def __init__(self, cfg, d_in, d_out):
        super().__init__(cfg, {"weight": (d_in, d_out)})


class _Norm(_Weights):
    def __init__(self, cfg, d):
        super().__init__(cfg, {"weight": (d,)}, ones=("weight",))


class _SwiGLU(nn.Layer):
    def __init__(self, cfg, width):
        super().__init__()
        self.gate_proj = _Linear(cfg, cfg.hidden_size, width)
        self.up_proj = _Linear(cfg, cfg.hidden_size, width)
        self.down_proj = _Linear(cfg, width, cfg.hidden_size)

    def leaves(self, prefix=""):
        return {prefix + "gate": self.gate_proj.weight._value,
                prefix + "up": self.up_proj.weight._value,
                prefix + "down": self.down_proj.weight._value}


class _Router(_Weights):
    def __init__(self, cfg):
        super().__init__(cfg, {"weight": (cfg.hidden_size,
                                          cfg.n_routed_experts)})
        # the selection bias: a buffer, float32, moved by the load
        # balancer in training and never by a gradient. Drawn from the
        # seed so that it is not a no-op
        self.register_buffer("e_score_correction_bias", Tensor(
            I.Normal(0.0, cfg.router_bias_std)([cfg.n_routed_experts],
                                               "float32")))


class _MoE(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        d, f, E = (cfg.hidden_size, cfg.moe_intermediate_size,
                   cfg.n_routed_experts)
        self.gate = _Router(cfg)
        # stacked [E, ...]: what the grouped product reads in place
        self.experts = _Weights(cfg, {"gate_proj": (E, d, f),
                                      "up_proj": (E, d, f),
                                      "down_proj": (E, f, d)})
        self.shared_experts = _SwiGLU(cfg, f * cfg.n_shared_experts)

    def leaves(self):
        e = self.experts
        return {"router": self.gate.weight._value,
                "bias": self.gate.e_score_correction_bias._value,
                "gate": e.gate_proj._value, "up": e.up_proj._value,
                "down": e.down_proj._value,
                **self.shared_experts.leaves("s_")}


class _Attention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        d, H = cfg.hidden_size, cfg.num_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.q_a_proj = _Linear(cfg, d, cfg.q_lora_rank)
        self.q_a_layernorm = _Norm(cfg, cfg.q_lora_rank)
        self.q_b_proj = _Linear(cfg, cfg.q_lora_rank, H * qk)
        self.kv_a_proj_with_mqa = _Linear(cfg, d, cfg.latent_dim)
        self.kv_a_layernorm = _Norm(cfg, cfg.kv_lora_rank)
        self.kv_b_proj = _Linear(
            cfg, cfg.kv_lora_rank,
            H * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = _Linear(cfg, H * cfg.v_head_dim, d)


class _Block(nn.Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.input_layernorm = _Norm(cfg, cfg.hidden_size)
        self.self_attn = _Attention(cfg)
        self.post_attention_layernorm = _Norm(cfg, cfg.hidden_size)
        self.mlp = (_SwiGLU(cfg, cfg.intermediate_size)
                    if index < cfg.first_k_dense_replace else _MoE(cfg))

    def leaves(self):
        a = self.self_attn
        return {"ln_in": self.input_layernorm.weight._value,
                "q_a": a.q_a_proj.weight._value,
                "q_norm": a.q_a_layernorm.weight._value,
                "q_b": a.q_b_proj.weight._value,
                "kv_a": a.kv_a_proj_with_mqa.weight._value,
                "kv_norm": a.kv_a_layernorm.weight._value,
                "kv_b": a.kv_b_proj.weight._value,
                "o": a.o_proj.weight._value,
                "ln_post": self.post_attention_layernorm.weight._value,
                "ffn": self.mlp.leaves()}


class _Decoder(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.embed_tokens = _Linear(cfg, cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([_Block(cfg, i) for i
                                    in range(cfg.num_hidden_layers)])
        self.norm = _Norm(cfg, cfg.hidden_size)


class GlmMoeLiteForCausalLM(nn.Layer):
    def __init__(self, cfg: GlmMoeLiteConfig = None, **kwargs):
        super().__init__()
        self.config = cfg = cfg or GlmMoeLiteConfig(**kwargs)
        self.model = _Decoder(cfg)
        self.lm_head = _Linear(cfg, cfg.hidden_size, cfg.vocab_size)
        self._forward_jit = None

    def decode_weights(self):
        """The weight pytree of the functional math: the parameters' own
        arrays (no copy, no restacking — a second copy of 9 GB does not
        fit beside the first), value-fresh because they ride jitted
        programs as ARGUMENTS."""
        m = self.model
        return {"embed": m.embed_tokens.weight._value,
                "norm": m.norm.weight._value,
                "head": self.lm_head.weight._value,
                "layers": [blk.leaves() for blk in m.layers]}

    def decode_family(self):
        """What `serving.GenerationEngine` asks a model for."""
        from ..serving.latent_family import LatentFamily
        return LatentFamily(self)

    def forward(self, input_ids):
        """Logits [B, S, V], float32: one op over the functional pass,
        compiled once a shape (inference; the parameters enter as
        values, so it is value-fresh)."""
        if self._forward_jit is None:
            cfg = self.config
            self._forward_jit = jax.jit(
                lambda W, ids: glm_forward(W, ids.astype(jnp.int32), cfg))
        W = self.decode_weights()
        return apply_op("glm_moe_lite_forward",
                        lambda ids: self._forward_jit(W, ids),
                        (input_ids,), {})
