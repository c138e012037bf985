"""GPT-style causal decoder (capability target: PaddleNLP GPT / ERNIE-3.0
decoder stacks on the reference). TPU-first: causal flash attention,
optional ring-attention sequence parallelism, optional MoE FFN with
expert parallelism over the 'ep' mesh axis."""
from __future__ import annotations

import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor, apply_op
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import creation, manipulation

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "MoEFeedForward",
           "gpt_prefill", "gpt_prefill_extend", "gpt_decode_step",
           "gpt_spec_verify", "gpt_logits", "dense_cache_write",
           "dense_cache_attend", "decode_weight_specs",
           "shard_decode_weights"]


# -- shared decode math (generate() AND serving.GenerationEngine) -----------
#
# One anchored re-expression of the Layer forward, cache-layout-agnostic:
# `gpt_prefill` runs the batched causal pass and RETURNS per-layer K/V
# (the caller writes them into its cache — contiguous [L,B,H,T,D]
# buffers for generate(), paged pools for the generation engine), and
# `gpt_decode_step` advances one position through caller-supplied
# `write_kv`/`attend` hooks. Keeping both consumers on these exact
# expressions is what makes the engine's greedy decode bit-anchored to
# tests/test_generate.py's full-forward oracle (within one compiled
# shape; cross-shape is float tolerance, the standard XLA caveat).


def _gen_ln(x, w, b):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.var(x, -1, keepdims=True)
    return (x - m) / jnp.sqrt(v + 1e-5) * w + b


def _gen_w(w, dtype):
    """Resolve one decode-weight leaf: a raw array passes through; a
    weight-only-quantized leaf `(q_int8 [in,out], scale [out])` —
    produced by decode_weights() for quantization.WeightOnlyLinear
    projections — dequantizes HERE, inside the traced math, so the
    HBM-resident form stays int8 and XLA fuses convert+mul into the
    consuming matmul (the fp32 weight is a fused temporary only)."""
    if isinstance(w, tuple):
        q, s = w
        return q.astype(dtype) * s.astype(dtype)
    return w


def gpt_logits(W, h):
    """Final LN + tied LM head over hidden states `h` [..., E]."""
    import jax

    lnfw, lnfb = W["lnf"]
    with jax.named_scope("lm_head"):
        return _gen_ln(h, lnfw, lnfb) @ W["wte"].T


def _gen_block_pass(W, h, attend, *, num_heads, reduce=None):
    """The ONE batched transformer-block loop both prefill flavors run:
    LN → QKV heads → `attend(layer, q, k, v)` → output proj + MLP
    residuals, collecting per-layer K/V. The attention expression is
    the only thing that differs between a full prefill (causal within
    the batch) and a tail prefill (cached context + within-tail) — it
    lives in the caller's hook, so the `_gen_w` quant hooks, gelu
    flavor and head-reshape discipline can never diverge between the
    two paths. Returns `(h, ks, vs)`.

    Tensor parallel (ISSUE 19): under a shard_map body the projection
    leaves are head-sharded SLICES — wq/wk/wv/w1 column-parallel
    (num_heads is then the LOCAL head count), wo/w2 row-parallel — and
    `reduce` is the per-block partial-sum reduction (lax.psum over the
    'tp' axis), applied to the row-parallel matmul outputs BEFORE the
    replicated bias + residual add, the Megatron discipline that keeps
    bo/b2 counted exactly once. Head/hidden reshapes derive the local
    width from the tensors (-1), never from the replicated E."""
    import jax

    B, S = h.shape[:2]
    H = num_heads
    ks, vs = [], []
    for i, (l1w, l1b, wq, bq, wk, bk, wv, bv, wo, bo, l2w, l2b,
            w1, b1, w2, b2) in enumerate(W["blocks"]):
        def heads(t):
            return t.reshape(B, S, H, -1).transpose(0, 2, 1, 3)
        # scopes are names on the device (`layer_3/attn/...` in a
        # profiler trace), nothing else: tools/trace_report.py groups by
        # them
        with jax.named_scope(f"layer_{i}/attn"):
            x = _gen_ln(h, l1w, l1b)
            q = heads(x @ _gen_w(wq, x.dtype) + bq)
            k = heads(x @ _gen_w(wk, x.dtype) + bk)
            v = heads(x @ _gen_w(wv, x.dtype) + bv)
            ks.append(k)
            vs.append(v)
            o = attend(i, q, k, v)
            o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
            ow = o @ _gen_w(wo, h.dtype)
            if reduce is not None:
                ow = reduce(ow)
            h = h + (ow + bo)
        with jax.named_scope(f"layer_{i}/mlp"):
            x2 = _gen_ln(h, l2w, l2b)
            mw = jax.nn.gelu(x2 @ _gen_w(w1, h.dtype) + b1,
                             approximate=False) @ _gen_w(w2, h.dtype)
            if reduce is not None:
                mw = reduce(mw)
            h = h + (mw + b2)
    return h, jnp.stack(ks), jnp.stack(vs)


def gpt_prefill(W, ids, *, num_heads, scale, reduce=None):
    """One batched causal pass over the whole prompt — the MXU sees
    [B,S,E] matmuls, not S tiny ones. Returns `(h, ks, vs)`: `h` [B,S,E]
    post-blocks pre-ln_f hidden states (project the position you need
    through `gpt_logits`), `ks`/`vs` [L,B,H,S,D] per-layer K/V for the
    caller's cache. Right-padded prompts are safe: causal masking keeps
    pad positions out of every real position's softmax (exact -1e30 →
    0.0), so the last REAL position's logits are pad-invariant within
    one compiled shape."""
    import jax

    _, S = ids.shape
    with jax.named_scope("embed"):
        h = W["wte"][ids] + W["wpe"][jnp.arange(S)][None]

    def attend(layer, q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    return _gen_block_pass(W, h, attend, num_heads=num_heads,
                           reduce=reduce)


def gpt_prefill_extend(W, ids, positions, ctx_attend, *, num_heads,
                       scale, reduce=None):
    """Batched causal pass over a prompt TAIL whose prefix K/V already
    lives in an external cache (the prefix-cache hit path, ISSUE 12).

    ids [B, S_t] tail token ids at absolute positions `positions` [S_t]
    (the caller clamps pad positions into range); attention is
    delegated per layer to

        ctx_attend(layer, q, k, v) -> [B, H, S_t, D]

    with q/k/v the tail's own projections — the hook attends each tail
    query over (external cached context + the given within-tail K/V)
    and owns the cache layout, masks AND the softmax scale, the same
    seam discipline as `gpt_decode_step`'s write_kv/attend. Returns
    `(h, ks, vs)` exactly like `gpt_prefill` ([B,S_t,E] hidden states,
    [L,B,H,S_t,D] per-layer tail K/V for the caller's cache writes) —
    both flavors share `_gen_block_pass`, so the block math literally
    cannot diverge from the full-prefill oracle."""
    import jax

    del scale  # the ctx_attend hook owns the scale (kept for symmetry)
    with jax.named_scope("embed"):
        h = W["wte"][ids] + W["wpe"][positions][None]
    return _gen_block_pass(W, h, ctx_attend, num_heads=num_heads,
                           reduce=reduce)


def gpt_spec_verify(W, toks, positions, ctx_attend, *, num_heads,
                    reduce=None):
    """Batched multi-position decode block for speculative verification
    (ISSUE 14): score a [B, K+1] block of tokens — each row's current
    token followed by K draft tokens — at PER-ROW absolute positions
    [B, K+1] in one `_gen_block_pass`, so verifying K drafts costs one
    forward over K+1 positions instead of K+1 decode dispatches.

    Attention is delegated per layer to

        ctx_attend(layer, q, k, v) -> [B, H, K+1, D]

    with q/k/v the block's own projections — the hook attends each
    block query over (cached context + the given within-block K/V) and
    owns the cache layout, masks AND the softmax scale, exactly the
    `gpt_prefill_extend` seam batched over rows. Returns `(h, ks, vs)`
    ([B,K+1,E] hidden states, [L,B,H,K+1,D] per-layer block K/V for the
    caller's — acceptance-masked — cache writes). Sharing
    `_gen_block_pass` is what anchors verification to the decode-step
    oracle: the block math literally cannot diverge."""
    import jax

    with jax.named_scope("embed"):
        h = W["wte"][toks] + W["wpe"][positions]
    return _gen_block_pass(W, h, ctx_attend, num_heads=num_heads,
                           reduce=reduce)


def gpt_decode_step(W, tok, pos, cache, write_kv, attend, *, num_heads,
                    scale, reduce=None):
    """Single-position forward against an abstract KV cache.

    tok [B] int32; pos scalar or [B] int32 (THIS token's position —
    written before attending, so attention covers t <= pos). The cache
    is an opaque pytree threaded functionally through the hooks:

        write_kv(cache, layer, k, v, pos) -> cache     (k/v [B, H, D])
        attend(cache, layer, q, pos)      -> [B, H, D]

    Returns (logits [B, V], cache). Under tensor parallelism
    `num_heads` is the LOCAL head count and `reduce` the per-block
    psum — the `_gen_block_pass` contract, same placement."""
    import jax

    B = tok.shape[0]
    H = num_heads
    with jax.named_scope("embed"):
        h = W["wte"][tok] + W["wpe"][pos]
    for i, (l1w, l1b, wq, bq, wk, bk, wv, bv, wo, bo, l2w, l2b,
            w1, b1, w2, b2) in enumerate(W["blocks"]):
        with jax.named_scope(f"layer_{i}/attn"):
            x = _gen_ln(h, l1w, l1b)
            q = (x @ _gen_w(wq, x.dtype) + bq).reshape(B, H, -1)
            k = (x @ _gen_w(wk, x.dtype) + bk).reshape(B, H, -1)
            v = (x @ _gen_w(wv, x.dtype) + bv).reshape(B, H, -1)
            cache = write_kv(cache, i, k, v, pos)
            o = attend(cache, i, q, pos).reshape(B, -1)
            ow = o @ _gen_w(wo, h.dtype)
            if reduce is not None:
                ow = reduce(ow)
            h = h + (ow + bo)
        with jax.named_scope(f"layer_{i}/mlp"):
            x2 = _gen_ln(h, l2w, l2b)
            mw = jax.nn.gelu(x2 @ _gen_w(w1, h.dtype) + b1,
                             approximate=False) @ _gen_w(w2, h.dtype)
            if reduce is not None:
                mw = reduce(mw)
            h = h + (mw + b2)
    return gpt_logits(W, h), cache


def decode_weight_specs(W, axis="tp"):
    """PartitionSpec pytree matching a `decode_weights()` pytree, for
    head-sharded tensor parallelism over mesh axis `axis` (ISSUE 19,
    Megatron layout): wq/wk/wv/w1 column-parallel (output dim — the
    heads axis, since E = H*D — sharded, so their biases shard too),
    wo/w2 row-parallel (input dim sharded, biases replicated: they are
    added once AFTER the psum), embeddings/LNs replicated. A
    weight-only-quantized `(q_int8 [in,out], scale [out])` leaf shards
    its scale with the output dim it scales: split for column-parallel,
    replicated for row-parallel. The same tree serves as shard_map
    in_specs and as NamedSharding specs for the one-time device_put."""
    from jax.sharding import PartitionSpec as P
    rep = P()

    def col(w):
        return ((P(None, axis), P(axis)) if isinstance(w, tuple)
                else P(None, axis))

    def row(w):
        return ((P(axis, None), rep) if isinstance(w, tuple)
                else P(axis, None))

    blocks = [
        (rep, rep, col(wq), P(axis), col(wk), P(axis), col(wv), P(axis),
         row(wo), rep, rep, rep, col(w1), P(axis), row(w2), rep)
        for (l1w, l1b, wq, bq, wk, bk, wv, bv, wo, bo, l2w, l2b,
             w1, b1, w2, b2) in W["blocks"]]
    return {"wte": rep, "wpe": rep, "lnf": (rep, rep), "blocks": blocks}


def shard_decode_weights(W, mesh, axis="tp"):
    """One-time `device_put` of a `decode_weights()` pytree onto `mesh`
    under the `decode_weight_specs` layout. Explicit recursion instead
    of tree_map: a quantized `(q_int8, scale)` leaf is a tuple — the
    same container `lnf` uses — so structure-blind mapping can't tell
    a two-leaf container from a paired leaf."""
    import jax
    from jax.sharding import NamedSharding
    specs = decode_weight_specs(W, axis=axis)

    def put(w, s):
        if isinstance(w, tuple):
            return tuple(jax.device_put(x, NamedSharding(mesh, ss))
                         for x, ss in zip(w, s))
        return jax.device_put(w, NamedSharding(mesh, s))

    return {
        "wte": put(W["wte"], specs["wte"]),
        "wpe": put(W["wpe"], specs["wpe"]),
        "lnf": tuple(put(w, s) for w, s in zip(W["lnf"], specs["lnf"])),
        "blocks": [tuple(put(w, s) for w, s in zip(blk, sblk))
                   for blk, sblk in zip(W["blocks"], specs["blocks"])],
    }


def dense_cache_write(cache, layer, k, v, pos):
    """Contiguous-buffer cache hook: cache = (kbufs, vbufs) with shape
    [L,B,H,T,D], scalar `pos` (the whole batch decodes in lockstep —
    generate()'s layout)."""
    import jax

    kb, vb = cache
    kb = jax.lax.dynamic_update_slice(
        kb, k[None, :, :, None, :], (layer, 0, 0, pos, 0))
    vb = jax.lax.dynamic_update_slice(
        vb, v[None, :, :, None, :], (layer, 0, 0, pos, 0))
    return kb, vb


def dense_cache_attend(scale):
    """Attend hook over the contiguous cache (masked softmax over every
    position <= pos; same expression the paged reference gathers into —
    ops/paged_ops.cached_attention)."""
    from ..ops.paged_ops import cached_attention

    def attend(cache, layer, q, pos):
        kb, vb = cache
        return cached_attention(q, kb[layer], vb[layer], pos, scale)
    return attend


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072,
                 max_position_embeddings=1024, dropout=0.1,
                 use_moe=False, num_experts=8, moe_top_k=1,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.dropout = dropout
        self.use_moe = use_moe
        self.num_experts = num_experts
        self.moe_top_k = moe_top_k
        self.initializer_range = initializer_range

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                 intermediate_size=128, max_position_embeddings=128)
        d.update(kw)
        return cls(**d)


class CausalSelfAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.q_proj = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.k_proj = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.v_proj = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.out_proj = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.dropout = cfg.dropout

    def forward(self, x, use_ring=False):
        b, s, e = x.shape
        def shape(t):
            t = manipulation.reshape(t, [b, s, self.num_heads, self.head_dim])
            return manipulation.transpose(t, [0, 2, 1, 3])
        q, k, v = shape(self.q_proj(x)), shape(self.k_proj(x)), \
            shape(self.v_proj(x))
        if use_ring:
            from ..parallel.mesh import get_mesh
            from ..parallel.ring_attention import shard_map_ring_attention
            mesh = get_mesh()
            out = apply_op(
                "ring_attention",
                lambda qq, kk, vv: shard_map_ring_attention(
                    qq, kk, vv, mesh, causal=True), (q, k, v), {})
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.dropout,
                training=self.training)
        out = manipulation.transpose(out, [0, 2, 1, 3])
        out = manipulation.reshape(out, [b, s, e])
        return self.out_proj(out)


class MoEFeedForward(nn.Layer):
    """Expert-parallel MoE FFN (new subsystem — absent in the reference;
    designed GSPMD-style: expert weights [E, d, f] sharded over 'ep',
    tokens dispatched with a dense one-hot combine so the whole layer is
    einsums XLA can partition; top-1 switch routing)."""

    def __init__(self, hidden_size, intermediate_size, num_experts,
                 top_k=1):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        init = I.XavierUniform()
        self.gate = nn.Linear(hidden_size, num_experts)
        self.w_up = self.create_parameter(
            [num_experts, hidden_size, intermediate_size],
            default_initializer=init)
        self.w_down = self.create_parameter(
            [num_experts, intermediate_size, hidden_size],
            default_initializer=init)
        from ..distributed.tensor_parallel import mark_sharding
        mark_sharding(self.w_up, "ep", None, None)
        mark_sharding(self.w_down, "ep", None, None)

    def forward(self, x):
        def impl(h, wu, wd, gate_w, gate_b):
            import jax
            b, s, d = h.shape
            logits = h @ gate_w + gate_b  # [b,s,E]
            probs = jax.nn.softmax(logits, axis=-1)
            idx = jnp.argmax(probs, axis=-1)  # top-1 switch
            onehot = jax.nn.one_hot(idx, wu.shape[0], dtype=h.dtype)
            gatev = jnp.sum(probs * onehot, axis=-1, keepdims=True)
            # dense dispatch: [b,s,E,d] routed tokens (zero elsewhere)
            up = jnp.einsum("bse,bsd,edf->bsef", onehot, h, wu)
            act = jax.nn.gelu(up)
            down = jnp.einsum("bsef,efd->bsd", act, wd)
            return down * gatev
        return apply_op("moe_ffn", impl,
                        (x, self.w_up, self.w_down, self.gate.weight,
                         self.gate.bias), {})


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = CausalSelfAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        if cfg.use_moe:
            self.mlp = MoEFeedForward(cfg.hidden_size, cfg.intermediate_size,
                                      cfg.num_experts, cfg.moe_top_k)
        else:
            self.mlp = nn.Sequential(
                nn.Linear(cfg.hidden_size, cfg.intermediate_size),
                nn.GELU(),
                nn.Linear(cfg.intermediate_size, cfg.hidden_size))
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x, use_ring=False):
        x = x + self.dropout(self.attn(self.ln1(x), use_ring=use_ring))
        x = x + self.dropout(self.mlp(self.ln2(x)))
        return x


class _GPTEmbeddingStage(nn.Layer):
    """Pipeline pre-section: token+position embedding (shares the GPT
    model's parameter Tensors; see parallel/pipeline.py)."""

    def __init__(self, gpt):
        super().__init__()
        self.wte = gpt.wte
        self.wpe = gpt.wpe
        self.drop = gpt.drop

    def forward(self, input_ids):
        b, s = input_ids.shape
        pos = creation.arange(0, s, dtype="int64")
        pos = manipulation.reshape(pos, [1, s])
        return self.drop(self.wte(input_ids) + self.wpe(pos))


class _GPTHeadStage(nn.Layer):
    """Pipeline post-section: final LN (+ tied LM head when lm=True)."""

    def __init__(self, gpt, lm):
        super().__init__()
        self.ln_f = gpt.ln_f
        self._lm = lm
        if lm:
            self.wte = gpt.wte  # tied head; dedup'd by named_parameters

    def forward(self, h):
        h = self.ln_f(h)
        if not self._lm:
            return h
        from ..ops.linalg import matmul
        return matmul(h, self.wte.weight, transpose_y=True)


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig = None, **kwargs):
        super().__init__()
        cfg = cfg or GPTConfig(**kwargs)
        self.config = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=init)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                                weight_attr=init)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, use_ring=False):
        b, s = input_ids.shape
        pos = creation.arange(0, s, dtype="int64")
        pos = manipulation.reshape(pos, [1, s])
        h = self.drop(self.wte(input_ids) + self.wpe(pos))
        for blk in self.blocks:
            h = blk(h, use_ring=use_ring)
        return self.ln_f(h)

    def pipeline_sections(self):
        """(pre, blocks, post) for heterogeneous pipeline parallelism
        (reference PipelineOptimizer splits a Program by device_guard,
        `fluid/optimizer.py:3718`; here the model declares its stages)."""
        return (_GPTEmbeddingStage(self), self.blocks,
                _GPTHeadStage(self, lm=False))


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg: GPTConfig = None, **kwargs):
        super().__init__()
        self.gpt = GPTModel(cfg, **kwargs)

    def forward(self, input_ids, use_ring=False):
        h = self.gpt(input_ids, use_ring=use_ring)
        from ..ops.linalg import matmul
        return matmul(h, self.gpt.wte.weight, transpose_y=True)

    def pipeline_sections(self):
        return (_GPTEmbeddingStage(self.gpt), self.gpt.blocks,
                _GPTHeadStage(self.gpt, lm=True))

    def decode_weights(self):
        """The decode-math weight pytree shared by `generate()` and
        `serving.GenerationEngine`: raw jnp leaves (value-fresh after
        training steps — they ride jitted programs as ARGUMENTS, never
        baked constants). A projection replaced by
        `quantization.WeightOnlyLinear` (quantize_weights) contributes a
        `(q_int8, scale)` leaf instead of a float array — the integer
        tensor is what rides HBM; `_gen_w` dequantizes inside the traced
        matmul (int4 layers unpack once to int8 here, still 4x smaller
        than fp32)."""
        gpt = self.gpt
        if gpt.config.use_moe:
            raise NotImplementedError(
                "decode_weights()/generate()/GenerationEngine with "
                "GPTConfig(use_moe=True): MoEFeedForward is a training "
                "layer (top-1, dense one-hot dispatch). The served expert "
                "model is models/glm_moe.py (GlmMoeLiteForCausalLM: "
                "routed + shared experts, grouped matmul)")

        def w(lin):
            leaf = getattr(lin, "quant_decode_leaf", None)
            return leaf() if leaf is not None else lin.weight._value

        return {
            "wte": gpt.wte.weight._value, "wpe": gpt.wpe.weight._value,
            "lnf": (gpt.ln_f.weight._value, gpt.ln_f.bias._value),
            "blocks": [(
                blk.ln1.weight._value, blk.ln1.bias._value,
                w(blk.attn.q_proj), blk.attn.q_proj.bias._value,
                w(blk.attn.k_proj), blk.attn.k_proj.bias._value,
                w(blk.attn.v_proj), blk.attn.v_proj.bias._value,
                w(blk.attn.out_proj),
                blk.attn.out_proj.bias._value,
                blk.ln2.weight._value, blk.ln2.bias._value,
                w(blk.mlp[0]), blk.mlp[0].bias._value,
                w(blk.mlp[2]), blk.mlp[2].bias._value)
                for blk in gpt.blocks],
        }

    def decode_family(self):
        """What `serving.GenerationEngine` asks a model for
        (serving/decode_family.py)."""
        from ..serving.gpt_family import GPTFamily
        return GPTFamily(self)

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 top_k=None, temperature=1.0, seed=0):
        """Autoregressive decoding with a fixed-size KV cache (reference
        ecosystem: PaddleNLP GenerationMixin.generate/greedy_search).

        TPU design: ONE jax.jit program — prefill is a single batched
        [B,S,E] causal pass writing the whole prompt's K/V, decode is a
        `lax.scan` over `max_new_tokens` steps; K/V live in
        [L, B, H, T, D] buffers written in place with
        dynamic_update_slice, so shapes are static for every step and
        nothing retraces per token. Weights ride as jit ARGUMENTS
        (value-fresh after training steps) and the compiled program is
        memoized per static config. Eval-mode math (no dropout); the
        decode math is the shared `gpt_prefill`/`gpt_decode_step`
        internals (also serving.GenerationEngine's), anchored to the
        Layer forward by tests/test_generate.py's full-forward oracle."""
        import jax

        gpt = self.gpt
        cfg = gpt.config
        ids = jnp.asarray(
            input_ids._value if isinstance(input_ids, Tensor)
            else input_ids, jnp.int32)
        B, S = ids.shape
        T = S + int(max_new_tokens)
        if T > cfg.max_position_embeddings:
            raise ValueError(
                f"{T} positions exceed max_position_embeddings="
                f"{cfg.max_position_embeddings}")
        weights = self.decode_weights()
        L, E = cfg.num_layers, cfg.hidden_size
        H = cfg.num_heads
        D = E // H
        scale = 1.0 / D ** 0.5

        cfg_key = (B, S, int(max_new_tokens), bool(do_sample),
                   int(top_k or 0), float(temperature))
        cached = getattr(self, "_gen_jit_cache", None)
        if cached is None:
            cached = self._gen_jit_cache = {}
        run = cached.get(cfg_key)
        if run is None:
            attend = dense_cache_attend(scale)

            def sample(logits, key):
                if not do_sample:
                    return jnp.argmax(logits, -1).astype(jnp.int32)
                lg = logits / jnp.maximum(temperature, 1e-6)
                if top_k:
                    kth = jax.lax.top_k(lg, int(top_k))[0][..., -1:]
                    lg = jnp.where(lg < kth, -1e30, lg)
                return jax.random.categorical(key, lg).astype(jnp.int32)

            def run_fn(W, ids, key):
                kbufs = jnp.zeros((L, B, H, T, D), W["wte"].dtype)
                vbufs = jnp.zeros_like(kbufs)
                h, ks, vs = gpt_prefill(W, ids, num_heads=H, scale=scale)
                kbufs = kbufs.at[:, :, :, :S].set(ks)
                vbufs = vbufs.at[:, :, :, :S].set(vs)
                logits = gpt_logits(W, h[:, -1])

                def dec(carry, _):
                    lg, pos, kb, vb, key = carry
                    key, sub = jax.random.split(key)
                    tok = sample(lg, sub)
                    lg2, (kb, vb) = gpt_decode_step(
                        W, tok, pos, (kb, vb), dense_cache_write, attend,
                        num_heads=H, scale=scale)
                    return (lg2, pos + 1, kb, vb, key), tok
                _, toks = jax.lax.scan(
                    dec, (logits, jnp.asarray(S, jnp.int32), kbufs,
                          vbufs, key), None,
                    length=int(max_new_tokens))
                return jnp.concatenate([ids, toks.T], axis=1)

            run = cached[cfg_key] = jax.jit(run_fn)

        out = run(weights, ids, jax.random.PRNGKey(int(seed)))
        return Tensor(out)
