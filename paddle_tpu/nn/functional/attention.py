"""Attention functionals.

The reference (~v2.0) has no fused attention op — MultiHeadAttention is
composed in Python (`python/paddle/nn/layer/transformer.py:87`). Here
scaled-dot-product attention is a first-class functional with a Pallas
flash-attention fast path on TPU (paddle_tpu/ops/pallas_ops.py), a
segment-aware splash fast path for PACKED sequences
(paddle_tpu/ops/splash_ops.py, `segment_ids=`), and a pure jnp fallback
that XLA fuses well on any backend.

Dispatch order for a call with `segment_ids`: splash kernel when the
shape gate passes (seq length >= FLAGS_splash_attention_min_seq, aligned)
on a TPU backend or under FLAGS_flash_attention_interpret, else the dense
fallback with the SAME segment-within-causal mask — so packed batches are
always correct and only the FLOPs story changes. Without segment_ids the
existing flash-vs-dense gate is unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.flags import flag
from ...framework.monitor import STAT_ADD
from ...framework.tensor import apply_op

__all__ = ["scaled_dot_product_attention"]


def _sdpa_ref(q, k, v, mask, scale, is_causal, dropout_p=0.0, rng=None,
              seg=None):
    # q,k,v: [B, H, S, D]; seg: (q_seg [B,S], kv_seg [B,K]) packed-batch
    # segment ids — cross-segment pairs are masked like the splash kernel.
    # KEEP the segment semantics IN SYNC with
    # ops/splash_ops.sdpa_segment_reference (the kernel parity oracle):
    # same equality mask, causal AND, fully-masked rows output zero
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    allowed = None
    if is_causal:
        S, K = s.shape[-2], s.shape[-1]
        # bottom-right aligned: query i sits at absolute position K-S+i, so
        # the KV-cache decode shape (S < K) attends to the whole prefix
        qpos = jnp.arange(S)[:, None] + (K - S)
        allowed = (qpos >= jnp.arange(K)[None, :])[None, None]
    if seg is not None:
        q_seg, kv_seg = seg
        same = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
        allowed = same if allowed is None else jnp.logical_and(allowed,
                                                               same)
    if allowed is not None:
        s = jnp.where(allowed, s, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            s = jnp.where(mask, s, -1e30)
        else:
            s = s + mask
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0:
        # dropout on the softmax probabilities (upscale-in-train), matching
        # the Pallas kernel's in-kernel semantics — NOT on the output
        keep = jax.random.bernoulli(rng, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0).astype(p.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    if seg is not None:
        # fully-masked rows emit zeros (splash kernel semantics), not the
        # uniform mix a -1e30 softmax degenerates to
        out = jnp.where(jnp.any(allowed, axis=-1)[..., None], out,
                        jnp.zeros((), out.dtype))
    return out


def _kernel_runs(eff_dropout):
    """Whether a Pallas attention kernel can run for this call: compiled
    on a TPU backend, or interpreted under FLAGS_flash_attention_interpret
    (the interpreter has no TPU PRNG lowering, so no dropout there).
    Everything else is the dense route — by this rule, not by catching a
    failed kernel."""
    if flag("FLAGS_flash_attention_interpret"):
        return eff_dropout == 0.0
    return jax.default_backend() == "tpu"


def _norm_segment_ids(segment_ids):
    """segment_ids: [B, S] array/Tensor shared by q and kv, or a
    (q_seg, kv_seg) pair. Returns raw [B, S] arrays."""
    from ...framework.tensor import Tensor
    if isinstance(segment_ids, (tuple, list)):
        qs, ks = segment_ids
    else:
        qs = ks = segment_ids
    unwrap = lambda x: x._value if isinstance(x, Tensor) \
        else jnp.asarray(x)  # noqa: E731
    return unwrap(qs), unwrap(ks)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None,
                                 segment_ids=None):
    """query/key/value: [batch, num_heads, seq, head_dim] (BHSD).

    segment_ids sits AFTER name so the reference-compatible positional
    contract (..., training, name) is preserved for existing callers.

    segment_ids: packed-sequence segment ids — a [batch, seq] int array
    (shared q/kv) or a (q_seg, kv_seg) pair, non-decreasing along each
    row (io.packing layout). Tokens attend only within their own
    segment (AND causally when is_causal). Mutually exclusive with
    attn_mask; routes to the splash kernel where supported, else to the
    dense segment-masked fallback.
    """
    d = query.shape[-1]
    scale = 1.0 / (d ** 0.5)
    eff_dropout = dropout_p if training else 0.0
    itemsize = jnp.dtype(getattr(query, "_value", query).dtype).itemsize

    if segment_ids is not None:
        if attn_mask is not None:
            raise ValueError(
                "scaled_dot_product_attention: attn_mask and segment_ids "
                "are mutually exclusive — packed padding is expressed as "
                "a trailing pad segment, not a key-padding mask")
        q_seg, kv_seg = _norm_segment_ids(segment_ids)
        use_splash = False
        if flag("FLAGS_use_splash_attention"):
            from ...ops.splash_ops import splash_supported
            use_splash = splash_supported(
                tuple(query.shape), tuple(key.shape), tuple(value.shape),
                is_causal=is_causal, itemsize=itemsize) \
                and _kernel_runs(eff_dropout)
        if use_splash:
            from ...framework.tensor import Tensor as _T
            qv = query._value if isinstance(query, _T) else query
            if isinstance(qv, jax.core.Tracer):
                # dispatching from inside a jit trace while a
                # multi-device mesh is live: that trace is (or may be)
                # GSPMD-partitioned, and GSPMD cannot partition a
                # pallas_call — the kernel would gather the GLOBAL
                # batch onto every chip, silently negating dp sharding.
                # The dense fallback partitions fine; meshes that want
                # the kernel use parallel.spmd.sharded_splash_attention
                # (shard_map) explicitly. Concrete (eager) inputs are
                # never pjit-partitioned, mesh or no mesh.
                from ...parallel.mesh import get_mesh
                mesh = get_mesh()
                if mesh is not None and mesh.devices.size > 1:
                    use_splash = False
        if use_splash:
            from ...ops.splash_ops import splash_attention
            STAT_ADD("STAT_splash_dispatches")
            return splash_attention(query, key, value, q_seg, kv_seg,
                                    causal=is_causal, scale=scale,
                                    dropout_p=eff_dropout)
        rng = None
        if eff_dropout > 0.0:
            from ...framework.random import get_rng_key
            rng = get_rng_key()

        def seg_impl(q, k, v):
            return _sdpa_ref(q, k, v, None, scale, is_causal, eff_dropout,
                             rng, seg=(q_seg, kv_seg))
        return apply_op("sdpa_segment", seg_impl, (query, key, value), {})

    use_flash = False
    if flag("FLAGS_use_flash_attention"):
        from ...ops.pallas_ops import flash_supported
        use_flash = flash_supported(
            tuple(query.shape), tuple(key.shape), tuple(value.shape),
            attn_mask, is_causal=is_causal, itemsize=itemsize) \
            and _kernel_runs(eff_dropout)

    if use_flash:
        from ...ops.pallas_ops import flash_attention
        return flash_attention(
            query, key, value, causal=is_causal, scale=scale,
            attn_mask=attn_mask, dropout_p=eff_dropout)

    if eff_dropout > 0.0:
        from ...framework.random import get_rng_key
        rng = get_rng_key()
    else:
        rng = None

    def impl(q, k, v, *m):
        mask = m[0] if m else None
        return _sdpa_ref(q, k, v, mask, scale, is_causal, eff_dropout, rng)
    args = (query, key, value) + ((attn_mask,) if attn_mask is not None else ())
    return apply_op("sdpa", impl, args, {})
