"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference (~v2.0) has NO long-context support (SURVEY §5) — this is a
new first-class subsystem, TPU-native by design:

* ring_attention: shard the sequence over the 'sp' mesh axis; each step
  computes a blockwise (online-softmax) attention against the resident
  K/V shard, then rotates K/V one hop around the ICI ring with
  lax.ppermute. Peak memory O(S/sp); comm fully overlapped by XLA's
  latency-hiding scheduler. Causal masking uses block-index arithmetic.
* ulysses_attention: all-to-all re-shard — [B, S/sp, H, D] ⇄
  [B, S, H/sp, D] — so full-sequence attention runs locally per head
  group; two lax.all_to_all ops ride ICI.

Both are pure jnp/lax functions meant to run inside shard_map over 'sp'.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "ulysses_attention", "shard_map_ring_attention"]


def _dot_precision(dtype):
    """bf16/f16 inputs take the fast single-pass MXU path; f32 inputs
    keep full precision. Must be explicit either way: the framework pins
    jax_default_matmul_precision="highest" globally
    (framework/__init__.py), which would upcast bf16 dots, while a bare
    DEFAULT would silently degrade f32 accuracy on TPU."""
    if dtype in (jnp.bfloat16, jnp.float16):
        return jax.lax.Precision.DEFAULT
    return jax.lax.Precision.HIGHEST


def _block_attend(q, k, v, scale, mask_val=None):
    """Partial (un-normalized) attention stats for one K/V block.
    q: [B,H,Sq,D]; k,v: [B,H,Sk,D] → (max, sumexp, acc).

    MXU dots run on the INPUT dtype (bf16 in production — 4x the f32
    path on v5e, same recipe as the Pallas flash kernel); the softmax
    statistics and accumulator stay f32."""
    prec = _dot_precision(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32,
                   precision=prec) * scale
    if mask_val is not None:
        s = jnp.where(mask_val, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32,
                     precision=prec)
    return m, l, acc


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None):
    """Blockwise ring attention. q,k,v: LOCAL shards [B, H, S_loc, D];
    the global sequence is sp * S_loc, laid out contiguously by rank."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    sp = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, H, S, D = q.shape

    q_pos = my * S + jnp.arange(S)  # global positions of my queries

    def mask_for(kv_rank):
        if not causal:
            return None
        k_pos = kv_rank * S + jnp.arange(S)
        return q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def body(i, carry):
        m, l, acc, k_cur, v_cur = carry
        kv_rank = (my - i) % sp
        msk = mask_for(kv_rank)
        if msk is not None:
            msk = msk[None, None]
        bm, bl, bacc = _block_attend(q, k_cur, v_cur, scale, msk)
        m_new = jnp.maximum(m, bm)
        scale_old = jnp.exp(m - m_new)
        scale_blk = jnp.exp(bm - m_new)
        l_new = l * scale_old + bl * scale_blk
        acc_new = acc * scale_old + bacc * scale_blk
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return m_new, l_new, acc_new, k_nxt, v_nxt

    # derive carries from q so they inherit the 'sp' varying manual axis;
    # stats/accumulator are f32, K/V rotate in their native (bf16) dtype
    qf = q.astype(jnp.float32)
    m0 = jnp.full_like(qf[..., :1], -1e30)
    l0 = jnp.zeros_like(qf[..., :1])
    acc0 = jnp.zeros_like(qf)
    m, l, acc, _, _ = lax.fori_loop(
        0, sp, body, (m0, l0, acc0, k, v))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                      scale: Optional[float] = None):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.
    Inputs: LOCAL shards [B, H, S_loc, D] with H % sp == 0. Re-shards to
    [B, H/sp, S_global, D], attends locally, re-shards back."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    sp = lax.axis_size(axis_name)

    def to_seq(x):
        # [B,H,S,D] -> split heads, gather sequence
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qs, ks, vs = to_seq(q), to_seq(k), to_seq(v)
    prec = _dot_precision(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", qs, ks,
                   preferred_element_type=jnp.float32,
                   precision=prec) * scale
    if causal:
        S = s.shape[-1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vs.dtype), vs,
                     preferred_element_type=jnp.float32,
                     precision=prec)
    # cast BEFORE the all_to_all so the ICI transfer rides bf16
    return to_heads(out.astype(q.dtype))


def shard_map_ring_attention(q, k, v, mesh, causal=False, impl="ring"):
    """Convenience: run (ring|ulysses) attention over global arrays
    [B, H, S, D] sequence-sharded on 'sp'."""
    from jax.sharding import PartitionSpec as P

    attn = ring_attention if impl == "ring" else ulysses_attention
    fn = jax.shard_map(
        functools.partial(attn, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False)
    return fn(q, k, v)
