"""Pallas TPU splash attention: segment-aware flash attention for packed
sequences.

Sequence packing (io/packing.py) concatenates short sequences into one
fixed-shape row; attention must then be masked PER SEGMENT so packed
neighbours never attend to each other. This module is the kernel layer of
that pipeline — the flash kernels of pallas_ops.py extended with
segment-id-driven masking plus the property that gives splash attention
its name: kv blocks entirely outside a q block's segment span are
SKIPPED, not just masked, so attention FLOPs track real tokens instead of
the padded row shape (in the spirit of
`jax.experimental.pallas.ops.tpu.splash_attention`'s `SegmentIds` —
SNIPPETS.md [1][2] — but sharing pallas_ops' layout, stats and
interpret-mode test story).

Design:
  * masking: attend iff q_seg == kv_seg, AND q_pos >= k_pos when causal
    ("causal within segment" — positions are global row offsets, so the
    plain causal predicate composes with the segment predicate).
  * block skipping: segment ids are CONTRACTUALLY non-decreasing along
    each row (the packing layout). The host wrapper then computes, per
    (batch, q block), the kv-index span [searchsorted(kv_seg, first_q_seg,
    left), searchsorted(kv_seg, last_q_seg, right)) with jnp reductions,
    rounds it to kv blocks, and ships the bounds into SMEM; the kernel's
    fori_loop runs only those blocks (the backward dkv kernel gets the
    transposed bounds over q blocks). Non-monotonic ids would make the
    skip DROP attention silently — the dispatch layer only builds ids via
    the packing collator, and splash_attention validates concrete inputs.
  * degenerate rows: a row whose segment has no visible key anywhere
    (cannot happen in the packing layout — causal keeps the diagonal and
    a token is its own key) outputs ZEROS, and the dense reference below
    mirrors that, unlike a -1e30 softmax which would emit a uniform mix.
  * forward/backward structure, dropout replay, f32 softmax stats, and
    the O(S·D) recompute backward are pallas_ops' — see its docstring.

Tile sizes ride the same FLAGS_flash_block_q / FLAGS_flash_block_kv knobs
as the flash kernel (the prior 512/512 flash result is the default; not
swept for this path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import (_BLOCK_MIN, _NEG_INF, _KernelStats, _dropout_bits,
                         _interpret, _pick_blocks, _smem_scalar_spec,
                         vmem_resident_ok)

__all__ = ["splash_attention", "splash_attention_raw", "splash_supported",
           "sdpa_segment_reference", "STATS"]


class _SplashStats(_KernelStats):
    _keys = {"splash_fwd": "STAT_splash_attention_fwd",
             "splash_bwd": "STAT_splash_attention_bwd"}


STATS = _SplashStats()


def sdpa_segment_reference(q, k, v, q_seg, kv_seg, causal, scale):
    """Dense reference with the kernel's exact segment semantics — the
    _sdpa_reference extension the interpret-mode parity tests check the
    kernels against. q/k/v: [B,H,S,D]; q_seg/kv_seg: [B,S] int.

    KEEP IN SYNC with the production dense fallback
    (nn/functional/attention.py `_sdpa_ref` with `seg=`): same
    segment-equality mask, same causal AND, same zero-output rule for
    fully-masked rows. This f32 copy exists so kernel parity tests
    don't depend on the functional layer's dtype/dropout plumbing."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    allowed = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        allowed = jnp.logical_and(
            allowed, jnp.tril(jnp.ones((Sq, Sk), bool))[None, None])
    s = jnp.where(allowed, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    # fully-masked rows emit zeros (kernel semantics), not a uniform mix
    out = jnp.where(jnp.any(allowed, axis=-1)[..., None], out, 0.0)
    return out.astype(q.dtype)


def _block_bounds(q_seg, kv_seg, block_q, block_k, causal):
    """Per-block loop bounds that realize the splash skip.

    Returns int32 arrays
      kv_lo, kv_hi [B, n_q_blocks] — kv-block range each q block visits
      q_lo,  q_hi  [B, n_kv_blocks] — q-block range each kv block visits
    computed from the non-decreasing segment ids: a q block spanning
    segments [s_first, s_last] can only see kv indices inside
    [first kv of s_first, last kv of s_last] — everything outside is
    masked by construction, so it is never loaded. Causal additionally
    caps at the diagonal exactly like the flash kernels."""
    B, Sq = q_seg.shape
    Sk = kv_seg.shape[1]
    nqb, nkb = Sq // block_q, Sk // block_k
    ss_l = jax.vmap(functools.partial(jnp.searchsorted, side="left"))
    ss_r = jax.vmap(functools.partial(jnp.searchsorted, side="right"))

    kv_lo = ss_l(kv_seg, q_seg[:, ::block_q]) // block_k
    kv_hi = -(-ss_r(kv_seg, q_seg[:, block_q - 1::block_q]) // block_k)
    if causal:
        cap = (jnp.arange(1, nqb + 1) * block_q
               + block_k - 1) // block_k          # flash's causal bound
        kv_hi = jnp.minimum(kv_hi, cap[None, :])
    kv_hi = jnp.maximum(kv_hi, kv_lo)             # empty span, not negative

    q_lo = ss_l(q_seg, kv_seg[:, ::block_k]) // block_q
    if causal:
        floor = (jnp.arange(nkb) * block_k) // block_q
        q_lo = jnp.maximum(q_lo, floor[None, :])
    q_hi = -(-ss_r(q_seg, kv_seg[:, block_k - 1::block_k]) // block_q)
    q_hi = jnp.maximum(q_hi, q_lo)
    return (kv_lo.astype(jnp.int32), kv_hi.astype(jnp.int32),
            q_lo.astype(jnp.int32), q_hi.astype(jnp.int32))


def _seg_mask(qseg, kseg, q_offs, k_offs, causal):
    allowed = qseg == kseg
    if causal:
        allowed = jnp.logical_and(allowed, q_offs >= k_offs)
    return allowed


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref, qs_ref,
                ks_ref, o_ref, lse_ref, *, scale, causal, block_k,
                dropout_p, heads):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    lo, hi = lo_ref[bh // heads, qi], hi_ref[bh // heads, qi]
    q = q_ref[:]
    S, D = k_ref.shape
    bq = q_ref.shape[0]
    qseg = qs_ref[:]                      # [bq, 1] int32
    q_offs = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    seed = seed_ref[0, 0]

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        kseg = ks_ref[0, pl.ds(kb * block_k, block_k)][None, :]   # [1, bk]
        k_offs = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        allowed = _seg_mask(qseg, kseg, q_offs, k_offs, causal)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.DEFAULT) * scale
        s = jnp.where(allowed, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # where, not exp alone: an all-masked row keeps p = 0 (l stays 0
        # -> zero output) instead of exp(-1e30 - -1e30) = 1 garbage
        p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        if dropout_p > 0.0:
            keep = _dropout_bits(seed, bh, qi, kb, p.shape, dropout_p)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        acc_new = alpha * acc + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[:] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l_safe)


# ---------------------------------------------------------------------------
# backward: dQ over q blocks, dK/dV over kv blocks (probability recompute)
# ---------------------------------------------------------------------------

def _recompute_p(q, k_blk, allowed, lse, scale):
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.DEFAULT) * scale
    # masked entries are zeroed OUTSIDE the exp so a degenerate row's
    # lse (= -1e30) cannot resurrect them as exp(0) = 1
    return jnp.where(allowed, jnp.exp(s - lse), 0.0)


def _dq_kernel(seed_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref, qs_ref,
               ks_ref, do_ref, lse_ref, dl_ref, dq_ref, *, scale, causal,
               block_k, dropout_p, heads):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    lo, hi = lo_ref[bh // heads, qi], hi_ref[bh // heads, qi]
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:]
    delta = dl_ref[:]
    S, D = k_ref.shape
    bq = q_ref.shape[0]
    qseg = qs_ref[:]
    q_offs = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    seed = seed_ref[0, 0]
    inv_keep = 1.0 / (1.0 - dropout_p) if dropout_p > 0.0 else 1.0

    def body(kb, dq):
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        kseg = ks_ref[0, pl.ds(kb * block_k, block_k)][None, :]
        k_offs = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        allowed = _seg_mask(qseg, kseg, q_offs, k_offs, causal)
        p = _recompute_p(q, k_blk, allowed, lse, scale)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=jax.lax.Precision.DEFAULT)
        if dropout_p > 0.0:
            keep = _dropout_bits(seed, bh, qi, kb, p.shape, dropout_p)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)

    dq0 = jnp.zeros((bq, D), jnp.float32)
    dq = jax.lax.fori_loop(lo, hi, body, dq0)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref, qs_ref,
                ks_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref, *, scale,
                causal, block_q, dropout_p, heads):
    bh = pl.program_id(0)
    kb = pl.program_id(1)
    lo, hi = lo_ref[bh // heads, kb], hi_ref[bh // heads, kb]
    k_blk = k_ref[:]                        # [bk, D]
    v_blk = v_ref[:]
    S, D = q_ref.shape
    bk = k_ref.shape[0]
    kseg = ks_ref[:]                        # [1, bk] (kv-block slice)
    k_offs = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    seed = seed_ref[0, 0]
    inv_keep = 1.0 / (1.0 - dropout_p) if dropout_p > 0.0 else 1.0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[pl.ds(qi * block_q, block_q), :]
        do = do_ref[pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[pl.ds(qi * block_q, block_q), :]
        delta = dl_ref[pl.ds(qi * block_q, block_q), :]
        qseg = qs_ref[pl.ds(qi * block_q, block_q), :]
        q_offs = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        allowed = _seg_mask(qseg, kseg, q_offs, k_offs, causal)
        p = _recompute_p(q, k_blk, allowed, lse, scale)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=jax.lax.Precision.DEFAULT)
        if dropout_p > 0.0:
            keep = _dropout_bits(seed, bh, qi, kb, p.shape, dropout_p)
            pd = jnp.where(keep, p * inv_keep, 0.0)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        else:
            pd = p
        ds = p * (dp - delta)
        dv = dv + jax.lax.dot_general(pd.astype(do.dtype), do,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=jax.lax.Precision.DEFAULT)
        dk = dk + jax.lax.dot_general(ds.astype(q.dtype), q,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=jax.lax.Precision.DEFAULT)
        return dk, dv

    dk0 = jnp.zeros((bk, D), jnp.float32)
    dv0 = jnp.zeros((bk, D), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, hi, body, (dk0, dv0))
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers
# ---------------------------------------------------------------------------

def _smem_bounds_spec():
    """The whole [batch, n_blocks] int32 bounds array in SMEM; the kernels
    index it by (batch, block) themselves. A (1, 1) block per grid cell
    does not lower on jax 0.9.0: Mosaic wants the last two block dims
    divisible by (8, 128) or equal to the array's."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _prep(q, k, v, q_seg, kv_seg):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    qs3 = q_seg.astype(jnp.int32).reshape(B, Sq, 1)   # [bq,1] kernel slices
    ks3 = kv_seg.astype(jnp.int32).reshape(B, 1, Sk)  # [1,bk] kernel slices
    return (B, H, Sq, Sk, D), qr, kr, vr, qs3, ks3


def _splash_call(q, k, v, q_seg, kv_seg, seed, causal, scale, dropout_p,
                 block_q, block_k):
    (B, H, Sq, Sk, D), qr, kr, vr, qs3, ks3 = _prep(q, k, v, q_seg, kv_seg)
    kv_lo, kv_hi, _, _ = _block_bounds(q_seg, kv_seg, block_q, block_k,
                                       causal)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, dropout_p=dropout_p,
                               heads=H)
    STATS.bump("splash_fwd")
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // block_q),
        in_specs=[
            _smem_scalar_spec(),
            _smem_bounds_spec(),
            _smem_bounds_spec(),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b // H, i, 0)),
            pl.BlockSpec((None, 1, Sk), lambda b, i: (b // H, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sq, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="splash_fwd",
    )(seed_arr, kv_lo, kv_hi, qr, kr, vr, qs3, ks3)
    return out.reshape(B, H, Sq, D), lse


def _splash_bwd_call(q, k, v, q_seg, kv_seg, seed, out, lse, g, causal,
                     scale, dropout_p, block_q, block_k):
    (B, H, Sq, Sk, D), qr, kr, vr, qs3, ks3 = _prep(q, k, v, q_seg, kv_seg)
    kv_lo, kv_hi, q_lo, q_hi = _block_bounds(q_seg, kv_seg, block_q,
                                             block_k, causal)
    gr = g.reshape(B * H, Sq, D)
    delta = jnp.sum(gr.astype(jnp.float32)
                    * out.reshape(B * H, Sq, D).astype(jnp.float32),
                    axis=-1, keepdims=True)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    # q segment ids sliced per q block in dq, but streamed whole-row in
    # dkv — [B, Sq, 1] serves both index maps
    qs_col = qs3
    STATS.bump("splash_bwd")

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, dropout_p=dropout_p, heads=H),
        grid=(B * H, Sq // block_q),
        in_specs=[
            _smem_scalar_spec(),
            _smem_bounds_spec(),
            _smem_bounds_spec(),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b // H, i, 0)),
            pl.BlockSpec((None, 1, Sk), lambda b, i: (b // H, 0, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=_interpret(),
        name="splash_bwd_dq",
    )(seed_arr, kv_lo, kv_hi, qr, kr, vr, qs_col, ks3, gr, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, dropout_p=dropout_p, heads=H),
        grid=(B * H, Sk // block_k),
        in_specs=[
            _smem_scalar_spec(),
            _smem_bounds_spec(),
            _smem_bounds_spec(),
            pl.BlockSpec((None, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sq, 1), lambda b, i: (b // H, 0, 0)),
            pl.BlockSpec((None, 1, block_k), lambda b, i: (b // H, 0, i)),
            pl.BlockSpec((None, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sq, 1), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sq, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sk, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sk, D), q.dtype),
        ],
        interpret=_interpret(),
        name="splash_bwd_dkv",
    )(seed_arr, q_lo, q_hi, qr, kr, vr, qs_col, ks3, gr, lse, delta)
    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _splash_raw_blocked(q, k, v, q_seg, kv_seg, seed, causal, scale,
                        dropout_p, block_q, block_k):
    out, _ = _splash_fwd_rule(q, k, v, q_seg, kv_seg, seed, causal, scale,
                              dropout_p, block_q, block_k)
    return out


def _splash_fwd_rule(q, k, v, q_seg, kv_seg, seed, causal, scale,
                     dropout_p, block_q, block_k):
    out, lse = _splash_call(q, k, v, q_seg, kv_seg, seed, causal, scale,
                            dropout_p, block_q, block_k)
    return out, (q, k, v, q_seg, kv_seg, seed, out, lse)


def _splash_bwd_rule(causal, scale, dropout_p, block_q, block_k, res, g):
    q, k, v, q_seg, kv_seg, seed, out, lse = res
    dq, dk, dv = _splash_bwd_call(q, k, v, q_seg, kv_seg, seed, out, lse,
                                  g, causal, scale, dropout_p, block_q,
                                  block_k)

    def zero_seg(s):
        return jnp.zeros_like(s) \
            if jnp.issubdtype(s.dtype, jnp.floating) \
            else jnp.zeros(s.shape, jax.dtypes.float0)
    dseed = np.zeros((), jax.dtypes.float0)
    return dq, dk, dv, zero_seg(q_seg), zero_seg(kv_seg), dseed


_splash_raw_blocked.defvjp(_splash_fwd_rule, _splash_bwd_rule)


def splash_attention_raw(q, k, v, q_seg, kv_seg, seed, causal, scale,
                         dropout_p):
    """Segment-aware flash attention with block skipping.

    q/k/v: [B, H, S, D]; q_seg/kv_seg: [B, S] int segment ids,
    NON-DECREASING along each row (the packing layout — the block-skip
    bounds assume it; see module docstring). seed: int32 scalar for
    in-kernel dropout. causal/scale/dropout_p are static. Segment ids
    and seed are non-differentiable.

    Tile sizes are snapshotted here and threaded through the custom_vjp
    as static args (same reason as flash_attention_raw: the dropout
    replay keys on block indices, so the forward and a later backward
    must never read different FLAGS_flash_block_* values).
    """
    bq, bk = _pick_blocks(q.shape[2], k.shape[2])
    return _splash_raw_blocked(q, k, v, q_seg, kv_seg, seed, causal,
                               scale, dropout_p, bq, bk)


def splash_supported(q_shape, k_shape=None, v_shape=None, is_causal=False,
                     min_seq=None, itemsize=4):
    """Static gate: shapes the splash kernels handle AND where they win.

    Packing is self-attention over one fixed row shape, so the gate is
    stricter than flash_supported: S_q == S_kv. Below `min_seq`
    (FLAGS_splash_attention_min_seq) the dense segment-masked fallback
    wins, same crossover story as the flash kernel — and the same VMEM
    bound on the resident sequence (`pallas_ops.vmem_resident_ok`).
    """
    if len(q_shape) != 4:
        return False
    B, H, Sq, D = q_shape
    k_shape = tuple(k_shape) if k_shape is not None else tuple(q_shape)
    v_shape = tuple(v_shape) if v_shape is not None else k_shape
    if len(k_shape) != 4 or k_shape != v_shape:
        return False
    if k_shape != (B, H, Sq, D):      # packed rows: strict self-attention
        return False
    if Sq % _BLOCK_MIN != 0 or D % 8 != 0:
        return False
    if not vmem_resident_ok(Sq, D, itemsize):
        return False
    if min_seq is None:
        from ..framework.flags import flag
        min_seq = flag("FLAGS_splash_attention_min_seq")
    return Sq >= min_seq


def _check_monotonic(seg):
    """Host-side validation when the ids are concrete (not traced): the
    block-skip contract. Inside jit the ids are tracers and the packing
    collator is the producer, so this is a best-effort guard."""
    try:
        arr = np.asarray(seg)
    except Exception:
        return  # traced: cannot inspect values
    if arr.ndim == 2 and np.any(np.diff(arr, axis=1) < 0):
        raise ValueError(
            "splash attention requires NON-DECREASING segment ids along "
            "each row (the packing layout); got a row with a decreasing "
            "id — re-pack or route through dense attention")


def splash_attention(query, key, value, q_seg, kv_seg, causal=False,
                     scale=None, dropout_p=0.0):
    """Framework-level entry: Tensor in/out, tape-recorded.

    q_seg/kv_seg: [B, S] int segment ids (Tensor or array),
    non-decreasing per row; packed padding tokens carry their own
    trailing segment id so they only ever attend to each other.
    """
    from ..framework.tensor import apply_op, Tensor
    if scale is None:
        scale = 1.0 / (query.shape[-1] ** 0.5)
    qs = q_seg._value if isinstance(q_seg, Tensor) else jnp.asarray(q_seg)
    ks = kv_seg._value if isinstance(kv_seg, Tensor) else jnp.asarray(kv_seg)
    _check_monotonic(qs)
    _check_monotonic(ks)
    if dropout_p > 0.0:
        from ..framework import random as frandom
        key_ = frandom.get_rng_key()
        seed = jax.random.randint(key_, (), 0, np.int32(2 ** 31 - 1),
                                  dtype=jnp.int32)
    else:
        seed = jnp.zeros((), jnp.int32)
    return apply_op(
        "splash_attention",
        lambda q, k, v: splash_attention_raw(q, k, v, qs, ks, seed, causal,
                                             scale, dropout_p),
        (query, key, value), {})
