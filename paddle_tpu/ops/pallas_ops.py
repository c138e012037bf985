"""Pallas TPU kernels for hot ops.

The reference ships hand-written CUDA for its hot paths
(`paddle/fluid/operators/fused/`, `math/`). The TPU equivalents are Pallas
kernels; everything else rides XLA fusion. Flagship kernel: flash attention
(online-softmax tiling, VMEM-resident K/V, in-kernel dropout via the TPU
PRNG), used by `F.scaled_dot_product_attention` / MultiHeadAttention.

Design (not from the reference — it has no fused attention):
  * forward: grid (batch*heads, q_blocks); K/V for the head stay in VMEM;
    inner fori_loop streams K blocks with the (m, l, acc) online-softmax
    recurrence; emits O and the per-row logsumexp (LSE).
  * backward: two Pallas kernels (dQ over q-blocks, dK/dV over k-blocks)
    that RECOMPUTE the probability tiles from (q, k, lse) block by block —
    no S×S matrix is ever materialized, so memory stays O(S·D) end to end.
  * masking: an additive key-padding bias [B, S] (the BERT/ERNIE padded
    -batch shape) plus an optional static causal mask.
  * dropout: per-(batch*head, q_block, k_block) reseeded TPU PRNG so the
    backward kernels regenerate bit-identical keep masks without storing
    them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["flash_attention", "flash_attention_raw", "STATS"]

_BLOCK_MIN = 128        # alignment the kernels require of S_q / S_kv
_NEG_INF = -1e30
# The kernels keep one head's whole K and V (forward, dQ) or Q and dO
# (dK/dV) resident in VMEM, double-buffered, inside Mosaic's 16 MiB scoped
# limit. What the v5e compiled (chip runs, PR 21; flash and splash, forward
# and backward): a 2 MiB resident operand at head dim 128 — f32 seq 4096,
# bf16 seq 8192 — and everything smaller tried. f32 at 4 MiB, and head dim
# 512 already at 2 MiB, ran out of VMEM; head dim 256 was only half tried.
# `vmem_resident_ok` admits what was shown and nothing else: widen it with
# a chip run. A row narrower than 128 lanes is charged a full lane tile.
_VMEM_RESIDENT_MAX = 2 * 1024 * 1024
_LANES = 128


def vmem_resident_ok(seq, head_dim, itemsize):
    return (head_dim <= _LANES
            and seq * _LANES * itemsize <= _VMEM_RESIDENT_MAX)


def _block_pref(flag_name):
    """Preferred tile size from a FLAGS_flash_block_* flag. The defaults
    (512/512) are from an on-chip sweep on an earlier machine (v5e,
    S=2048, bf16; the sweep script is gone — a profiler trace read with
    tools/trace_report.py shows `flash_fwd` / `flash_bwd_dq` /
    `flash_bwd_dkv` by name). The splash path rides the same flags."""
    from ..framework.flags import flag
    # lint: allow(flag-in-trace): this IS the sanctioned snapshot point — flash/splash_attention_raw reads the tile flags once per outer trace and threads them through the custom_vjp as static args, so fwd and bwd can never desync (the PR 6 contract)
    pref = int(flag(flag_name))
    if pref < _BLOCK_MIN or pref % _BLOCK_MIN != 0:
        raise ValueError(
            f"{flag_name}={pref}: attention tile sizes must be positive "
            f"multiples of {_BLOCK_MIN}")
    return pref


def _pick_blocks(Sq, Sk):
    """Largest preferred tile that divides the sequence lengths, capped
    by the FLAGS_flash_block_q / FLAGS_flash_block_kv preferences."""
    for s in (Sq, Sk):
        if s % _BLOCK_MIN != 0:
            raise ValueError(
                f"flash: sequence length {s} must be a multiple of "
                f"{_BLOCK_MIN} (pad the sequence or route through dense "
                f"attention via flash_supported)")
    prefq = _block_pref("FLAGS_flash_block_q")
    prefk = _block_pref("FLAGS_flash_block_kv")
    bq = max(b for b in sorted({128, 256, 512, prefq})
             if Sq % b == 0 and b <= Sq and b <= prefq)
    bk = max(b for b in sorted({128, 256, 512, prefk})
             if Sk % b == 0 and b <= Sk and b <= prefk)
    return bq, bk

from ..framework.monitor import stat_add as _stat_add, stat_get as _stat_get


class _KernelStats:
    """Trace-time engagement counters (prove the kernel ran in a given
    program). Backed by the framework STAT registry
    (framework/monitor.py) so there is one source of truth."""

    _keys = {"flash_fwd": "STAT_flash_attention_fwd",
             "flash_bwd": "STAT_flash_attention_bwd"}

    def __getitem__(self, k):
        return _stat_get(self._keys[k])

    def bump(self, k):
        _stat_add(self._keys[k])


STATS = _KernelStats()

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret():
    """`interpret=` for every pallas_call here and in splash_ops.

    Interpreter mode is chosen by FLAGS_flash_attention_interpret and by
    nothing else. Without the flag a kernel is handed to Mosaic, which only
    a TPU backend can compile, so on any other backend the raw kernel
    entries raise here; the public dispatch
    (F.scaled_dot_product_attention) never reaches them off-TPU — it takes
    the dense route."""
    from ..framework.flags import flag
    # lint: allow(flag-in-trace): interpret mode is lowering structure by definition — the flag selects HOW pallas_call compiles (TPU vs interpreter), re-read at every trace; there is no runtime value to thread
    if flag("FLAGS_flash_attention_interpret"):
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"Pallas attention kernel on the {jax.default_backend()!r} "
            f"backend: the kernels compile for TPU only. Set "
            f"FLAGS_flash_attention_interpret=1 to run them in the Pallas "
            f"interpreter, or call F.scaled_dot_product_attention, which "
            f"takes the dense path here")
    return False


def _sdpa_reference(q, k, v, bias, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        S, K = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((S, K), bool))
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def _dropout_bits(seed, bh, qi, kb, shape, dropout_p):
    """Regenerable keep-mask for one (bh, q_block, k_block) tile.

    Mosaic allows at most two seed values, so the tile coordinates are
    packed into one int32 (wraps for astronomically large grids, but stays
    deterministic and identical across the fwd/dq/dkv kernels, which is
    the property the backward replay needs)."""
    tile = (bh * 1048576 + qi * 1024 + kb).astype(jnp.int32) \
        if hasattr(bh, "astype") else jnp.int32(bh * 1048576 + qi * 1024 + kb)
    pltpu.prng_seed(seed, tile)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    thresh = np.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return bits >= thresh


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, *,
                scale, causal, block_k, dropout_p):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    # MXU dots run on the INPUT dtype (bf16 in production — 4x the f32
    # path on v5e) with f32 accumulation; softmax stats stay f32
    q = q_ref[:]
    S, D = k_ref.shape
    bq = q_ref.shape[0]
    nkb = S // block_k
    q_offs = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    seed = seed_ref[0, 0]

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale
        s += b_ref[0, pl.ds(kb * block_k, block_k)][None, :]  # b_ref [1,S]
        if causal:
            k_offs = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(q_offs >= k_offs, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
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
    if causal:
        last = jnp.minimum(nkb, ((qi + 1) * bq + block_k - 1) // block_k)
        m, l, acc = jax.lax.fori_loop(0, last, body, (m0, l0, acc0))
    else:
        m, l, acc = jax.lax.fori_loop(0, nkb, body, (m0, l0, acc0))
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l)


# ---------------------------------------------------------------------------
# backward: dQ kernel (grid over q blocks) and dK/dV kernel (over k blocks)
# ---------------------------------------------------------------------------

def _recompute_p(q, k_blk, bias_row, q_offs, k_offs, lse, scale, causal):
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale
    s += bias_row
    if causal:
        s = jnp.where(q_offs >= k_offs, s, _NEG_INF)
    return jnp.exp(s - lse)


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
               dl_ref, dq_ref, *, scale, causal, block_k, dropout_p):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:]          # [bq, 1]
    delta = dl_ref[:]         # [bq, 1]
    S, D = k_ref.shape
    bq = q_ref.shape[0]
    nkb = S // block_k
    q_offs = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    seed = seed_ref[0, 0]
    inv_keep = 1.0 / (1.0 - dropout_p) if dropout_p > 0.0 else 1.0

    def body(kb, dq):
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        k_offs = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        bias_row = b_ref[0, pl.ds(kb * block_k, block_k)][None, :]
        p = _recompute_p(q, k_blk, bias_row, q_offs, k_offs, lse, scale,
                         causal)
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
    if causal:
        last = jnp.minimum(nkb, ((qi + 1) * bq + block_k - 1) // block_k)
        dq = jax.lax.fori_loop(0, last, body, dq0)
    else:
        dq = jax.lax.fori_loop(0, nkb, body, dq0)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
                dl_ref, dk_ref, dv_ref, *, scale, causal, block_q,
                dropout_p):
    bh = pl.program_id(0)
    kb = pl.program_id(1)
    k_blk = k_ref[:]                        # [bk, D]
    v_blk = v_ref[:]
    S, D = q_ref.shape
    bk = k_ref.shape[0]
    nqb = S // block_q
    k_offs = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    bias_row = b_ref[:]                     # [1, bk] (k-block slice)
    seed = seed_ref[0, 0]
    inv_keep = 1.0 / (1.0 - dropout_p) if dropout_p > 0.0 else 1.0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[pl.ds(qi * block_q, block_q), :]
        do = do_ref[pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[pl.ds(qi * block_q, block_q), :]
        delta = dl_ref[pl.ds(qi * block_q, block_q), :]
        q_offs = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        p = _recompute_p(q, k_blk, bias_row, q_offs, k_offs, lse, scale,
                         causal)
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
    if causal:
        first = (kb * bk) // block_q
        dk, dv = jax.lax.fori_loop(first, nqb, body, (dk0, dv0))
    else:
        dk, dv = jax.lax.fori_loop(0, nqb, body, (dk0, dv0))
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers
# ---------------------------------------------------------------------------

def _smem_scalar_spec():
    return pl.BlockSpec((1, 1), lambda *_: (0, 0), memory_space=pltpu.SMEM)


def _flash_call(q, k, v, bias, seed, causal, scale, dropout_p,
                block_q, block_k):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    bias3 = bias.reshape(B, 1, Sk)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, dropout_p=dropout_p)
    STATS.bump("flash_fwd")
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // block_q),
        in_specs=[
            _smem_scalar_spec(),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
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
        name="flash_fwd",
    )(seed_arr, qr, kr, vr, bias3)
    return out.reshape(B, H, Sq, D), lse


def _flash_bwd_call(q, k, v, bias, seed, out, lse, g, causal, scale,
                    dropout_p, block_q, block_k):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    gr = g.reshape(B * H, Sq, D)
    bias3 = bias.reshape(B, 1, Sk)
    # delta = rowsum(dO ∘ O) — tiny elementwise+reduce, XLA fuses it
    delta = jnp.sum(gr.astype(jnp.float32)
                    * out.reshape(B * H, Sq, D).astype(jnp.float32),
                    axis=-1, keepdims=True)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    STATS.bump("flash_bwd")

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, dropout_p=dropout_p),
        grid=(B * H, Sq // block_q),
        in_specs=[
            _smem_scalar_spec(),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, 1, Sk), lambda b, i: (b // H, 0, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(seed_arr, qr, kr, vr, bias3, gr, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, dropout_p=dropout_p),
        grid=(B * H, Sk // block_k),
        in_specs=[
            _smem_scalar_spec(),
            pl.BlockSpec((None, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
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
        name="flash_bwd_dkv",
    )(seed_arr, qr, kr, vr, bias3, gr, lse, delta)
    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_raw_blocked(q, k, v, bias, seed, causal, scale, dropout_p,
                       block_q, block_k):
    out, _ = _flash_fwd_rule(q, k, v, bias, seed, causal, scale,
                             dropout_p, block_q, block_k)
    return out


def _flash_fwd_rule(q, k, v, bias, seed, causal, scale, dropout_p,
                    block_q, block_k):
    out, lse = _flash_call(q, k, v, bias, seed, causal, scale, dropout_p,
                           block_q, block_k)
    return out, (q, k, v, bias, seed, out, lse)


def _flash_bwd_rule(causal, scale, dropout_p, block_q, block_k, res, g):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv = _flash_bwd_call(q, k, v, bias, seed, out, lse, g, causal,
                                 scale, dropout_p, block_q, block_k)
    dbias = jnp.zeros(bias.shape, jax.dtypes.float0) \
        if not jnp.issubdtype(bias.dtype, jnp.floating) \
        else jnp.zeros_like(bias)
    dseed = np.zeros((), jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


_flash_raw_blocked.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_raw(q, k, v, bias, seed, causal, scale, dropout_p):
    """Flash attention with O(S·D) memory in fwd AND bwd.

    q/k/v: [B, H, S, D]; bias: additive key-padding mask [B, S] (zeros
    for no mask); seed: int32 scalar driving in-kernel dropout; causal/
    scale/dropout_p are static. bias and seed are non-differentiable.

    Tile sizes are snapshotted HERE and threaded through the custom_vjp
    as static args: the in-kernel dropout keep mask is reseeded per
    (bh, q_block, k_block) tile, so a FLAGS_flash_block_* change
    between an eager forward and its later backward must not let the
    two passes pick different tiles (the replayed masks would silently
    diverge and corrupt gradients).
    """
    bq, bk = _pick_blocks(q.shape[2], k.shape[2])
    return _flash_raw_blocked(q, k, v, bias, seed, causal, scale,
                              dropout_p, bq, bk)


def flash_supported(q_shape, k_shape=None, v_shape=None, mask=None,
                    is_causal=False, min_seq=None, itemsize=4):
    """Static gate: shapes the kernels handle AND where they win. Every
    shape it admits must compile on the chip.

    Below `min_seq` queries (default: FLAGS_flash_attention_min_seq, 512)
    XLA's fused dense attention beats the Pallas kernel on v5e — dense won
    the round-2/3 bench at seq 128 by ~25% — so short sequences are
    refused here and ride the jnp fallback. `itemsize` (bytes per element
    of q/k/v; 4 when unknown) bounds the sequence the kernels can keep
    resident in VMEM (`vmem_resident_ok`: head dim <= 128, seq <= 4096 in
    f32 and 8192 in bf16); the rest ride the fallback too.
    """
    if len(q_shape) != 4:
        return False
    B, H, Sq, D = q_shape
    k_shape = tuple(k_shape) if k_shape is not None else tuple(q_shape)
    v_shape = tuple(v_shape) if v_shape is not None else k_shape
    if len(k_shape) != 4 or k_shape != v_shape:
        return False
    Bk, Hk, Sk, Dk = k_shape
    if (Bk, Hk, Dk) != (B, H, D):
        return False
    if is_causal and Sk != Sq:  # causal ranges assume aligned diagonals
        return False
    if Sq % _BLOCK_MIN != 0 or Sk % _BLOCK_MIN != 0 or D % 8 != 0:
        return False
    if not vmem_resident_ok(max(Sq, Sk), D, itemsize):
        return False
    if min_seq is None:
        from ..framework.flags import flag
        min_seq = flag("FLAGS_flash_attention_min_seq")
    if Sq < min_seq:
        return False
    if mask is not None:
        ms = getattr(mask, "shape", None)
        if ms is None or len(ms) != 4 or ms[1] != 1 or ms[2] != 1 \
                or ms[0] != B or ms[3] != Sk:
            return False
    return True


def flash_attention(query, key, value, causal=False, scale=None,
                    attn_mask=None, dropout_p=0.0):
    """Framework-level entry: Tensor in/out, tape-recorded.

    attn_mask: None, or a [B, 1, 1, S_kv] additive (float) / boolean
    key-padding mask — the padded-batch BERT/ERNIE shape.
    """
    from ..framework.tensor import apply_op, Tensor
    if scale is None:
        scale = 1.0 / (query.shape[-1] ** 0.5)
    B, S = key.shape[0], key.shape[2]
    if attn_mask is None:
        bias = jnp.zeros((B, S), jnp.float32)
    else:
        mv = attn_mask._value if isinstance(attn_mask, Tensor) else attn_mask
        mv = mv.reshape(B, S)
        bias = jnp.where(mv, 0.0, _NEG_INF) if mv.dtype == jnp.bool_ \
            else mv.astype(jnp.float32)
    if dropout_p > 0.0:
        from ..framework import random as frandom
        key_ = frandom.get_rng_key()
        seed = jax.random.randint(key_, (), 0, np.int32(2 ** 31 - 1),
                                  dtype=jnp.int32)
    else:
        seed = jnp.zeros((), jnp.int32)
    return apply_op(
        "flash_attention",
        lambda q, k, v: flash_attention_raw(q, k, v, bias, seed, causal,
                                            scale, dropout_p),
        (query, key, value), {})
