"""State-space (Mamba-2) sequence mixing: a chunked scan for prefill, a
one-token state update for decode, and the causal depthwise convolution that
feeds both.

For one sequence, H heads of width P, G groups of state size N (head `h`
reads group `h // (H // G)`), per-head scalars `A < 0` and `dt_t > 0`:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S in R^{H x P x N}
    y_t = S_t C_t                                        (the caller adds D x_t)

**Prefill** (`ssd_chunked_scan`) is the state-space-dual form over chunks
of Q positions: with `a_t = dt_t A` and `cum` its running sum inside a chunk,

    inside a chunk   Y = ((C B^T) * L) (dt x),  L[t, s] = exp(cum_t - cum_s), s <= t
    a chunk's state  Z = (exp(cum_Q - cum_s) dt_s x_s)^T B
    across chunks    S_c = exp(cum_Q) S_{c-1} + Z_c        (`lax.scan`, S / Q steps)
    from before      Y += exp(cum_t) C_t S_{c-1}

so every large product is a matmul (operands in the activations' dtype,
float32 accumulation) and nothing loops over positions; decays, running sums
and the carried state are float32. `dt_t = 0` is an exact no-op (decay 1,
input 0), which is how a bucket's padding is masked.

**Decode** (`ssm_decode_update`) moves each slot's state once each way:
`S <- decay S + (dt x) (x) B`, `y = S C`, over ONE layer of the slot pool
`[L, M, H, P, N]` in place. Two implementations, one shape-and-backend rule
(`ssm_decode_path`, the way `paged_ops.paged_latent_path` has it): on a TPU
one Pallas kernel `ssm_decode_update` over (slot, block of heads) blocks of
`[hb, P, N]` float32 with the pool aliased to its output; elsewhere, and as
the kernel's oracle, the `jax.numpy` form. Both are counted at TRACE time
(`STAT_ssm_decode_kernel` / `STAT_ssm_decode_reference`).

**The convolution** (`causal_conv_window`, `causal_conv_window_step`) is
depthwise over channels and `K` wide; what it carries between calls is the
last K PRE-activation rows (the window), so one decode step is shift, then
the same K-term sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import monitor
from ..framework.flags import flag
from .pallas_ops import _interpret

__all__ = ["causal_conv_window", "causal_conv_window_step",
           "ssd_chunked_scan", "ssm_scan_reference", "ssm_decode_path",
           "ssm_decode_update", "ssm_decode_update_reference",
           "ssm_decode_kernel_supported"]


def _group_of_heads(v, heads):
    """[..., G, N] -> [..., H, N]: head h reads group h // (H // G)."""
    G = v.shape[-2]
    return v if G == heads else jnp.repeat(v, heads // G, axis=-2)


# -- the convolution ----------------------------------------------------------


def causal_conv_window(x, w, b, length=None):
    """Depthwise causal convolution over one whole sequence, history zero.

    x [S, C] (pre-activation); w [C, K]; b [C]. out_t = b + sum_j w[:, j]
    x_{t-K+1+j}: K shifted adds, float32 accumulation, in x's dtype.
    Returns (out [S, C], window [K, C]): the window is the last K rows of
    x BEFORE position `length` (the whole sequence where None; zero rows
    where the sequence is shorter than K), what `causal_conv_window_step`
    carries on from."""
    S, K = x.shape[0], w.shape[1]
    # K zero rows in front: row i of xp is x_{i-K}
    xp = jnp.concatenate([jnp.zeros((K, x.shape[1]), x.dtype), x], 0)
    acc = b.astype(jnp.float32)[None]
    for j in range(K):
        acc = acc + (xp[j + 1:j + 1 + S].astype(jnp.float32)
                     * w[:, j].astype(jnp.float32)[None])
    # rows length-K .. length-1 of x
    window = jax.lax.dynamic_slice_in_dim(
        xp, S if length is None else length, K, axis=0)
    return acc.astype(x.dtype), window


def causal_conv_window_step(window, x, w, b):
    """One position for each of M rows: window [K, M, C] (the last K
    pre-activation rows, oldest first; K in front, so that a pool of such
    windows is dense in the device's default layout), x [M, C] the new one.
    Returns (out [M, C], the window shifted by one with x last)."""
    window = jnp.concatenate([window[1:], x[None].astype(window.dtype)], 0)
    out = (jnp.einsum("kmc,ck->mc", window.astype(jnp.float32),
                      w.astype(jnp.float32))
           + b.astype(jnp.float32)[None])
    return out.astype(x.dtype), window


# -- prefill: the chunked scan -------------------------------------------------


def ssm_scan_reference(x, dt, A, B, C, init_state=None):
    """The recurrence itself, one position at a time, float32: the oracle
    of `ssd_chunked_scan` (tests only; nothing serves through it).
    x [S, H, P]; dt [S, H]; A [H]; B, C [S, G, N]. Returns (y [S, H, P],
    state [H, P, N])."""
    S, H, P = x.shape
    f = jnp.float32
    Bh = _group_of_heads(B.astype(f), H)
    Ch = _group_of_heads(C.astype(f), H)
    s0 = (jnp.zeros((H, P, B.shape[-1]), f) if init_state is None
          else init_state.astype(f))

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = (jnp.exp(dtt * A)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, ct, precision="highest")

    s, y = jax.lax.scan(step, s0, (x.astype(f), dt.astype(f), Bh, Ch))
    return y, s


def ssd_chunked_scan(x, dt, A, B, C, chunk=128, init_state=None):
    """x [S, H, P] (activations' dtype); dt [S, H] float32, >= 0 and 0 at
    the positions to skip; A [H] float32 < 0; B, C [S, G, N]; `chunk`
    divides S (the prefill buckets are multiples of it; a shorter sequence
    is one chunk). Returns (y [S, H, P] float32, state [H, P, N] float32
    after the last position). Module docstring has the algebra."""
    S, H, P = x.shape
    G, N = B.shape[1:]
    Q = min(int(chunk), S)
    if S % Q:
        raise ValueError(f"chunk {Q} does not divide the sequence {S}")
    nc, f, cd = S // Q, jnp.float32, x.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=f)
    with jax.named_scope("ssd_decay"):
        a = (dt.astype(f) * A.astype(f)[None]).reshape(nc, Q, H)
        cum = jnp.cumsum(a, axis=1)                          # [nc, Q, H]
        total = cum[:, -1]                                   # [nc, H]
        # L[c, h, t, s] = exp(cum_t - cum_s) for s <= t, else 0
        diff = cum[:, :, None, :] - cum[:, None, :, :]       # [nc, t, s, H]
        tri = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
        L = jnp.moveaxis(jnp.exp(jnp.where(tri, diff, -jnp.inf)), 3, 1)
        dtx = (dt.astype(f)[..., None] * x.astype(f)).reshape(nc, Q, H, P)
    xc = dtx.astype(cd)
    Bc, Cc = B.reshape(nc, Q, G, N), C.reshape(nc, Q, G, N)
    with jax.named_scope("ssd_diag"):
        cb = mm("ctgn,csgn->cgts", Cc, Bc)                   # [nc, G, Q, Q]
        m = (_group_of_heads(jnp.moveaxis(cb, 1, -2), H)     # [nc, t, H, s]
             * jnp.moveaxis(L, 1, 2)).astype(cd)
        y = mm("cths,cshp->cthp", m, xc)                     # [nc, Q, H, P]
    with jax.named_scope("ssd_states"):
        to_end = jnp.exp(total[:, None, :] - cum)            # [nc, Q, H]
        xz = (dtx * to_end[..., None]).astype(cd)
        Bh = _group_of_heads(Bc, H)                          # [nc, Q, H, N]
        z = mm("cshp,cshn->chpn", xz, Bh)                    # [nc, H, P, N]
    with jax.named_scope("ssd_carry"):
        s0 = (jnp.zeros((H, P, N), f) if init_state is None
              else init_state.astype(f))

        def carry(s, inp):
            zc, tot = inp
            return jnp.exp(tot)[:, None, None] * s + zc, s

        last, before = jax.lax.scan(carry, s0, (z, total))   # [nc, H, P, N]
    with jax.named_scope("ssd_off"):
        Ch = _group_of_heads(Cc, H)                          # [nc, Q, H, N]
        off = mm("cthn,chpn->cthp", Ch, before.astype(cd))
        y = y + jnp.exp(cum)[..., None] * off
    return y.reshape(S, H, P), last


# -- decode: one token, the state in place ------------------------------------

_HEAD_BLOCK = 8     # heads a kernel block: [8, 128, 256] float32 is 1 MiB;
#                     in, out and both double-buffered 4 MiB of VMEM


def ssm_decode_kernel_supported(pool_shape, pool_dtype, groups) -> bool:
    """The shapes the Pallas kernel is written for: a float32 pool
    `[L, M, H, P, N]` whose `[P, N]` are whole (8, 128) tiles, heads in
    whole blocks of `_HEAD_BLOCK`, and a block's heads in ONE group."""
    _, _, H, P, N = pool_shape
    hb = _HEAD_BLOCK
    return (jnp.dtype(pool_dtype) == jnp.float32 and P % 128 == 0
            and N % 128 == 0 and H % hb == 0 and H % groups == 0
            and (H // groups) % hb == 0)


def ssm_decode_path(pool_shape, pool_dtype, groups) -> str:
    """Which implementation `ssm_decode_update` traces for these shapes:
    "kernel" (a TPU backend, or the interpreter the tests turn on, and a
    shape `ssm_decode_kernel_supported` admits) or "reference". Known
    before anything is traced; `stats()["ssm_decode_path"]` shows it."""
    # lint: allow(flag-in-trace): interpret mode is lowering structure (pallas_ops._interpret); the choice of path is made at trace time by design
    runs = (bool(flag("FLAGS_flash_attention_interpret"))
            or jax.default_backend() == "tpu")
    if runs and ssm_decode_kernel_supported(pool_shape, pool_dtype, groups):
        return "kernel"
    return "reference"


def ssm_decode_update_reference(pool, layer, decay, dtx, B, C):
    """The `jax.numpy` form and the kernel's oracle. pool [L, M, H, P, N];
    decay [M, H] (= exp(dt A); 1 for a slot to leave alone); dtx [M, H, P]
    (= dt x; 0 there); B, C [M, G, N]. Returns (pool with layer `layer`
    updated, y [M, H, P] float32)."""
    H = pool.shape[2]
    f = jnp.float32
    s = pool[layer].astype(f)
    Bh, Ch = _group_of_heads(B.astype(f), H), _group_of_heads(C.astype(f), H)
    s = (decay.astype(f)[:, :, None, None] * s
         + dtx.astype(f)[..., None] * Bh[:, :, None, :])
    y = jnp.sum(s * Ch[:, :, None, :], axis=-1)
    return pool.at[layer].set(s.astype(pool.dtype)), y


def _decode_kernel(layer_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref,
                   s_out, y_ref):
    del layer_ref                   # the index maps read it
    hb, P, N = s_ref.shape[2:]
    m, hblk = pl.program_id(0), pl.program_id(1)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (P, P), 1))
    b_row = b_ref[0, 0]                                       # [1, N]
    c_row = c_ref[0, 0]
    for h in range(hb):
        # dt x of this head as a COLUMN [P, 1]: the row selected on the
        # diagonal and summed over the lanes (exact: one term a row)
        col = jnp.sum(jnp.where(eye, dtx_ref[0, h:h + 1, :], 0.0), axis=1,
                      keepdims=True)
        s = (decay_ref[m * (hb * pl.num_programs(1)) + hblk * hb + h]
             * s_ref[0, 0, h] + col * b_row)
        s_out[0, 0, h] = s
        y_col = jnp.sum(s * c_row, axis=1, keepdims=True)    # [P, 1]
        # and back to a row [1, P] for a lane-dense result
        y_ref[0, h:h + 1, :] = jnp.sum(jnp.where(eye, y_col, 0.0), axis=0,
                                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call(pool, layer, decay, dtx, B, C, *, interpret):
    L, M, H, P, N = pool.shape
    G, hb = B.shape[1], _HEAD_BLOCK
    per_group = (H // G) // hb       # head blocks a group
    out_pool, y = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # the layer, the decays
            grid=(M, H // hb),
            in_specs=[
                pl.BlockSpec((1, hb, P), lambda m, h, l, d: (m, h, 0)),
                # B and C as [M, G, 1, N]: a block's last two dims are the
                # array's own
                pl.BlockSpec((1, 1, 1, N),
                             lambda m, h, l, d: (m, h // per_group, 0, 0)),
                pl.BlockSpec((1, 1, 1, N),
                             lambda m, h, l, d: (m, h // per_group, 0, 0)),
                pl.BlockSpec((1, 1, hb, P, N),
                             lambda m, h, l, d: (l[0], m, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb, P, N),
                             lambda m, h, l, d: (l[0], m, h, 0, 0)),
                pl.BlockSpec((1, hb, P), lambda m, h, l, d: (m, h, 0)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((M, H, P), jnp.float32)],
        # operands count the scalar-prefetch arguments: the pool is the 6th
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_decode_update",
    )(layer, decay.reshape(-1), dtx, B[:, :, None], C[:, :, None], pool)
    return out_pool, y


def ssm_decode_update(pool, layer, decay, dtx, B, C):
    """One token of every slot through ONE layer of the state pool, in
    place: pool `[L, M, H, P, N]` float32, `layer` an int; decay [M, H]
    float32 (exp(dt A)); dtx [M, H, P] float32 (dt x); B, C [M, G, N]. A
    slot whose decay is 1 and whose dtx is 0 keeps its state bit for bit.
    Returns (pool, y [M, H, P] float32 = S C after the update). Which
    implementation: `ssm_decode_path`.

    The kernel reads and writes only layer `layer` of the pool (the layer
    reaches the index maps as a prefetched scalar, the pool is aliased to
    the result), so a step moves each slot's state once each way and no
    layer is cut out of the pool or written back into it. It is a `jax.jit`
    of its own, so the layers of a decode program share one traced and
    lowered kernel (as `latent_decode_attention`)."""
    G = B.shape[1]
    if ssm_decode_path(pool.shape, pool.dtype, G) == "kernel":
        monitor.stat_add("STAT_ssm_decode_kernel")     # traces, not calls
        f = jnp.float32
        return _decode_call(pool, jnp.full((1,), layer, jnp.int32),
                            decay.astype(f), dtx.astype(f), B.astype(f),
                            C.astype(f), interpret=_interpret())
    monitor.stat_add("STAT_ssm_decode_reference")      # traces, not calls
    return ssm_decode_update_reference(pool, layer, decay, dtx, B, C)
