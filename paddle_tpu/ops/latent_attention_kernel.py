"""Decode attention over a latent (MLA) pool as ONE Pallas kernel: each slot's
own pages, walked in place, the softmax carried across blocks.

The plain form (`ops/paged_ops.paged_latent_attention`, the gather) takes
every slot's whole table out of the pool — `[B, PP*P, Rp]`, written once and
read twice a layer whatever the slots hold — and masks what lies past `pos`.
This kernel follows what the slots HOLD: for each slot it copies that slot's
pages from the pool in HBM into VMEM a block of pages at a time, up to the
page that holds `pos` and no further, and keeps a running maximum, sum and
weighted sum of the rows' first `kv_rank` lanes (flash style), so nothing of
size `B x PP x P` exists anywhere. A page is read once for all the heads of
its slot (a latent row has no head axis) and once for both products (the
values are the row's leading lanes).

Modelled on `jax.experimental.pallas.ops.tpu.paged_attention`
(`paged_flash_attention_kernel_inline_seq_dim`: a loop over the slot's
blocks up to its length, page copies double-buffered, the next block — the
next SLOT's first block at a slot's end — in flight while this one is
computed), and not that kernel, for three reasons:

- it drops a masked position by ADDING a large negative number to its score
  and multiplies the values unmasked, so a non-finite row in a page it reads
  and does not attend (the trash page that fills a table's tail and takes the
  dead slots' writes, the rows past `pos` in a slot's last page) poisons a
  slot that does not own it. Here a masked position is dropped by SELECTION
  in both products: the score by `where`, the value row by `where`. What a
  slot does attend reaches it unfiltered: a non-finite row it owns gives it
  NaN (its score is non-finite), as the gather does;
- it wants K and V as two arrays and would copy every page twice;
- it has no `interpret` argument; this one runs under `pallas_ops._interpret`
  like the flash kernels, so the CPU tests run the same body.

One program, not a grid over slots: the loops over slots, a slot's blocks
and a block's pages live inside the kernel, so the buffer that holds the
block in flight is a loop value and no state crosses grid steps. The query
of every slot (1.3 MB at 32 x 32 x 640 bfloat16) and the result (2.1 MB
float32) sit in VMEM whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _NEG_INF, _interpret

__all__ = ["latent_block_pages", "latent_decode_attention"]

# One of the two VMEM buffers a block of pages is copied into: 32 pages of
# 16 rows x 640 lanes bfloat16 (the benchmark's pool), 512 rows a round. Both
# buffers, the queries and the result stay far inside Mosaic's 16 MiB scoped
# limit. On the v5e, all 7 layers' attention for 32 slots (my chip run,
# PR 30), at 4 / 8 / 16 / 32 / 64 pages a round: holding 32,480 rows as the
# benchmark's mix does 1.83 / 1.25 / 0.98 / 0.83 / 0.84 ms (the gather
# 15.65); 4,000 rows each 6.94 / 4.69 / 3.51 / 2.87 / 2.65; ONE row each
# 0.24 / 0.23 / 0.23 / 0.24 / 0.28.
_BLOCK_BYTES = 640 * 1024
_HEAD_TILE = 16     # query heads are padded to whole sublane tiles (bf16: 16)


def latent_block_pages(page_size, row_width, itemsize, table_width) -> int:
    """Pages one round of copies moves: what fits `_BLOCK_BYTES`, rounded
    down to a power of two, and no more than the table holds. Derived from
    what the code can see; `paged_ops.paged_latent_kernel_supported` asks
    that it divide the table's width."""
    fit = max(1, _BLOCK_BYTES // (page_size * row_width * itemsize))
    return min(1 << (fit.bit_length() - 1), int(table_width))


def _kernel(len_ref, table_ref, layer_ref, q_ref, pool_ref, o_ref, buf, sems,
            *, table_width, kv_rank, scale, precision):
    B = q_ref.shape[0]
    _, bp, P, Rp = buf.shape
    bk = bp * P
    pool = pool_ref.at[layer_ref[0]]

    def copies(b, i, slot, go):
        """Start (`go`) or wait for the copies of block `i` of slot `b`
        into buffer `slot`: the slot's pages `i*bp ..`, as far as the page
        that holds its last position. What lies past it in the buffer is
        whatever an earlier block left there: never attended."""
        first = b * table_width + i * bp

        def one(j, _):
            dma = pltpu.make_async_copy(pool.at[table_ref[first + j]],
                                        buf.at[slot, j], sems.at[slot])
            if go:
                dma.start()
            else:
                dma.wait()
            return 0

        jax.lax.fori_loop(
            0, jnp.minimum(pl.cdiv(len_ref[b], P) - i * bp, bp), one, 0)

    copies(0, 0, 0, True)

    def per_slot(b, slot):
        length = len_ref[b]
        blocks = pl.cdiv(length, bk)
        q = q_ref[b]                                           # [H, Rp]
        H = q.shape[0]

        def per_block(i, carry):
            m, l, acc, slot = carry
            # the next block is in flight while this one is computed: this
            # slot's next, or at its end the next slot's first (every slot
            # has one: a length is at least 1)
            last = i + 1 == blocks
            nb = jnp.where(last, b + 1, b)
            ni = jnp.where(last, 0, i + 1)

            @pl.when(nb < B)
            def _():
                copies(nb, ni, 1 - slot, True)

            copies(b, i, slot, False)
            rows = buf[slot].reshape(bk, Rp)
            s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=precision) * scale   # [H, bk]
            t = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            s = jnp.where(t < length, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            tv = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            v = jnp.where(tv < length, rows[:, :kv_rank], 0)
            acc_new = alpha * acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
            return m_new, l_new, acc_new, 1 - slot

        m0 = jnp.full((H, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((H, 1), jnp.float32)
        acc0 = jnp.zeros((H, kv_rank), jnp.float32)
        _, l, acc, slot = jax.lax.fori_loop(0, blocks, per_block,
                                            (m0, l0, acc0, slot))
        o_ref[b] = acc / l
        return slot

    jax.lax.fori_loop(0, B, per_slot, 0)


@functools.partial(jax.jit, static_argnames=("scale", "kv_rank",
                                             "block_pages", "interpret"))
def _call(q, pool, page_table, lengths, layer, *, scale, kv_rank,
          block_pages, interpret):
    B, H, Rp = q.shape
    P, PP = pool.shape[2], page_table.shape[1]
    Hp = -(-H // _HEAD_TILE) * _HEAD_TILE
    q = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, Hp - H), (0, 0)))
    # float32 pools (the tiny CPU models; no cell) keep true-float32
    # products, as the gather has them under the framework's "highest" pin
    precision = (jax.lax.Precision.HIGHEST if pool.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(_kernel, table_width=PP, kv_rank=kv_rank,
                               scale=scale, precision=precision)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # lengths, the page table, the layer
            grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, block_pages, P, Rp), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hp, kv_rank), jnp.float32),
        interpret=interpret,
        name="latent_decode_attention",
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32).reshape(-1),
      layer, q, pool)
    return out[:, :H]


def latent_decode_attention(q, pool, page_table, lengths, scale, kv_rank,
                            layer=None, block_pages=None):
    """q [B, H, Rp] (padded to the pool's row); pool ONE layer `[N, P, Rp]`,
    or with `layer` the whole `[L, N, P, Rp]` pool, of which that layer is
    read in place — no layer is cut out of the pool first (handed
    `pool[layer]` XLA:TPU copies the layer for the custom call: 167 MB a
    layer at the benchmark's shapes); page_table [B, PP]; lengths [B] >= 1,
    the positions each slot attends. Returns float32 [B, H, kv_rank].
    Scores, running maximum, sum and rescaling are float32; the two products
    take the pool's dtype and accumulate in float32.

    The layer reaches the kernel as a scalar, and the call is a `jax.jit` of
    its own: a decode program's layers share ONE traced and lowered kernel
    (set-up time is an end-to-end metric: with a kernel traced for every
    layer, and every page's copy unrolled in it, the decode program took
    2.6 s to trace where the gather's took 0.3 — 22 s on the chip's host,
    in every process; PERF.md PR 30)."""
    if layer is None:
        pool, layer = pool[None], 0
    P, Rp = pool.shape[2:]
    if block_pages is None:
        block_pages = latent_block_pages(P, Rp, pool.dtype.itemsize,
                                         page_table.shape[1])
    return _call(q, pool, page_table, lengths,
                 jnp.full((1,), layer, jnp.int32), scale=float(scale),
                 kv_rank=int(kv_rank), block_pages=int(block_pages),
                 interpret=_interpret())


# -- grouped-query head pools -------------------------------------------------
#
# The same page walk over split head pools `[L, Hkv, N, P, D]` (a token's K
# and V rows per K/V head, `ops/paged_ops.HeadPoolForm`): the decode
# attention of `paged_ops.paged_attention` where its rule
# `paged_kernel_supported` admits the shapes. Written below the latent
# kernel so that none of that kernel's lines move: a program that holds a
# Pallas kernel keys its compile cache on the kernel's source lines
# (PERF.md PR 29), and the latent family's decode program stays as it was.
#
# What differs from the latent walk is what one page holds and who reads
# it. One copy moves a page of K for ALL the K/V heads, `pool.at[layer, :,
# page]` -> `[Hkv, P, D]` (strided in HBM), and one more that page of V: JAX's
# paged kernel, which this one replaced (PR 37), copied each head's page of
# K and of V on its own, four times the copies at 4 K/V heads, from a grid
# of slots x heads x blocks. Each K/V head's G query heads (query head i
# reads K/V head i // G), padded to a whole sublane tile, are scored
# against the round's rows of that head, the softmax state per head float32
# and flash style. A masked position is dropped by selection in both
# products, as above: no additive mask, no unmasked value row.

__all__ += ["head_block_pages", "head_decode_attention", "head_query_rows"]

# A round's pages of K plus V, one of the two rounds in VMEM: 32 pages of 4
# heads x 16 rows x 128 lanes of K and of V in bfloat16 (the falcon cell's
# pools), 512 rows a head a round. On the v5e, six layers'
# attention for the cell's 96 slots at 8 / 16 / 32 pages a round (my chip
# run, PR 37): the ring's contexts (1,985 pages a layer) 1.45 / 1.50 / 1.23
# ms (JAX's paged kernel at 4 / 8 / 16 / 32 pages a block 8.93 / 5.72 /
# 4.70 / 4.51); full tables 5.70 / 5.64 / 4.16 (31.3 / 16.1 / 10.8 / 8.27);
# ONE position each 0.40 / 0.63 / 0.66 (3.41 / 3.34 / 3.68 / 4.26).
_HEAD_BLOCK_BYTES = 1 << 20


def head_block_pages(page_size, kv_heads, head_dim, itemsize,
                     table_width, block_bytes=_HEAD_BLOCK_BYTES) -> int:
    """Pages one round of copies moves: what of a page of K plus V fits
    `block_bytes`, rounded down to a power of two, and no more than the
    table holds. `paged_ops.paged_kernel_supported` asks that it divide
    the table's width."""
    page = 2 * kv_heads * page_size * head_dim * itemsize
    fit = max(1, block_bytes // page)
    return min(1 << (fit.bit_length() - 1), int(table_width))


def _head_kernel(len_ref, table_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                 kbuf, vbuf, sems, *, table_width, scale, precision):
    B = q_ref.shape[0]
    _, bp, Hkv, P, D = kbuf.shape
    Gp = q_ref.shape[1] // Hkv
    bk = bp * P
    pools = ((k_ref.at[layer_ref[0]], kbuf), (v_ref.at[layer_ref[0]], vbuf))

    def copies(b, i, slot, go):
        """Start (`go`) or wait for the copies of round `i` of slot `b` into
        buffer `slot`: two a page (K, V), each of all the K/V heads, as far
        as the page that holds the slot's last position."""
        first = b * table_width + i * bp

        def one(j, _):
            page = table_ref[first + j]
            for pool, buf in pools:
                dma = pltpu.make_async_copy(pool.at[:, page], buf.at[slot, j],
                                            sems.at[slot])
                if go:
                    dma.start()
                else:
                    dma.wait()
            return 0

        jax.lax.fori_loop(
            0, jnp.minimum(pl.cdiv(len_ref[b], P) - i * bp, bp), one, 0)

    copies(0, 0, 0, True)

    def per_slot(b, slot):
        length = len_ref[b]
        rounds = pl.cdiv(length, bk)

        def per_round(i, carry):
            state, slot = carry
            last = i + 1 == rounds
            nb = jnp.where(last, b + 1, b)
            ni = jnp.where(last, 0, i + 1)

            @pl.when(nb < B)
            def _():
                copies(nb, ni, 1 - slot, True)

            copies(b, i, slot, False)
            t = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            tv = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            new = []
            for h, (m, l, acc) in enumerate(state):
                q = q_ref[b, h * Gp:(h + 1) * Gp, :]                # [Gp, D]
                k = kbuf[slot, :, h].reshape(bk, D)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=precision) * scale                   # [Gp, bk]
                s = jnp.where(t < length, s, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                v = jnp.where(tv < length, vbuf[slot, :, h].reshape(bk, D), 0)
                new.append((
                    m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                    alpha * acc + jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=precision)))
            return tuple(new), 1 - slot

        state0 = tuple((jnp.full((Gp, 1), _NEG_INF, jnp.float32),
                        jnp.zeros((Gp, 1), jnp.float32),
                        jnp.zeros((Gp, D), jnp.float32)) for _ in range(Hkv))
        state, slot = jax.lax.fori_loop(0, rounds, per_round, (state0, slot))
        for h, (_, l, acc) in enumerate(state):
            o_ref[b, h * Gp:(h + 1) * Gp, :] = (acc / l).astype(o_ref.dtype)
        return slot

    jax.lax.fori_loop(0, B, per_slot, 0)


def head_query_rows(heads, kv_heads) -> int:
    """Rows a K/V head's queries take in the kernel: its group, padded to
    whole sublane tiles."""
    return -(-(heads // kv_heads) // _HEAD_TILE) * _HEAD_TILE


@functools.partial(jax.jit, static_argnames=("scale", "block_pages",
                                             "interpret"))
def _head_call(q, k_pool, v_pool, page_table, lengths, layer, *, scale,
               block_pages, interpret):
    B, H, D = q.shape
    _, Hkv, _, P, _ = k_pool.shape
    G, Gp = H // Hkv, head_query_rows(H, Hkv)
    qg = jnp.pad(q.astype(k_pool.dtype).reshape(B, Hkv, G, D),
                 ((0, 0), (0, 0), (0, Gp - G), (0, 0))).reshape(B, -1, D)
    precision = (jax.lax.Precision.HIGHEST if k_pool.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(_head_kernel, table_width=page_table.shape[1],
                               scale=scale, precision=precision)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, block_pages, Hkv, P, D), k_pool.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # lengths, the page table, the layer
            grid=(1,),
            in_specs=[whole, in_place, in_place],
            out_specs=whole,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv * Gp, D), q.dtype),
        interpret=interpret,
        name="head_decode_attention",
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32).reshape(-1),
      layer, qg, k_pool, v_pool)
    return out.reshape(B, Hkv, Gp, D)[:, :, :G].reshape(B, H, D)


def head_decode_attention(q, k_pool, v_pool, page_table, lengths, scale,
                          layer=None, block_pages=None):
    """q [B, H, D]; k_pool / v_pool ONE layer `[Hkv, N, P, D]`, or with
    `layer` the whole `[L, Hkv, N, P, D]` pools, read in place; page_table
    [B, PP]; lengths [B] >= 1, the positions each slot attends. Query head
    i reads K/V head i // (H / Hkv). Returns [B, H, D] in q's dtype. Scores,
    running maximum, sum and rescaling are float32; the two products take
    the pools' dtype and accumulate in float32.

    A round is `head_block_pages` (32 KB a page of K plus V at 4 heads of
    128 in bfloat16: 32 pages, which divide the benchmark's 96-entry
    table). The layer reaches the kernel as a scalar
    and the call is a `jax.jit` of its own, so a decode program's layers
    share one traced kernel, as `latent_decode_attention`'s do."""
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    _, Hkv, _, P, D = k_pool.shape
    if block_pages is None:
        block_pages = head_block_pages(P, Hkv, D, k_pool.dtype.itemsize,
                                       page_table.shape[1])
    return _head_call(q, k_pool, v_pool, page_table, lengths,
                      jnp.full((1,), layer, jnp.int32), scale=float(scale),
                      block_pages=int(block_pages), interpret=_interpret())


# -- fused head pools ----------------------------------------------------------
#
# The same page walk over fused head pools `[L, N, P, row]`: a token's H
# heads of D lanes side by side in one row of whole 128-lane tiles
# (`ops/paged_ops.HeadPoolForm`; gpt2-xl: 25 heads of 64 in 1,664 lanes),
# the decode attention of `paged_ops.paged_attention` where its rule
# `paged_row_kernel_supported` admits the shapes. Written below the two
# kernels above so that none of their lines move: a program that holds a
# Pallas kernel keys its compile cache on the kernel's source lines.
#
# A page of K is one contiguous copy for all the heads, and one more that
# page of V. What differs from the split pools is where a head lies: in the
# LANES of the row, not on an axis of its own. So a slot's queries enter the
# matrix unit block-diagonal: row h holds head h's query in head h's D lanes
# and zeros elsewhere (H rows padded to whole float32 sublane tiles), and
# [Hp, row] x [row, bk] scores every head against the round's rows at once
# (a zero lane, past H*D too, adds nothing); p [Hp, bk] x V [bk, row] gives
# each head's weighted sum in every lane, of which row h keeps head h's
# lanes, by selection. Both products are float32 at "highest" (bfloat16
# pages are widened in VMEM): scores, maximum, sum and rescaling are float32
# and no K, V or q value is rounded below float32. A masked position is
# dropped by selection in both products, as above.
#
# On the v5e, the 48 layers' attention at the gpt2-xl cell's shapes (16
# slots, 25 x 64 in 1,664 lanes, float32 pages of 16, 320 pages, a 64-entry
# table; my chip run, PR 39), timed alone: the ring's contexts (227 pages
# held) at 4 / 8 / 16 pages a round 5.12 / 4.52 / 5.00 ms; a vector-unit
# form (K * q over the lanes, each head's lanes summed against a 0/1 matrix
# in three bfloat16 parts) 5.52 / 5.16 / 5.88; pool-dense 23.2. Full tables
# at 4 pages: 18.6 (vector-unit 19.5); one position a slot 1.85 (2.08).
# Hence rounds of 2 MiB of K plus V: 8 pages, 128 rows, at those shapes.
_ROW_BLOCK_BYTES = 2 << 20

__all__ += ["row_block_pages", "row_decode_attention", "row_query_rows"]


def row_block_pages(page_size, row, itemsize, table_width) -> int:
    """Pages a round of the fused-row kernel moves: `head_block_pages` of
    one row a page at `_ROW_BLOCK_BYTES`.
    `paged_ops.paged_row_kernel_supported` asks that it divide the table's
    width."""
    return head_block_pages(page_size, 1, row, itemsize, table_width,
                            _ROW_BLOCK_BYTES)


def row_query_rows(heads) -> int:
    """Rows of a slot's block-diagonal queries: its heads, padded to whole
    float32 sublane tiles."""
    return -(-heads // 8) * 8


def _row_kernel(len_ref, table_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                kbuf, vbuf, sems, *, table_width, heads, head_dim, scale):
    B = q_ref.shape[0]
    _, bp, P, R = kbuf.shape
    bk = bp * P
    pools = ((k_ref.at[layer_ref[0]], kbuf), (v_ref.at[layer_ref[0]], vbuf))
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32,
                             (row_query_rows(heads), R))
    head, lane = iota(0), iota(1)
    own = ((lane >= head * head_dim) & (lane < (head + 1) * head_dim)
           & (head < heads))                      # row h: head h's lanes

    def copies(b, i, slot, go):
        """Start (`go`) or wait for the copies of round `i` of slot `b` into
        buffer `slot`: two a page (K, V), each a whole row of every head,
        as far as the page that holds the slot's last position."""
        first = b * table_width + i * bp

        def one(j, _):
            page = table_ref[first + j]
            for pool, buf in pools:
                dma = pltpu.make_async_copy(pool.at[page], buf.at[slot, j],
                                            sems.at[slot])
                if go:
                    dma.start()
                else:
                    dma.wait()
            return 0

        jax.lax.fori_loop(
            0, jnp.minimum(pl.cdiv(len_ref[b], P) - i * bp, bp), one, 0)

    copies(0, 0, 0, True)

    def dot(x, y, contract):
        return jax.lax.dot_general(x, y, (contract, ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=jax.lax.Precision.HIGHEST)

    def per_slot(b, slot):
        length = len_ref[b]
        rounds = pl.cdiv(length, bk)
        q = jnp.where(own, q_ref[b].astype(jnp.float32), 0.0)     # [Hp, R]

        def per_round(i, carry):
            m, l, acc, slot = carry
            last = i + 1 == rounds
            nb = jnp.where(last, b + 1, b)
            ni = jnp.where(last, 0, i + 1)

            @pl.when(nb < B)
            def _():
                copies(nb, ni, 1 - slot, True)

            copies(b, i, slot, False)
            t = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            tv = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            k = kbuf[slot].reshape(bk, R).astype(jnp.float32)
            s = dot(q, k, ((1,), (1,))) * scale                   # [Hp, bk]
            s = jnp.where(t < length, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            v = jnp.where(tv < length,
                          vbuf[slot].reshape(bk, R).astype(jnp.float32), 0.0)
            return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                    alpha * acc + dot(p, v, ((1,), (0,))), 1 - slot)

        m0 = jnp.full((q.shape[0], 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((q.shape[0], 1), jnp.float32)
        _, l, acc, slot = jax.lax.fori_loop(
            0, rounds, per_round, (m0, l0, jnp.zeros_like(q), slot))
        o_ref[b] = jnp.sum(jnp.where(own, acc / l, 0.0), axis=0,
                           keepdims=True).astype(o_ref.dtype)
        return slot

    jax.lax.fori_loop(0, B, per_slot, 0)


@functools.partial(jax.jit, static_argnames=("scale", "block_pages",
                                             "interpret"))
def _row_call(q, k_pool, v_pool, page_table, lengths, layer, *, scale,
              block_pages, interpret):
    B, H, D = q.shape
    P, R = k_pool.shape[2:]
    rows = jnp.pad(q.reshape(B, 1, H * D), ((0, 0), (0, 0), (0, R - H * D)))
    kernel = functools.partial(_row_kernel, table_width=page_table.shape[1],
                               heads=H, head_dim=D, scale=scale)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, block_pages, P, R), k_pool.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # lengths, the page table, the layer
            grid=(1,),
            in_specs=[whole, in_place, in_place],
            out_specs=whole,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, 1, R), q.dtype),
        interpret=interpret,
        name="row_decode_attention",
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32).reshape(-1),
      layer, rows, k_pool, v_pool)
    return out[:, 0, :H * D].reshape(B, H, D)


def row_decode_attention(q, k_pool, v_pool, page_table, lengths, scale,
                         layer=None, block_pages=None):
    """q [B, H, D]; k_pool / v_pool ONE fused layer `[N, P, row]` (a
    token's H heads of D lanes side by side, zero past H*D), or with
    `layer` the whole `[L, N, P, row]` pools, read in place; page_table
    [B, PP]; lengths [B] >= 1, the positions each slot attends. Query head
    i reads lanes i*D .. (i+1)*D of every row. Returns [B, H, D] in q's
    dtype. Every product and sum is float32 (the comment above
    `_ROW_BLOCK_BYTES`).

    A round is `row_block_pages`. The layer reaches the kernel as a scalar
    and the call is a `jax.jit` of its own, so a decode program's layers
    share one traced kernel, as `head_decode_attention`'s do."""
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    P, R = k_pool.shape[2:]
    if block_pages is None:
        block_pages = row_block_pages(P, R, k_pool.dtype.itemsize,
                                      page_table.shape[1])
    return _row_call(q, k_pool, v_pool, page_table, lengths,
                     jnp.full((1,), layer, jnp.int32), scale=float(scale),
                     block_pages=int(block_pages), interpret=_interpret())
