"""Paged KV-cache attention: three implementations, two shape rules.

vLLM's PagedAttention insight, TPU-shaped: decode-time K/V lives in
fixed-size **pages** inside preallocated per-layer pools, and a
per-sequence **page table** maps logical token positions to physical
pages — so sequences of wildly
different lengths share one pool with zero fragmentation beyond the last
partial page, and admission control is exact page arithmetic
(`serving/kv_cache.py`).

Three decode-attention implementations over that layout, one math:

- **kernel** — one Pallas program walks each slot's own pages in place
  as far as `pos`, one copy a page for all the heads
  (`ops/latent_attention_kernel.py`): `head_decode_attention` over
  split pools, shape-gated by `paged_kernel_supported` (head dim a
  multiple of 128), and `row_decode_attention` over fused pools,
  whose heads lie in the lanes of one row, shape-gated by
  `paged_row_kernel_supported` (one K/V head a query head); each round
  derived from the page's bytes and the table's width. Flag-gated by
  `FLAGS_use_paged_attention` and run where a Pallas kernel runs.
- **pool** (pool-dense, `paged_pool_attention`) — no gather: all B
  rows' queries are scored against the layer's WHOLE pool in one
  batched matmul (`[B, D] x [D, N*P]` per head) and a page-ownership
  mask (`paged_pool_mask`, built once a step from the page table and
  `pos` alone) says which pool rows each sequence reads. Every pool
  page is read once per layer, whatever the batch. Shape-gated by
  `paged_pool_dense_supported`: floating pools, not a shape of the
  split pools' kernel, and `N <= B*PP` — the pool holds no more pages
  than the gather would materialize, so it never reads more than the
  path it replaces. Where a Pallas kernel runs, the fused rows' kernel
  is chosen before it; elsewhere (the CPU) such shapes stay here.
- **reference** (every other shape: a pool larger than `B*PP`, int8
  pools, and the oracle the other two are tested against) — gather the
  page table into a dense `[B, H, T, D]` buffer and run
  `cached_attention`, the EXACT masked-softmax expression
  `GPTModel.generate`'s fixed cache uses, so the generation engine's
  greedy decode is anchored to the same oracle as
  `tests/test_generate.py` (positions beyond `pos` mask to -1e30 → exp
  underflows to exactly 0.0, so page-tail junk and trash-page reads
  contribute +0.0 and numerics match the contiguous cache bit-for-bit
  within one compiled shape). The pool path is the same masked
  expression over the pool's PHYSICAL order — a permutation of the same
  sum.

**Row isolation** is part of the contract of all three: a row's output
depends only on the positions `t <= pos` of its own pages. The gather
and the kernel get that by reading nothing else; the pool path reads
every page for every row, and `0.0 * NaN` is NaN, so it masks K by
`where` (a NaN score outside the mask is dropped, never multiplied),
multiplies the probabilities by a V made finite, and sets a row's
output to NaN where a V row it attends is non-finite — the owner of a
poisoned page still trips the engine's non-finite-logit flag and nobody
else does. No "the pools are always finite" assumption.

All choices are trace-time (python `if` under `jax.jit`) by observable
shape — `paged_attention_path` names the one a call takes — and counted
by `STAT_paged_attn_kernel` / `STAT_paged_attn_pool` /
`STAT_paged_attn_reference`: **traces**, not calls, mirroring the
exact-compile accounting everywhere else in the serving stack.

**Two forms of a head pool, one shape rule** (`HeadPoolForm`,
`head_pools_fused`). The paged kernel reads a layer as
`[Hkv, N, P, D]` in place, and with a head width that fills
whole 128-lane tiles that shape is dense on the TPU: such heads keep the
*split* form `[L, H, N, P, D]` (scales `[L, H, N]`). Narrower heads
(64-wide: GPT-2) would pad every (page, head) tile 2.56-fold there, and
XLA:TPU relaid the whole pools in and out of every program (PERF.md,
PR 24-28); they take the *fused* form `[L, N, P, H*D]` (scales
`[L, N, H]`): a row is a token's heads side by side, the page axis is
axis 1 as in the latent pools, and the plain row-major layout is dense.
Like a latent row (`latent_pool_width`) a fused row takes whole 128-lane
tiles, the lanes past `H*D` zero (gpt2-xl: 1,600 values in 1,664 lanes):
the backend's DEFAULT layout for `f32[48,128,16,1600]` puts the 128 pages
in the lanes, because that pads nothing, and a layout that is not the
default does not survive JAX's persistent compile cache (PR 28: an
executable the cache hands back returns default layouts); with a
lane-exact row the default is the row-major one the decode program
wants, for any number of pages. Every function below takes either form
and tells them by rank; what it hands the arithmetic (whole pages, scale
rows, gathered views) has the split form's axis order in both, so each
algorithm is written once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import monitor
from ..framework.flags import flag

__all__ = ["HeadPoolForm", "head_pools_fused",
           "cached_attention", "paged_attention", "paged_attention_path",
           "paged_gather", "paged_kernel_supported",
           "paged_pool_attention", "paged_pool_dense_supported",
           "paged_pool_mask",
           "paged_gather_layers", "paged_gather_quantized",
           "latent_pool_width", "paged_latent_attention",
           "paged_latent_kernel_supported", "paged_latent_path",
           "paged_latent_write", "paged_row_kernel_supported",
           "paged_prefix_attention", "paged_write",
           "paged_write_quantized", "page_rows_for_positions",
           "sharded_paged_attention"]


# Every function below that touches the pools runs under a named scope of
# its own — `kv_write`, `kv_gather`, `kv_mask`, `kv_attend` — and a kernel
# under its own name, so a profiler trace tells the pools' relay from the
# attention (tools/trace_report.py). Names are metadata: the programs are
# the same.


# -- where the page axis and the head axis are --------------------------------


def head_pools_fused(head_dim: int) -> bool:
    """The shape rule of the head pools (module docstring): a head width
    that is a whole number of 128-lane tiles keeps the split form the
    Pallas kernel reads in place (`paged_kernel_supported` asks for the
    same widths); any other width takes the fused form, whose row-major
    layout is dense. By observable shape, like `paged_attention_path`."""
    return int(head_dim) % 128 != 0


class HeadPoolForm:
    """Where the page axis and the head axis of a head pool are: the one
    place the cache, the engine and the family's programs ask.

    split  K/V `[L, H, N, P, D]`, scales `[L, H, N]`: page axis 2, heads 1
    fused  K/V `[L, N, P, row]`, scales `[L, N, H]`: page axis 1, heads last

    A fused `row` is the heads' `H*D` values in whole 128-lane tiles
    (`latent_pool_width`). `heads` is the number the pools hold in all;
    on a tp mesh of `shards` devices each shard's own heads lie in a row
    of their own whole tiles, so that the last axis splits evenly and a
    shard sees the pool of a `HeadPoolForm(heads // shards, head_dim)`
    (the rule reads the head width, which sharding leaves alone)."""

    def __init__(self, heads: int, head_dim: int, shards: int = 1):
        self.heads, self.head_dim = int(heads), int(head_dim)
        self.shards = int(shards)
        self.fused = head_pools_fused(head_dim)
        self.page_axis = 1 if self.fused else 2
        self.pool_rank = 4 if self.fused else 5
        self.name = "[L,N,P,H*D]" if self.fused else "[L,H,N,P,D]"
        # the lanes one token takes in a K or V pool, and those it fills
        self.used = self.heads * self.head_dim
        self.row = (self.shards * latent_pool_width(self.used // self.shards)
                    if self.fused else self.used)

    def pool_shape(self, layers, num_pages, page_size):
        if self.fused:
            return (layers, num_pages, page_size, self.row)
        return (layers, self.heads, num_pages, page_size, self.head_dim)

    def scale_shape(self, layers, num_pages):
        return ((layers, num_pages, self.heads) if self.fused
                else (layers, self.heads, num_pages))

    def layer_shape(self, pool_shape):
        """One layer of a pool as the shape rules read it, `(H, N, P, D)`
        (`paged_attention_path`), whichever form the pool has."""
        N, P = pool_shape[self.page_axis:self.page_axis + 2]
        return (self.heads, N, P, self.head_dim)

    def head_axis(self, ndim: int, lead: int = 0) -> int:
        """The axis a tp mesh shards, of a pool, a scale pool, one page
        cut out of either, or (`lead=1`) a chunk of such pages."""
        return ndim - 1 if self.fused else 1 + lead

    def spec(self, ndim: int, axis_name: str = "tp", lead: int = 0):
        from jax.sharding import PartitionSpec
        spec = [None] * ndim
        spec[self.head_axis(ndim, lead)] = axis_name
        return PartitionSpec(*spec)

    def _index(self, pages):
        return (slice(None),) * self.page_axis + (pages,)

    def pages(self, pool, pages):
        """Whole pages of a K/V or scale pool by id, in the pool's own
        order: a scalar id cuts the page axis out, a vector [W] leaves W
        in its place."""
        return pool[self._index(pages)]

    def at_pages(self, pool, pages):
        """`pool.at[...]` over the same pages (zero, copy-on-write, tier
        write): `.set` takes what `pages` returns."""
        return pool.at[self._index(pages)]

    def zero_pages(self, pool, pages, scratch):
        """Zero the pages a scratch-padded row names (`PagedKVCache.
        zero_rows`: the freed pages first, the scratch page's id after
        them) — one page at a time and in place, the freed pages and the
        scratch page once. One scatter of the whole row first builds a
        row of zero pages and then writes every padding entry too: for
        gpt2-xl's 64 entries 314 MB a pool where a request frees 18
        pages of 4.9 MB (compiled for the v5e, PR 28)."""
        ax = self.page_axis
        n = jnp.minimum(jnp.sum(pages != scratch) + 1, pages.shape[0])
        zero = jnp.zeros(pool.shape[:ax] + (1,) + pool.shape[ax + 1:],
                         pool.dtype)
        return jax.lax.fori_loop(
            0, n, lambda i, pool: jax.lax.dynamic_update_slice_in_dim(
                pool, zero, pages[i], axis=ax), pool)

    def from_chunk(self, blocks):
        """A host-tier chunk `[W, *page]` (pages stacked in front) as
        `at_pages(pool, ids [W]).set` takes it."""
        return jnp.moveaxis(blocks, 0, self.page_axis)


def _split_heads(x, hd):
    """A fused row's heads: [..., row] -> [..., H, D], `hd` = (H, D); the
    lanes past H*D are the row's padding."""
    H, D = hd
    return x[..., :H * D].reshape(x.shape[:-1] + (H, D))


def _merge_heads(x, row):
    """[..., H, D] -> a fused row [..., row], zero past H*D."""
    return _pad_lanes(x.reshape(x.shape[:-2] + (-1,)), row)


# Whole pages and their scale rows, handed over in the SPLIT form's axis
# order whichever form the pool has (a fused pool: rank 4, one layer rank
# 3). `layer=None`: every layer, ids [S] -> pages [L, H, S, P, D], scales
# [L, H, S]. `layer` an int: ids [B] -> pages [H, B, P, D], scales [H, B].


def _take_pages(pages, layer, ids, hd):
    if layer is None:
        if pages.ndim == 5:
            return pages[:, :, ids]
        return jnp.moveaxis(_split_heads(pages[:, ids], hd), 3, 1)
    if pages.ndim == 5:
        return pages[layer][:, ids]
    return jnp.moveaxis(_split_heads(pages[layer, ids], hd), 2, 0)


def _put_pages(pages, layer, ids, blocks):
    if layer is None:
        if pages.ndim == 5:
            return pages.at[:, :, ids].set(blocks)
        return pages.at[:, ids].set(
            _merge_heads(jnp.moveaxis(blocks, 1, 3), pages.shape[-1]))
    if pages.ndim == 5:
        # the scalar layer index joins the advanced block, which is then
        # non-contiguous, so the batch dim lands in FRONT (same subtlety
        # as paged_write's docstring) — move it there
        return pages.at[layer, :, ids, :, :].set(jnp.moveaxis(blocks, 1, 0))
    return pages.at[layer, ids].set(
        _merge_heads(jnp.moveaxis(blocks, 0, 2), pages.shape[-1]))


def _gather_rows(pages, table, axis, hd, scales=None, dtype=None):
    """The gathers over a fused pool: the table's pages taken along the
    page `axis` (0: one layer `[N, P, row]`, table [B, PP]; 1: all layers
    `[L, N, P, row]`, table [PP]), dequantized by their `scales`
    (`[.., N, H]`) where given, as `[.., H, PP*P, D]`."""
    kb = _split_heads(jnp.take(pages, table, axis=axis), hd)  # [..,PP,P,H,D]
    if scales is not None:
        sc = jnp.take(scales, table, axis=axis)               # [.., PP, H]
        kb = kb.astype(dtype) * sc[..., None, :, None].astype(dtype)
    kb = kb.reshape(kb.shape[:-4] + (-1,) + kb.shape[-2:])    # [.., T, H, D]
    return jnp.moveaxis(kb, -3, -2)


def _take_scales(scales, layer, ids, fused):
    if layer is None:
        return (jnp.swapaxes(scales[:, ids], 1, 2) if fused
                else scales[:, :, ids])
    return scales[layer, ids].T if fused else scales[layer][:, ids]


def _at_scales(scales, layer, ids, fused):
    """`.at[...]` over the same rows; its `.set` / `.max` take them as
    `_pool_order` lays them (a scalar layer index beside the ids puts the
    batch dim in FRONT: [B, H] for one layer of either form)."""
    if layer is None:
        return scales.at[:, ids] if fused else scales.at[:, :, ids]
    return scales.at[layer, ids] if fused else scales.at[layer, :, ids]


def _pool_order(rows, layer, fused):
    """Scale rows in the split order ([L, H, S] / [H, B]) as `_at_scales`
    takes them: [L, S, H] for a fused pool, [B, H] for one layer of
    either (the scalar layer index puts the batch dim in front)."""
    if layer is None:
        return jnp.swapaxes(rows, 1, 2) if fused else rows
    return rows.T


@jax.named_scope("kv_attend")
def cached_attention(q, kb, vb, pos, scale):
    """Masked attention of one-position queries over a dense cache.

    q [B, H, D]; kb/vb [B, H, T, D]; pos scalar or [B] int (index of the
    LAST valid cache position — attention covers t <= pos, exactly
    `GPTModel.generate`'s decode mask). Returns [B, H, D]."""
    s = jnp.einsum("bhd,bhtd->bht", q, kb) * scale
    T = kb.shape[2]
    limit = pos[:, None, None] if jnp.ndim(pos) else pos
    s = jnp.where(jnp.arange(T)[None, None, :] <= limit, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,bhtd->bhd", p, vb)


@jax.named_scope("kv_gather")
def paged_gather(pages, page_table, heads=None):
    """Materialize page-table rows as a dense cache view.

    pages [H, N, P, D] (one layer's pool) or fused [N, P, row] with
    `heads` = (H, D) given; page_table [B, PP] int32. Returns [B, H, PP*P, D] —
    logical token order regardless of physical page placement."""
    B, PP = page_table.shape
    if pages.ndim == 3:
        return _gather_rows(pages, page_table, 0, heads)
    H, _, P, D = pages.shape
    kb = jnp.take(pages, page_table, axis=1)     # [H, B, PP, P, D]
    return jnp.moveaxis(kb, 1, 0).reshape(B, H, PP * P, D)


@jax.named_scope("kv_mask")
def paged_pool_mask(page_table, pos, num_pages, page_size):
    """Page-ownership mask of the pool-dense path: which rows of one
    layer's pool, in PHYSICAL order, each sequence attends.

    page_table [B, PP] int32; pos [B] int32 (last valid position).
    Returns valid [B, N*P] bool: pool row `n*P + p` is valid for
    sequence b iff page n is entry j of `page_table[b]` with
    `j*P + p <= pos[b]` — exactly the positions `cached_attention`
    leaves unmasked in the gathered view. A page shared by two rows
    (prefix cache, copy-on-write) is owned by both, each at its own j;
    table entries past a row's length, free pages and other rows' pages
    are masked; an inactive slot (every entry `TRASH_PAGE`, pos 0) keeps
    the trash page's first row, as it does in the gathered view. Built
    from the table and `pos` alone, so one mask serves every layer of a
    decode step."""
    PP = page_table.shape[1]
    # last valid offset inside table entry j: P-1 for a full page,
    # pos % P for the page holding `pos`, negative past the row's length
    last = jnp.minimum(
        pos[:, None] - jnp.arange(PP, dtype=pos.dtype)[None, :] * page_size,
        page_size - 1)                                        # [B, PP]
    own = page_table[:, :, None] == jnp.arange(num_pages)[None, None, :]
    limit = jnp.max(jnp.where(own, last[:, :, None], -1), axis=1)  # [B, N]
    offs = jnp.arange(num_pages * page_size) % page_size
    return offs[None, :] <= jnp.repeat(limit, page_size, axis=1)


@jax.named_scope("kv_attend")
def paged_pool_attention(q, k_pages, v_pages, valid, scale):
    """Pool-dense decode attention: every row's query against the whole
    of one layer's pool, `valid` (`paged_pool_mask`) picking each row's
    own positions. q [B, H, D]; k_pages/v_pages [H, N, P, D]; valid
    [B, N*P]. Returns [B, H, D].

    The masked softmax is `cached_attention`'s (-1e30 → exactly 0.0)
    over the pool's physical order. Isolation (module docstring): a
    non-finite K row outside the mask is dropped by the `where`; V is
    multiplied as a finite copy, and a (row, head) whose own valid V
    rows are not all finite reads NaN, as the gather would give it.

    A fused layer `[N, P, row]` is read as `[N*P, H, D]`, rows outermost
    as they lie: the same products and sums with the pool's row axis in
    front of the head axis."""
    if k_pages.ndim == 3:
        return _pool_attention_rows(q, k_pages, v_pages, valid, scale)
    H, N, P, D = k_pages.shape
    k = k_pages.reshape(H, N * P, D)
    # V has two readers, the product and the is-finite reduction. Left
    # alone XLA:TPU slices the layer out of the pool once for each; the
    # barrier makes the slice one value that both read (on the v5e,
    # gpt2-xl's decode step: 39.2 -> 35.3 ms, PERF.md PR 26; on K, which
    # has one reader, a barrier changes nothing)
    v = jax.lax.optimization_barrier(v_pages.reshape(H, N * P, D))
    s = jnp.einsum("bhd,htd->bht", q, k) * scale
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    finite = jnp.isfinite(v)
    out = jnp.einsum("bht,htd->bhd", p, jnp.where(finite, v, 0))
    poisoned = ~jnp.all(finite, axis=-1)                      # [H, N*P]
    bad = jnp.any(valid[:, None, :] & poisoned[None], axis=-1)    # [B, H]
    return jnp.where(bad[..., None], jnp.nan, out)


def _pool_attention_rows(q, k_pages, v_pages, valid, scale):
    """`paged_pool_attention` over one fused layer `[N, P, row]`."""
    hd = q.shape[1:]
    k = _split_heads(k_pages.reshape(-1, k_pages.shape[-1]), hd)  # [T,H,D]
    v = jax.lax.optimization_barrier(
        _split_heads(v_pages.reshape(-1, v_pages.shape[-1]), hd))
    s = jnp.einsum("bhd,thd->bht", q, k) * scale
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    finite = jnp.isfinite(v)
    out = jnp.einsum("bht,thd->bhd", p, jnp.where(finite, v, 0))
    poisoned = ~jnp.all(finite, axis=-1)                      # [T, H]
    bad = jnp.any(valid[:, :, None] & poisoned[None], axis=1)     # [B, H]
    return jnp.where(bad[..., None], jnp.nan, out)


def page_rows_for_positions(page_table, positions, page_size):
    """(page_ids, offsets) physical coordinates for logical `positions`.

    page_table [PP] or [B, PP]; positions [S] (with a [PP] table), [B]
    (with a [B, PP] table — one position per row), or [B, S] (with a
    [B, PP] table — a block of positions per row, the speculative
    verify shape). Out-of-range page indices clamp onto the row's last
    entry (XLA gather semantics) — callers mask such coordinates to the
    scratch page before writing."""
    if page_table.ndim == 1:
        return page_table[positions // page_size], positions % page_size
    B = page_table.shape[0]
    if positions.ndim == 2:
        rows = jnp.arange(B)[:, None]
        return (page_table[rows, positions // page_size],
                positions % page_size)
    return (page_table[jnp.arange(B), positions // page_size],
            positions % page_size)


def _write_rows(pages, layer, page_ids, offsets, values):
    """`paged_write`, outside its scope."""
    if pages.ndim == 4:
        if layer is not None:
            return pages.at[layer, page_ids, offsets, :].set(
                _merge_heads(values, pages.shape[-1]))
        rows = _merge_heads(jnp.moveaxis(values, 1, 2), pages.shape[-1])
        # one scatter of L x S whole rows, the layer an INDEX like page
        # and offset: with the layer axis left as a window (`[:, ids,
        # offs]`) XLA:TPU relays the whole pool to a layers-minor layout
        # and back (compiled for the v5e, PR 27 and PR 28), and one
        # scatter a layer traces and lowers 48 of them a pool
        layers = jnp.arange(pages.shape[0])[:, None]
        return pages.at[layers, page_ids[None], offsets[None], :].set(rows)
    # the split form: layer and head are INDICES like page and offset, and
    # the window is one head's D values. With either left as a window
    # (`[layer, :, ids, offs]`) XLA:TPU wants the heads beside the lanes and
    # relays the whole pool to `[L, N, P, H, D]` and back in every program
    # that writes (compiled for the v5e at 4 K/V heads, PR 36: four copies
    # of a 340 MB pool a decode step), as it did for the fused form's layers
    heads = jnp.arange(pages.shape[1])
    if layer is None:
        layers = jnp.arange(pages.shape[0])[:, None, None]
        return pages.at[layers, heads[None, :, None],
                        page_ids[None, None], offsets[None, None], :].set(
                            values)
    # values are [B, H, D]
    return pages.at[layer, heads[None], page_ids[:, None],
                    offsets[:, None], :].set(values)


@jax.named_scope("kv_write")
def paged_write(pages, layer, page_ids, offsets, values):
    """Scatter per-row K/V vectors into one layer of a paged pool.

    pages [L, H, N, P, D]; page_ids/offsets [B]; values [B, H, D] (the
    integer layer index joins the advanced block, which is then
    non-contiguous, so numpy indexing moves the batch dim to the
    front). `layer=None` writes all layers at once (prefill):
    page_ids/offsets [S], values [L, H, S, D] (adjacent advanced block
    stays in place). A fused pool [L, N, P, row] takes the same values
    and stores each token's heads side by side in one row, zero past
    them."""
    return _write_rows(pages, layer, page_ids, offsets, values)


# -- int8 page mode ---------------------------------------------------------
#
# FLAGS_kv_cache_dtype=int8: pools store int8 with a parallel
# per-(layer, head, page) fp32 scale pool (symmetric abs-max; dequant =
# q * scale). Writes QUANTIZE on append; reads dequantize on gather. The
# quantization grid is per page: when a newly appended token's abs-max
# exceeds the page's current scale, the page's existing int8 content is
# REQUANTIZED onto the wider grid (round(q * old/new)) — shape-static,
# touches only the [P, D] page being appended to, and bounds the
# round-off to one extra rounding per scale growth. Scale 0 marks an
# empty page (zero-on-free resets both pools), so freed pages never leak
# a stale grid to their next owner.


def _q8(v, s):
    """Symmetric int8 quantization of `v` against per-slice scales `s`
    (broadcastable); s == 0 (empty/all-zero) maps to 0."""
    q = jnp.where(s > 0, v / jnp.where(s > 0, s, 1.0), 0.0)
    return jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8)


@jax.named_scope("kv_gather")
def paged_gather_quantized(pages, scales, page_table, dtype=jnp.float32,
                           heads=None):
    """Dequantizing gather: int8 pages [H, N, P, D] + scales [H, N] (or
    fused [N, P, row] + [N, H], `heads` = (H, D)) → dense floating
    [B, H, PP*P, D] (only THIS batch's pages are ever materialized in
    floating form — the pools stay int8 in HBM)."""
    monitor.stat_add("STAT_kv_quant_reads")  # traces, not calls
    B, PP = page_table.shape
    if pages.ndim == 3:
        return _gather_rows(pages, page_table, 0, heads, scales, dtype)
    H, _, P, D = pages.shape
    kb = jnp.take(pages, page_table, axis=1)        # [H, B, PP, P, D]
    sc = jnp.take(scales, page_table, axis=1)       # [H, B, PP]
    kb = kb.astype(dtype) * sc[..., None, None].astype(dtype)
    return jnp.moveaxis(kb, 1, 0).reshape(B, H, PP * P, D)


@jax.named_scope("kv_write")
def paged_write_quantized(pages, scales, layer, page_ids, offsets, values,
                          requant=False):
    """Quantize-on-append into int8 pools; returns (pages, scales).

    Decode (`layer` an int): page_ids/offsets [B], values [B, H, D] —
    gathers each row's single page, grows its scale to cover the new
    token (requantizing existing content when it does), writes the
    quantized token. Duplicate page ids (inactive slots parked on the
    trash page) scatter last-writer-wins, which is fine for the same
    reason the fp32 path tolerates it: trash content is masked junk.

    Prefill (`layer=None`): page_ids/offsets [S], values [L, H, S, D] —
    scatter-max builds each target page's scale over every token landing
    in it, then all tokens quantize against their page's final scale.
    Assumes freshly zeroed target pages (scale 0 — exactly what
    zero-on-free guarantees for an alloc) UNLESS `requant=True` (a
    trace-time switch): the tail-prefill program (prefix cache,
    ISSUE 12) can write onto a copy-on-write split page that arrives
    with cloned content + a non-zero scale, so it additionally
    requantizes the target pages' existing content onto the (possibly
    widened) grid before the token writes land — growing the grid
    without requantizing would silently inflate every prior token on
    dequant. The full-prefill program keeps `requant=False` and skips
    that whole-page traffic (for zeroed pages it would rewrite zeros
    with zeros). The trash page (padded prefill tails) accumulates junk
    between frees, which dequantizes finite and is masked out, same as
    the fp32 contract."""
    monitor.stat_add("STAT_kv_quant_writes")  # traces, not calls
    # whole pages and scale rows come and go in the split form's order,
    # whichever form the pools have
    fused, hd = pages.ndim == 4, (values.shape[1], values.shape[-1])
    if layer is None:
        a = jnp.max(jnp.abs(values), axis=-1) / 127.0        # [L, H, S]
        s_old = _take_scales(scales, None, page_ids, fused)  # [L, H, S]
        scales = _at_scales(scales, None, page_ids, fused).max(
            _pool_order(a, None, fused))                     # dup-safe
        s_tok = _take_scales(scales, None, page_ids, fused)  # [L, H, S]
        if requant:
            # duplicate page ids are safe — s_old/s_tok are per-page,
            # so duplicates compute identical requantized pages and the
            # scatter's last-writer-wins is a no-op
            fdt = values.dtype
            pk = _take_pages(pages, None, page_ids, hd)      # [L,H,S,P,D]
            ratio = jnp.where(
                s_tok > 0, s_old / jnp.where(s_tok > 0, s_tok, 1.0), 1.0)
            pk = jnp.round(pk.astype(fdt) * ratio[..., None, None]) \
                .astype(jnp.int8)
            pages = _put_pages(pages, None, page_ids, pk)
        q = _q8(values, s_tok[..., None])
        return _write_rows(pages, None, page_ids, offsets, q), scales
    B = page_ids.shape[0]
    fdt = values.dtype
    a = jnp.max(jnp.abs(values), axis=-1) / 127.0            # [B, H]
    s_old = _take_scales(scales, layer, page_ids, fused)     # [H, B]
    s_new = jnp.maximum(s_old, a.T)                          # [H, B]
    pk = _take_pages(pages, layer, page_ids, hd)             # [H, B, P, D]
    ratio = jnp.where(s_new > 0,
                      s_old / jnp.where(s_new > 0, s_new, 1.0), 1.0)
    pk = jnp.round(pk.astype(fdt) * ratio[..., None, None]) \
        .astype(jnp.int8)
    q = _q8(values, jnp.moveaxis(s_new, 1, 0)[..., None])    # [B, H, D]
    pk = pk.at[:, jnp.arange(B), offsets, :].set(jnp.moveaxis(q, 0, 1))
    pages = _put_pages(pages, layer, page_ids, pk)
    scales = _at_scales(scales, layer, page_ids, fused).set(
        _pool_order(s_new, layer, fused))                    # [B, H]
    return pages, scales


def paged_kernel_supported(q_shape, pages_shape, table_shape,
                           pages_dtype=jnp.float32) -> bool:
    """Static gate, like `flash_supported`: the shapes the repo's head-pool
    decode kernel (`ops/latent_attention_kernel.head_decode_attention`)
    lowers and compiles for. q [B, H, D]; pages ONE layer [Hkv, N, P, D];
    table [B, PP].

    - floating pages of 2 or 4 bytes, head dim a multiple of 128, a page
      whole sublane tiles (8 rows float32, 16 bfloat16): one copy a page
      moves all the K/V heads' `[Hkv, P, D]` tiles. Head dim 64 (GPT-2)
      is fused rows, the shapes of `paged_row_kernel_supported`.
    - query heads a multiple of K/V heads (grouped query included).
    - the kernel walks a slot's table `head_block_pages` entries at a time
      (derived from the bytes of a page of K plus V and the table's width)
      and asks that the round divide the table.
    - every slot's queries and results, each K/V head's group padded to
      whole sublane tiles, sit in VMEM whole: at most 8 MiB together.
    Every shape admitted here must compile on the chip: the described v5e
    compiles the falcon cell's (`tests/test_v5e_compile.py`), and the chip
    ran the shapes of `tests/test_chip_kernels.py` and the cell's 96 slots
    x 20 query heads over 4 K/V heads of 128, pages of 16, a 96-entry
    table, 3,456 pages a layer (PERF.md PR 37). Widen the rule only with a
    chip run that shows it."""
    from .latent_attention_kernel import head_block_pages, head_query_rows
    B, H, D = q_shape
    Hkv, _, P, Dk = pages_shape
    dtype = jnp.dtype(pages_dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize not in (2, 4):
        return False
    if D != Dk or D % 128 or H % Hkv or P % (32 // dtype.itemsize):
        return False
    if 2 * B * Hkv * head_query_rows(H, Hkv) * D * 4 > 8 << 20:
        return False
    PP = table_shape[1]
    return PP % head_block_pages(P, Hkv, D, dtype.itemsize, PP) == 0


def paged_pool_dense_supported(q_shape, pages_shape, table_shape,
                               pages_dtype=jnp.float32) -> bool:
    """Static gate of the pool-dense path (`paged_pool_attention`), by
    observable shape like `paged_kernel_supported`. q [B, H, D]; pages
    [H, N, P, D]; table [B, PP].

    - floating pools: int8 pools dequantize per page on gather.
    - not a shape of the split pools' kernel (`paged_kernel_supported`),
      which reads pages in place. The fused rows' kernel
      (`paged_row_kernel_supported`) is chosen before this path only
      where a Pallas kernel runs (`paged_attention_path`), so its shapes
      stay pool-dense on every other backend.
    - one K/V head per query head (the reference's own limit).
    - `N <= B*PP`: the pool holds no more pages than the gather would
      materialize for this batch, so pool-dense never reads more than
      the path it replaces. The engine's defaults (512 pages, 8 slots,
      64 entries) sit exactly on the rule; few slots over a large
      prefix-cached pool stay on the gather."""
    B, H, D = q_shape
    Hkv, N, _, Dk = pages_shape
    return (jnp.issubdtype(pages_dtype, jnp.floating)
            and H == Hkv and D == Dk
            and not paged_kernel_supported(q_shape, pages_shape, table_shape,
                                           pages_dtype)
            and N <= B * table_shape[1])


def paged_attention_path(q_shape, pages_shape, table_shape,
                         pages_dtype=jnp.float32, fused=None) -> str:
    """Which implementation `paged_attention` traces for these shapes:
    "kernel", "pool" or "reference" (module docstring). `fused` is the
    pool's form, by default the one `HeadPoolForm` gives the head width:
    it says which kernel's rule applies."""
    if not jnp.issubdtype(pages_dtype, jnp.floating):
        return "reference"    # int8 pools dequantize page by page on gather
    if fused is None:
        fused = head_pools_fused(q_shape[-1])
    kernel_supported = (paged_row_kernel_supported if fused
                        else paged_kernel_supported)
    # lint: allow(flag-in-trace): kernel-vs-reference is a trace-time choice by design (module docstring); the flag picks which program gets built
    if (bool(flag("FLAGS_use_paged_attention")) and _pallas_runs()
            and kernel_supported(q_shape, pages_shape, table_shape,
                                 pages_dtype)):
        return "kernel"
    if paged_pool_dense_supported(q_shape, pages_shape, table_shape,
                                  pages_dtype):
        return "pool"
    return "reference"


def paged_attention(q, k_pages, v_pages, page_table, pos, scale,
                    k_scales=None, v_scales=None, pool_mask=None,
                    kv_heads=None, layer=None):
    """One decode position of attention over a paged KV cache.

    q [B, H, D]; k_pages/v_pages [H, N, P, D] (ONE layer's pool, or
    fused [N, P, row]) — or, with `layer` (a Python int or a scalar), the
    whole pools [L, ...], of which that layer is read; page_table [B, PP]
    int32; pos [B] int32 (last valid position, the token just written).
    Returns [B, H, D]. Grouped queries: the pools hold Hkv < H heads and
    query head i reads K/V head i // (H / Hkv); a split layer says Hkv by
    its shape, a fused layer's row does not, so its caller gives
    `kv_heads`.

    `paged_attention_path` picks the implementation from the shapes: where
    a Pallas kernel runs, shapes `paged_kernel_supported` admits dispatch
    the repo's head-pool kernel and fused layers that
    `paged_row_kernel_supported` admits its fused-row kernel; both read
    the pages in place (with `layer`, in the whole pools: no layer is cut
    out for them); shapes
    `paged_pool_dense_supported` admits score every row against the
    whole pool under `pool_mask` (the `paged_pool_mask` of this table
    and `pos`; a caller with many layers builds it once and passes it,
    else it is built here); every other shape gathers to dense and
    reuses `cached_attention` — the generate-anchored math. The choice
    is a shape rule made before the call, never a fallback from a
    kernel that failed.

    int8 pools pass k_scales/v_scales ([H, N] per-page scales): the
    kernel has no int8+scale-pool input layout, so quantized reads
    always take the dequantizing gather + dense reference (the
    gather materializes only this batch's pages in floating form; the
    pools stay int8 in HBM — on TPU and CPU alike)."""
    H, D = q.shape[1:]
    hd = (kv_heads or H, D)     # the heads a fused row holds
    shape = k_pages.shape if layer is None else k_pages.shape[1:]
    if len(shape) == 3:         # a fused layer [N, P, row]: one K/V head
        layer_shape = hd[:1] + shape[:2] + (D,)     # a query head
    else:                       # unless `kv_heads` says fewer
        layer_shape = shape
    fused = len(shape) == 3
    path = paged_attention_path(q.shape, layer_shape, page_table.shape,
                                k_pages.dtype, fused)
    if path == "kernel":
        monitor.stat_add("STAT_paged_attn_kernel")  # traces, not calls
        from .latent_attention_kernel import (head_decode_attention,
                                              row_decode_attention)
        # (a length is at least 1, a dead slot's too: the kernel starts
        # every slot's first round of copies behind the slot before)
        kernel = row_decode_attention if fused else head_decode_attention
        return kernel(q, k_pages, v_pages, page_table,
                      jnp.maximum(pos + 1, 1), scale, layer=layer)
    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
        if k_scales is not None:
            k_scales, v_scales = k_scales[layer], v_scales[layer]
    if path == "pool":
        monitor.stat_add("STAT_paged_attn_pool")  # traces, not calls
        if pool_mask is None:
            pool_mask = paged_pool_mask(page_table, pos, *layer_shape[1:3])
        return paged_pool_attention(q, k_pages, v_pages, pool_mask, scale)
    monitor.stat_add("STAT_paged_attn_reference")  # traces, not calls
    if k_scales is not None:
        kb = paged_gather_quantized(k_pages, k_scales, page_table, q.dtype,
                                    hd)
        vb = paged_gather_quantized(v_pages, v_scales, page_table, q.dtype,
                                    hd)
    else:
        kb = paged_gather(k_pages, page_table, hd)
        vb = paged_gather(v_pages, page_table, hd)
    if kb.shape[1] != H:
        # grouped queries: query head i reads K/V head i // (H / Hkv)
        kb, vb = (jnp.repeat(x, H // x.shape[1], axis=1) for x in (kb, vb))
    return cached_attention(q, kb, vb, pos, scale)


def sharded_paged_attention(mesh, scale, tp_axis="tp", quantized=False):
    """KV-head-sharded `paged_attention` over a tp mesh (ISSUE 19; the
    SNIPPETS [3] layout): one layer's pools enter
    `P(tp, None, None, None)` — sharded along the heads axis — with
    page table and positions replicated and q sharded on ITS head axis,
    and each shard dispatches `paged_attention` on its local head slice
    (Pallas kernel on TPU, dequantizing gather + dense reference
    elsewhere). GSPMD cannot partition a pallas_call, so the shard_map
    wrapper IS the multi-chip dispatch — without it pjit would gather
    the full pool onto every device.

    Returns a jitted
    `f(q, k_pages, v_pages, page_table, pos)` — or, with
    `quantized=True`,
    `f(q, k_pages, v_pages, k_scales, v_scales, page_table, pos)` —
    yielding [B, H, D] head-sharded like q."""
    from jax.sharding import PartitionSpec as P

    hs = P(None, tp_axis, None)           # q / out [B, H, D]
    pool = P(tp_axis, None, None, None)   # one layer [H, N, Pg, D]
    spool = P(tp_axis, None)              # scale grid [H, N]
    rep = P()
    if quantized:
        def call(q, kp, vp, ks, vs, pt, pos):
            return paged_attention(q, kp, vp, pt, pos, scale,
                                   k_scales=ks, v_scales=vs)
        in_specs = (hs, pool, pool, spool, spool, rep, rep)
    else:
        def call(q, kp, vp, pt, pos):
            return paged_attention(q, kp, vp, pt, pos, scale)
        in_specs = (hs, pool, pool, rep, rep)
    return jax.jit(jax.shard_map(call, mesh=mesh, in_specs=in_specs,
                                 out_specs=hs, check_vma=False))


@jax.named_scope("kv_gather")
def paged_gather_layers(pages, page_table, scales=None,
                        dtype=jnp.float32, heads=None):
    """Materialize ONE sequence's page-table row as a dense view across
    ALL layers at once: pages [L, H, N, P, D] + page_table [PP] →
    [L, H, PP*P, D] (dequantized via per-page `scales` [L, H, N] in the
    int8 mode; a fused pool [L, N, P, row] with `heads` = (H, D) given,
    scales [L, N, H]). One gather from the whole pool instead of a per-layer
    `pages[layer]` slice — slicing the [L, ...] pool per layer copies
    the full layer buffer each time, which dwarfs the tail prefill's
    actual compute; gathering first touches only this row's pages."""
    if pages.ndim == 4:        # fused [L, N, P, row], scales [L, N, H]
        return _gather_rows(pages, page_table, 1, heads, scales, dtype)
    L, H, _, P, D = pages.shape
    PP = page_table.shape[0]
    kb = jnp.take(pages, page_table, axis=2)       # [L, H, PP, P, D]
    if scales is not None:
        sc = jnp.take(scales, page_table, axis=2)  # [L, H, PP]
        kb = kb.astype(dtype) * sc[..., None, None].astype(dtype)
    return kb.reshape(L, H, PP * P, D)


@jax.named_scope("kv_attend")
def paged_prefix_attention(q, kb, vb, k_tail, v_tail, prefix_len, scale):
    """Tail-prefill attention: multi-position queries over a cached
    prefix (pre-gathered from pages) plus the tail's own in-flight K/V.

    q / k_tail / v_tail [B, H, S, D]; kb/vb [B, H, T, D] — ONE layer of
    the `paged_gather_layers` view of the sequence's page-table row;
    prefix_len scalar int32, or [B] int32 for per-row context lengths
    (the speculative verify block, ISSUE 14 — every decode slot carries
    its own cache length) — cached positions t < prefix_len are
    attended, everything at or past it in the gathered view (fresh
    pages, table padding) masks to exact 0.0. Tail position j is
    attended by tail query i iff j <= i (causal within the tail; the
    tail K/V never round-trips through the pages, so the page gather
    stays READ-ONLY — pad tail positions are routed to the scratch page
    by the caller's WRITE, never read here). Returns [B, H, S, D].

    The joint softmax over [prefix ; tail] is the same masked-softmax
    expression as `cached_attention` (-1e30 → exact 0.0), so a tail
    prefill is anchored to the same oracle as the decode step."""
    monitor.stat_add("STAT_paged_attn_reference")  # traces, not calls
    T = kb.shape[2]
    S = q.shape[2]
    sp = jnp.einsum("bhsd,bhtd->bhst", q, kb) * scale
    limit = (prefix_len[:, None, None, None] if jnp.ndim(prefix_len)
             else prefix_len)
    sp = jnp.where(jnp.arange(T)[None, None, None, :] < limit,
                   sp, -1e30)
    st = jnp.einsum("bhsd,bhtd->bhst", q, k_tail) * scale
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    st = jnp.where(causal[None, None], st, -1e30)
    p = jax.nn.softmax(jnp.concatenate([sp, st], axis=-1), axis=-1)
    return (jnp.einsum("bhst,bhtd->bhsd", p[..., :T], vb)
            + jnp.einsum("bhst,bhtd->bhsd", p[..., T:], v_tail))


# -- latent pools (MLA) -----------------------------------------------------
#
# A latent-attention model (models/glm_moe.py) caches ONE row per token and
# layer, with no head axis: `[c_kv after its norm | k_r after RoPE]`,
# `kv_rank + rope` values (576 for GLM-4.7-Flash). The pool is
# `[L, N, P, Rp]`; pages, tables, the scratch page and zero-on-free are the
# head pools'. Decode attends with the up-projection ABSORBED into the
# query, so all the heads of a slot score against the same rows: a slot's
# pages are read once a layer, shared by its heads.
#
# Two implementations, one shape-and-backend rule (`paged_latent_path`):
# the Pallas kernel of `ops/latent_attention_kernel.py`, which walks each
# slot's own pages in place as far as `pos` with the softmax fused, and the
# per-slot gather of the slot's whole table, which is what every other
# backend and shape takes and the plain form the kernel is tested against.
# There is no pool-dense third: `paged_latent_attention`'s docstring, why.
#
# The pool's rows are `latent_pool_width(R)` wide: R rounded up to whole
# 128-lane tiles (640 for 576), the extra lanes zero. With a 576-wide minor
# dimension XLA:TPU lays the pool out with the PAGE axis in the lanes and
# every program then copies the whole pool to row-major on entry and back on
# exit (compiled for the v5e, PR 27: `jit_gen_zero_pages` 1.17 GB of
# temporaries against 0, the decode step two 1.06 GB copies) — the head
# pools' relay of PERF.md PR 24-26. Writes pad the row, the score product
# pads the query, and the values read the first `kv_rank` lanes.


def latent_pool_width(latent_dim: int) -> int:
    """Lanes one cached row takes in the pool: whole 128-lane tiles."""
    return -(-int(latent_dim) // 128) * 128


def _pad_lanes(x, width):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


@jax.named_scope("latent_write")
def paged_latent_write(pool, layer, page_ids, offsets, rows):
    """Scatter cached rows into a latent pool `[L, N, P, R]`. Decode
    (`layer` an int): page_ids/offsets [B], rows [B, R]. Prefill
    (`layer=None`): page_ids/offsets [S], rows [L, S, R]."""
    rows = _pad_lanes(rows.astype(pool.dtype), pool.shape[-1])
    if layer is None:
        # one scatter a layer: a single scatter over the layer axis makes
        # XLA:TPU relay the whole pool to a layers-minor layout and back
        # (compiled for the v5e, PR 27: two 1.17 GB copies a prefill)
        for i in range(pool.shape[0]):
            pool = pool.at[i, page_ids, offsets, :].set(rows[i])
        return pool
    return pool.at[layer, page_ids, offsets, :].set(rows)


def paged_latent_kernel_supported(q_shape, pool_shape, table_shape,
                                  pool_dtype=jnp.bfloat16) -> bool:
    """Static gate of the latent decode kernel
    (`ops/latent_attention_kernel.py`), by observable shape like
    `paged_kernel_supported`. q [B, H, R]; pool ONE layer [N, P, Rp];
    table [B, PP].

    - floating rows of 2 or 4 bytes (bfloat16 as served, float32).
    - a page is whole sublane tiles of its dtype (8 rows of float32, 16 of
      bfloat16): one page is one copy into a `[P, Rp]` VMEM tile.
    - the row whole 128-lane tiles (`latent_pool_width`), the query no
      wider than it.
    - the kernel walks a slot's table `latent_block_pages` entries at a
      time (derived from the page's bytes and the table's width) and asks
      that the block divide the table.
    Every shape admitted here must compile on the chip: compiled for the
    described v5e at the benchmark's shapes in bfloat16 and float32
    (`tests/test_v5e_compile.py`) and run there (PERF.md PR 30). Widen the
    rule only with a chip run that shows it."""
    from .latent_attention_kernel import latent_block_pages
    _, P, Rp = pool_shape
    dtype = jnp.dtype(pool_dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize not in (2, 4):
        return False
    if P % (32 // dtype.itemsize) or Rp % 128 or q_shape[-1] > Rp:
        return False
    PP = table_shape[1]
    return PP % latent_block_pages(P, Rp, dtype.itemsize, PP) == 0


def paged_latent_path(q_shape, pool_shape, table_shape,
                      pool_dtype=jnp.bfloat16) -> str:
    """Which implementation `paged_latent_attention` traces for these
    shapes: "latent_kernel" where a Pallas kernel can run (compiled on a
    TPU backend, or interpreted under FLAGS_flash_attention_interpret: the
    flash kernels' rule, `nn/functional/attention._kernel_runs`) and
    `paged_latent_kernel_supported` admits the shapes, else
    "latent_gather". Made before the call, never a fallback from a kernel
    that failed."""
    if _pallas_runs() and paged_latent_kernel_supported(
            q_shape, pool_shape, table_shape, pool_dtype):
        return "latent_kernel"
    return "latent_gather"


def _pallas_runs() -> bool:
    # lint: allow(flag-in-trace): interpret mode is lowering structure (pallas_ops._interpret); the choice of path is made at trace time by design
    return (bool(flag("FLAGS_flash_attention_interpret"))
            or jax.default_backend() == "tpu")


def paged_latent_attention(q, pool, page_table, pos, scale, kv_rank,
                           layer=None):
    """Absorbed-weight decode attention over ONE layer of a latent pool.

    q [B, H, R]: per head `[q_nope . W_UK | q_rope]`; pool [N, P, Rp],
    Rp = `latent_pool_width(R)` — or, with `layer` (a Python int), the
    whole pool [L, N, P, Rp], of which that layer is read; page_table
    [B, PP]; pos [B]. Returns [B, H, kv_rank] float32: the
    probability-weighted sum of the rows' first `kv_rank` values (the
    caller multiplies by W_UV). Scores and softmax are float32.

    `paged_latent_path` picks the implementation from backend and shapes.
    **latent_kernel**: one Pallas kernel copies each slot's own pages out
    of the pool a block at a time, as far as the page that holds `pos`,
    and carries the softmax across blocks; a page is read once for the
    slot's H heads and both products, and nothing of size `B x PP x P` is
    written. With `layer` it reads the whole pool in place: no layer is cut
    out for it. **latent_gather** (every other backend and shape, and the
    plain form the kernel is tested against): each slot gathers the rows of
    its own table, `[B, PP*P, Rp]`, ONCE for all its heads, whatever it
    holds — on the v5e 7.8 of the decode step's 27.6 ms in the cell that
    serves this family, and 12.0 ms with its products and softmax, for
    0.3 ms of bytes (PERF.md PR 27, PR 30).

    There is no pool-dense third path. Pool-dense under the page-ownership
    mask (the head pools' `paged_pool_attention`) scores every slot's heads
    against the WHOLE pool, B times the products; measured end to end in
    that cell (PERF.md PR 27: 32 slots x 20 heads, 8,192 pages = 32 x 256
    entries) it took 33.7 ms a decode step against the gather's 31.0, so it
    is not built for latent pools.

    Row isolation, on both paths: a non-finite row a slot does not attend
    (another slot's page, the trash page, the rest of its last page) cannot
    reach it, and a slot that attends a non-finite cached row reads NaN —
    its owner trips the engine's flag, nobody else. The gather drops a
    score past `pos` by `where`, multiplies the values as a finite copy and
    marks the slots whose own rows are not finite; the kernel drops a
    position past `pos` by `where` in the scores AND in the values, and
    what a slot does attend reaches it unfiltered."""
    shape = pool.shape if layer is None else pool.shape[1:]
    path = paged_latent_path(q.shape, shape, page_table.shape, pool.dtype)
    R = pool.shape[-1]
    with jax.named_scope("latent_attend"):
        q = _pad_lanes(q, R)
        if path == "latent_kernel":
            monitor.stat_add("STAT_paged_attn_latent_kernel")  # traces
            from .latent_attention_kernel import latent_decode_attention
            # (a length is at least 1, a dead slot's too: the kernel starts
            # every slot's first round of copies behind the slot before)
            return latent_decode_attention(
                q, pool, page_table, jnp.maximum(pos + 1, 1), scale, kv_rank,
                layer=layer)
        monitor.stat_add("STAT_paged_attn_latent")     # traces, not calls
        if layer is not None:
            pool = pool[layer]
        rows = jnp.take(pool, page_table, axis=0)           # [B, PP, P, R]
        rows = rows.reshape(page_table.shape[0], -1, R)
        s = jnp.einsum("bhr,btr->bht", q, rows,
                       preferred_element_type=jnp.float32) * scale
        valid = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]  # [B, T]
        p = jax.nn.softmax(jnp.where(valid[:, None, :], s, -1e30), axis=-1)
        v = rows[..., :kv_rank]
        finite = jnp.isfinite(v)
        out = jnp.einsum("bht,btc->bhc", p.astype(v.dtype),
                         jnp.where(finite, v, 0),
                         preferred_element_type=jnp.float32)
        bad = jnp.any(valid & ~jnp.all(finite, axis=-1), axis=-1)    # [B]
        return jnp.where(bad[:, None, None], jnp.nan, out)


def paged_row_kernel_supported(q_shape, pages_shape, table_shape,
                               pages_dtype=jnp.float32) -> bool:
    """Static gate of the fused-row decode kernel
    (`ops/latent_attention_kernel.row_decode_attention`), by observable
    shape like `paged_kernel_supported`, which takes the split form. q
    [B, H, D]; pages ONE layer as the rules read it, [Hkv, N, P, D]
    (`HeadPoolForm.layer_shape`), of a fused pool `[N, P, row]` whose row
    is the H*D values in whole 128-lane tiles; table [B, PP].

    - fused rows (`head_pools_fused`: a head width that is no whole number
      of lane tiles) of floating values of 2 or 4 bytes, a page whole
      sublane tiles (8 rows float32, 16 bfloat16): one copy a page moves
      a row of every head.
    - one K/V head a query head, and a slot's block-diagonal queries
      (`row_query_rows(H)` x row float32) at most 1 MiB: they and their
      weighted sums live in VMEM through a slot's rounds.
    - every slot's query row and result, padded to a sublane tile in
      float32, sit in VMEM whole: at most 8 MiB together.
    - the kernel walks a slot's table `row_block_pages` entries at a time
      (derived from the bytes of a page of K plus V and the table's width)
      and asks that the round divide the table.
    Every shape admitted here must compile on the chip. What shows it: the
    gpt2-xl cell's decode program (16 slots x 25 heads of 64 in 1,664
    lanes, float32 pages of 16, a 64-entry table, 320 pages) compiles for
    the described v5e (`tests/test_v5e_compile.py`), and
    `tests/test_chip_kernels.py::test_row_rule_admits_only_what_compiles`
    runs the kernel against the gather on the chip at float32 and bfloat16
    pages, pages of 8 and 16, 64- and 96-wide heads, 6 to 64 heads. The
    size of the pool does not enter: the kernel reads it in place. Widen
    the rule only with a chip run that shows it."""
    from .latent_attention_kernel import row_block_pages, row_query_rows
    B, H, D = q_shape
    Hkv, _, P, Dk = pages_shape
    dtype = jnp.dtype(pages_dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize not in (2, 4):
        return False
    if D != Dk or not head_pools_fused(D) or H != Hkv:
        return False
    if P % (32 // dtype.itemsize):
        return False
    row = latent_pool_width(H * D)
    if row_query_rows(H) * row * 4 > 1 << 20 or 2 * B * 8 * row * 4 > 8 << 20:
        return False
    PP = table_shape[1]
    return PP % row_block_pages(P, row, dtype.itemsize, PP) == 0
