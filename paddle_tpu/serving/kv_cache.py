"""Paged KV cache: block allocator + preallocated per-layer K/V pools.

The pools come from a description of what one cached token IS. Head pools
(the constructor, the GPT family): a K and a V row per head, two pools
whose form follows the head width (`ops/paged_ops.HeadPoolForm`, the one
place that knows where the page axis and the head axis are): 64-wide heads
take `[L, N, P, H*D]`, a token's heads side by side in one dense row of
whole 128-lane tiles (so that the device's default layout is row-major);
128-wide heads keep `[L, H, N, P, D]`, which the head-pool kernel reads in
place. With grouped-query attention the pools hold the K/V heads
(`num_kv_heads`), fewer than the query heads that read them. A family with a
fixed-size state per SLOT beside its pages (the hybrid family,
models/falcon_h1.py: a state-space state and a convolution window) names
those pools too (`slot_pools`): they ride after K and V, have no page axis,
and the allocator never touches them. `PagedKVCache.described(shapes, ...)`
(the latent-attention family,
models/glm_moe.py) builds whatever pools the family names by shape and
dtype — ONE pool `[L, N, P, row]` with no head axis. Pages, tables, the
scratch page, refcounts and admission arithmetic are the same for all; the
engine threads `pools`, whatever they are, through its programs, and tells
the cache which layout they took on the device (`note_layout`).

vLLM's PagedAttention memory model on TPU terms: decode-time K/V for
every live sequence lives in ONE pair of preallocated pools, carved into
fixed-size pages handed out by a free-list allocator. A sequence owns `ceil(tokens / page_size)`
pages recorded in a fixed-width page-table row (trash-padded), so the
device-side shapes never depend on how many sequences are live or how
long they are — the prerequisite for the generation engine's single
compiled decode step.

Design points:

- **Page 0 is reserved scratch ("trash")**: inactive decode slots and
  padded prefill tails write there, and page-table padding points there,
  so masked lanes always have a legal physical target. It is never
  allocated.
- **Worst-case admission**: `can_admit(tokens)` is exact page
  arithmetic over the request's prompt + max-new budget; the engine
  refuses admission (keeps the request queued) while free pages are
  short, so a mid-decode sequence can never be starved of the pages it
  was promised — no mid-flight OOM, evictions only on deadline/poison.
- **Zero-on-free**: freed pages are zeroed by the owner engine before
  reuse (`zero_rows` builds the scatter coordinates). Masked attention
  multiplies stale entries by exactly 0.0, which is only safe when
  stale never means NaN/Inf — a poisoned sequence's pages must not
  leak NaNs into the next owner's masked lanes (0.0 * NaN = NaN).
- **Refcounted sharing (prefix cache, ISSUE 12)**: every allocated page
  carries a refcount. `alloc_shared` maps an already-filled prefix
  chain read-only into a new sequence's page table (incref), the
  prefix index itself holds a reference on registered pages
  (`cache_hold`), and `cow_split` swaps one shared page for a private
  copy. Zero-on-free now keys on refcounts, not ownership: `free()`
  returns ONLY the pages whose count hit 0 — a page another sequence
  (or the prefix index) still reads is never zeroed under it. Pages
  held only by the index (`refcount == 1` and cache-held) are
  *evictable*: `can_admit`/`headroom` count them as reclaimable so
  admission capacity stays truthful, and the engine evicts them (LRU,
  via the prefix index) before allocating.
- Host-side state is plain python under the engine's lock; the pools
  themselves are jnp arrays the engine threads through its jitted
  step functions (donated, so XLA updates them in place).
- **int8 page mode** (`dtype="int8"`, FLAGS_kv_cache_dtype): pools
  store int8 with parallel per-(layer, head, page) fp32 scale pools
  (`k_scales`/`v_scales`); `ops/paged_ops.paged_write_quantized`
  quantizes on append, the attention path dequantizes on gather. One
  page costs ~4x fewer HBM bytes than fp32
  (`page_hbm_bytes`/`pages_for_budget` do the arithmetic), so the same
  pool budget admits ~4x the concurrent sequences — the quantized-
  serving capacity multiplier. Zero-on-free covers the scale pools:
  a freed page's scale resets to 0 ("empty") with its content.
"""
from __future__ import annotations

import weakref
from typing import Dict, List, Optional

import numpy as np

from ..framework import monitor
from ..framework.errors import InvalidArgumentError, ResourceExhaustedError

__all__ = ["PagedKVCache"]

TRASH_PAGE = 0

# STAT_kv_cache_hbm_bytes gauges pool bytes across LIVE caches: each
# cache gauge_add()s its pool (+ scale-pool) bytes at construction and
# subtracts them when collected (weakref.finalize — the engine drops
# its cache on GC, there is no explicit close), so a multi-engine
# process exports the aggregate of what actually exists rather than
# whichever pool was built last.
def _note_pool_bytes(delta: int) -> None:
    monitor.stat_gauge_add("STAT_kv_cache_hbm_bytes", delta)


# Per-shard companion gauge (ISSUE 19): on a tp mesh each device holds
# heads/tp of every pool, so the PER-DEVICE HBM cost is total/tp — the
# number admission headroom and capacity planning must use. Only
# tp>1 caches contribute; shard gauges times tp reconcile with the
# aggregate STAT_kv_cache_hbm_bytes for those caches.
def _note_shard_bytes(delta: int) -> None:
    monitor.stat_gauge_add("STAT_tp_kv_shard_bytes", delta)


def _ungauge(gauged) -> None:
    _note_pool_bytes(-gauged[0])
    _note_shard_bytes(-gauged[1])


class PagedKVCache:
    """Block allocator over per-layer paged K/V pools.

    `alloc()`/`free()` are NOT thread-safe — the generation engine calls
    them from its single step thread (same single-writer discipline as
    the PR 3 collector)."""

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 page_size: int, num_pages: int, pages_per_seq: int,
                 dtype="float32", mesh=None, tp_axis: str = "tp",
                 num_kv_heads: Optional[int] = None, slot_pools=()):
        """Head pools: K and V in the form the head width takes
        (`self.form`), plus the two scale pools in the int8 page mode,
        head-sharded on a tp mesh. `described` builds any other pools.

        `num_kv_heads` (grouped-query attention): the heads the pools
        HOLD, fewer than the `num_heads` that read them; every shape, the
        form and the tp split are of the K/V heads. `slot_pools`: (shape,
        dtype) of pools WITHOUT a page axis that ride after K and V — a
        family's fixed-size state per slot, indexed by slot and not by
        page, which the allocator never touches (`kind` "slots" in
        `stats()["pools"]`; one device, float pages)."""
        self._init_pages(page_size, num_pages, pages_per_seq)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_heads if num_kv_heads is None
                                else num_kv_heads)
        if self.num_heads % self.num_kv_heads != 0:
            raise InvalidArgumentError(
                f"num_heads={num_heads} is not a multiple of "
                f"num_kv_heads={num_kv_heads}")
        self.head_dim = int(head_dim)
        self.dtype = str(dtype)
        self.quantized = self.dtype == "int8"
        # mesh-sliced pools (ISSUE 19): on a tp mesh the K/V pools (and
        # the int8 scale grids) are laid out head-sharded with
        # NamedSharding — each device holds heads/tp of every page, so
        # one chip's HBM pays total/tp and the page axis stays FULL on
        # every shard (page ids, tables and the allocator are
        # tp-invariant)
        self.mesh = mesh
        self.tp_axis = str(tp_axis)
        self.tp = int(mesh.shape[tp_axis]) if mesh is not None else 1
        if self.num_kv_heads % self.tp != 0:
            raise InvalidArgumentError(
                f"num_heads={self.num_kv_heads} not divisible by "
                f"tp={self.tp} — head-sharded pools need equal slices")
        import jax.numpy as jnp

        from ..ops.paged_ops import HeadPoolForm
        # the shape rule (ops/paged_ops.head_pools_fused): no flag
        self.form = HeadPoolForm(self.num_kv_heads, self.head_dim, self.tp)
        shape = self.form.pool_shape(self.num_layers, self.num_pages,
                                     self.page_size)
        self.k_pages = self._place(jnp.zeros(shape, self.dtype))
        self.v_pages = self._place(jnp.zeros(shape, self.dtype))
        # int8 page mode: per-(layer, head, page) symmetric abs-max
        # scales in a parallel pool (dequant = q * scale; scale 0 means
        # "page empty" — zero-on-free resets both pools, so a freed
        # page's next owner starts from a clean quantization grid)
        if self.quantized:
            sshape = self.form.scale_shape(self.num_layers, self.num_pages)
            self.k_scales = self._place(jnp.zeros(sshape, "float32"))
            self.v_scales = self._place(jnp.zeros(sshape, "float32"))
            self.pools = (self.k_pages, self.v_pages, self.k_scales,
                          self.v_scales)
        else:
            self.k_scales = self.v_scales = None
            self.pools = (self.k_pages, self.v_pages)
        if slot_pools:
            if self.quantized or mesh is not None:
                raise InvalidArgumentError(
                    "slot pools ride float pages on one device")
            self._slot_pools = len(slot_pools)
            self.pools += tuple(jnp.zeros(tuple(shape), dt)
                                for shape, dt in slot_pools)
        self._note_pools()

    @classmethod
    def described(cls, pool_shapes, page_size: int, num_pages: int,
                  pages_per_seq: int):
        """The allocator over pools the FAMILY describes: `pool_shapes`
        is a sequence of (shape, dtype), one per pool, in the order the
        family's programs take and return them; each holds `num_pages`
        pages somewhere in its shape, which only the family's programs
        know. One device, no head axis to shard (the latent family's one
        pool `[L, N, P, row]`)."""
        import jax.numpy as jnp
        self = cls.__new__(cls)
        self._init_pages(page_size, num_pages, pages_per_seq)
        self.pools = tuple(jnp.zeros(tuple(shape), dtype)
                           for shape, dtype in pool_shapes)
        self.dtype = str(self.pools[0].dtype)
        self.quantized = False
        self.mesh, self.tp = None, 1
        self.form = None            # no head axis: the family's own rows
        self._note_pools()
        return self

    def _init_pages(self, page_size, num_pages, pages_per_seq):
        if page_size < 1 or num_pages < 2 or pages_per_seq < 1:
            raise InvalidArgumentError(
                f"PagedKVCache needs page_size>=1, num_pages>=2 (page 0 "
                f"is reserved scratch), pages_per_seq>=1; got "
                f"{page_size}/{num_pages}/{pages_per_seq}")
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.pages_per_seq = int(pages_per_seq)
        # LIFO free list: the page freed last is reallocated first, so a
        # hot pool keeps touching the same HBM region
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._owned: Dict[int, List[int]] = {}  # seq id -> pages
        self._ref: Dict[int, int] = {}          # page -> refcount
        # pages the prefix index holds a reference on (cache_hold);
        # evictable = cache-held AND refcount 1 (no live sequence reads)
        self._cache_held: set = set()
        # free-list watermarks since construction: the low-water mark is
        # "how close did this pool ever get to exhaustion" — the
        # capacity-planning number /stats surfaces (ISSUE 11)
        self._free_low_water = len(self._free)
        self._free_high_water = len(self._free)
        self._slot_pools = 0        # trailing pools with no page axis
        monitor.stat_set("STAT_kv_pages_inuse", 0)

    def _kind(self, i: int) -> str:
        """`pages` for a pool the allocator carves, `slots` for one a
        family indexes by slot (the trailing `slot_pools`)."""
        return ("slots" if i >= len(self.pools) - self._slot_pools
                else "pages")

    def _note_pools(self):
        # what ONE page's share of the host tier is sized from: the pools
        # that have pages
        self._pool_bytes = sum(int(p.nbytes) for i, p in
                               enumerate(self.pools)
                               if self._kind(i) == "pages")
        # until the engine says otherwise (`note_layout`) the pools lie
        # as allocated: the default layout, device bytes = logical bytes
        self._pool_info = [self._describe(i, p, "default", int(p.nbytes))
                           for i, p in enumerate(self.pools)]
        # the gauges count DEVICE bytes; one mutable cell so the
        # finalizer takes back whatever `note_layout` made of them
        self._gauged = [0, 0]
        weakref.finalize(self, _ungauge, self._gauged)
        self._gauge()

    def _describe(self, i, pool, layout, device_bytes) -> dict:
        """One entry of `stats()["pools"]`."""
        kind = self._kind(i)
        return {"shape": list(pool.shape), "dtype": str(pool.dtype),
                "kind": kind, "layout": layout,
                "device_bytes": device_bytes,
                "logical_bytes": self._logical_bytes(pool, kind)}

    def _logical_bytes(self, pool, kind="pages") -> int:
        """The bytes of what a pool stores: a fused head pool's rows are
        whole lane tiles, of which the heads fill `form.used` lanes."""
        form = self.form
        if (form is not None and form.fused and pool.ndim == 4
                and kind == "pages"):
            return int(pool.nbytes) * form.used // form.row
        return int(pool.nbytes)

    def _gauge(self):
        b = self.hbm_bytes()
        _note_pool_bytes(b - self._gauged[0])
        self._gauged[0] = b
        if self.tp > 1:
            s = self.shard_hbm_bytes()
            _note_shard_bytes(s - self._gauged[1])
            self._gauged[1] = s

    def note_layout(self, pools):
        """The engine laid the pools out as its decode program was
        compiled to take them: record, per pool, the layout the device
        holds (`default`, or `major_to_minor` + tiling as the compiler
        reported it) and the bytes it takes there, and let the gauges
        count those. Nothing else in the cache depends on the layout."""
        from ..device import array_layout
        self._pool_info = [self._describe(i, p, *array_layout(p))
                           for i, p in enumerate(pools)]
        self._gauge()

    def _place(self, arr):
        """Lay one pool onto the tp mesh head-sharded; a mesh-less cache
        keeps the single-device default placement."""
        if self.mesh is None:
            return arr
        import jax
        from jax.sharding import NamedSharding
        return jax.device_put(arr, NamedSharding(
            self.mesh, self.form.spec(arr.ndim, self.tp_axis)))

    # -- capacity arithmetic ----------------------------------------------

    @staticmethod
    def page_hbm_bytes(num_layers: int, num_heads: int, head_dim: int,
                       page_size: int, dtype="float32", tp: int = 1) -> int:
        """Device bytes ONE page costs across both pools (K and V, every
        layer), including its slice of the int8 scale pools — the unit
        of the capacity arithmetic below. With `tp > 1` this is the
        PER-SHARD cost (each device stores heads/tp of the page), the
        number a per-chip HBM budget actually pays — router pressure
        and `pages_for_budget` must size against the shard, not the
        unsharded fiction."""
        tp = int(tp)
        if tp < 1 or num_heads % tp != 0:
            raise InvalidArgumentError(
                f"num_heads={num_heads} not divisible by tp={tp}")
        from ..ops.paged_ops import HeadPoolForm
        item = np.dtype(dtype).itemsize
        # the lanes a token takes in a pool: the form's (a fused row is
        # whole 128-lane tiles a shard)
        row = HeadPoolForm(num_heads, head_dim, tp).row // tp
        b = 2 * num_layers * page_size * row * item
        if str(dtype) == "int8":
            b += 2 * num_layers * (num_heads // tp) * 4  # fp32 scale per (L, H/tp)
        return b

    def page_host_bytes(self) -> int:
        """Host-RAM bytes ONE page costs demoted into the kv_tier
        store: the raw K/V page blocks in the pool dtype plus (int8
        mode) the fp32 scale rows — identical arithmetic to
        `page_hbm_bytes`, because the tier stores the bytes RAW (no
        transcoding; that is the cross-tier exactness guarantee). The
        tier byte-budget / working-set sizing unit (ISSUE 18). Always
        the FULL (unsharded) page: the tier gather reassembles every
        head shard into one host block, so host RAM pays tp-invariant
        bytes per page."""
        return self._pool_bytes // self.num_pages

    @classmethod
    def pages_for_budget(cls, budget_bytes: int, *, num_layers: int,
                         num_heads: int, head_dim: int, page_size: int,
                         dtype="float32", tp: int = 1) -> int:
        """Most pages (incl. the reserved scratch page) an HBM budget
        admits: int8 pages are ~4x denser than fp32 — the serving-
        capacity multiplier the quantized KV mode exists for, and how
        the tests build equal-byte fp32/int8 pools. `budget_bytes` is
        PER-CHIP HBM; with tp > 1 each chip stores only heads/tp of
        every page, so the same per-chip budget admits tp× the pages —
        the mesh-slice capacity unlock (ISSUE 19)."""
        per = cls.page_hbm_bytes(num_layers, num_heads, head_dim,
                                 page_size, dtype, tp=tp)
        return max(2, int(budget_bytes) // per)

    def hbm_bytes(self) -> int:
        """Live device bytes of the K/V pools + scale pools (summed
        across every shard on a tp mesh), in the layout the device holds
        them: a padded tiling counts."""
        return sum(i["device_bytes"] for i in self._pool_info)

    def shard_hbm_bytes(self) -> int:
        """Per-device pool bytes: heads shard evenly over tp, so ONE
        chip's HBM holds exactly total/tp — the gauge admission headroom
        reasons about (shards × tp reconcile to `hbm_bytes`)."""
        return self.hbm_bytes() // self.tp

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1  # minus the trash page

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.usable_pages - len(self._free)

    @property
    def max_tokens_per_seq(self) -> int:
        return self.pages_per_seq * self.page_size

    def pages_needed(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_size)  # ceil

    def fits(self, tokens: int) -> bool:
        """Could `tokens` EVER be admitted (table width + pool size)?"""
        need = self.pages_needed(tokens)
        return need <= self.pages_per_seq and need <= self.usable_pages

    @property
    def evictable_pages(self) -> int:
        """Pages the prefix index alone holds (refcount 1, cache-held):
        reclaimable on demand by an LRU eviction before alloc."""
        return sum(1 for p in list(self._cache_held)
                   if self._ref.get(p) == 1)

    @property
    def reclaimable_pages(self) -> int:
        """Free-list pages plus evictable cached pages — the honest
        admission capacity (ISSUE 12: cached-but-evictable counts as
        free, with the eviction performed before alloc)."""
        return len(self._free) + self.evictable_pages

    def can_admit(self, tokens: int) -> bool:
        """Admission check: worst-case pages available RIGHT NOW (free
        list + evictable cached pages — the caller evicts before
        alloc)."""
        need = self.pages_needed(tokens)
        return need <= self.pages_per_seq and need <= self.reclaimable_pages

    # -- alloc / free ------------------------------------------------------

    def alloc(self, seq_id: int, tokens: int) -> np.ndarray:
        """Reserve worst-case pages for `tokens`; returns the sequence's
        fixed-width page-table row (trash-padded int32 [pages_per_seq]).
        Raises ResourceExhaustedError when the pool is short — callers
        gate on `can_admit` so this raising means an accounting bug."""
        if seq_id in self._owned:
            raise InvalidArgumentError(
                f"sequence {seq_id} already holds pages")
        need = self.pages_needed(tokens)
        if need > self.pages_per_seq:
            raise InvalidArgumentError(
                f"{tokens} tokens need {need} pages > pages_per_seq="
                f"{self.pages_per_seq} (page_size={self.page_size})")
        if need > len(self._free):
            raise ResourceExhaustedError(
                f"KV page pool exhausted: need {need} pages, "
                f"{len(self._free)} free of {self.usable_pages}")
        pages = [self._free.pop() for _ in range(need)]
        for p in pages:
            self._ref[p] = 1
        self._owned[seq_id] = pages
        self._free_low_water = min(self._free_low_water, len(self._free))
        monitor.stat_set("STAT_kv_pages_inuse", self.pages_in_use)
        row = np.full((self.pages_per_seq,), TRASH_PAGE, np.int32)
        row[:need] = pages
        return row

    def alloc_shared(self, seq_id: int, tokens: int,
                     shared_pages: List[int]) -> np.ndarray:
        """Like `alloc`, but the leading pages of the page-table row map
        an already-filled prefix chain READ-ONLY (each shared page's
        refcount is incremented; the sequence never writes them — its
        first write position sits past the shared prefix, or behind a
        `cow_split`). Only the tail pages come off the free list."""
        if seq_id in self._owned:
            raise InvalidArgumentError(
                f"sequence {seq_id} already holds pages")
        need = self.pages_needed(tokens)
        fresh = need - len(shared_pages)
        if fresh < 0 or need > self.pages_per_seq:
            raise InvalidArgumentError(
                f"{tokens} tokens need {need} pages "
                f"(pages_per_seq={self.pages_per_seq}, "
                f"{len(shared_pages)} shared)")
        for p in shared_pages:
            if self._ref.get(p, 0) < 1:
                raise InvalidArgumentError(
                    f"shared page {p} is not allocated")
        if fresh > len(self._free):
            raise ResourceExhaustedError(
                f"KV page pool exhausted: need {fresh} fresh pages, "
                f"{len(self._free)} free of {self.usable_pages}")
        for p in shared_pages:
            self._ref[p] += 1
        pages = [self._free.pop() for _ in range(fresh)]
        for p in pages:
            self._ref[p] = 1
        self._owned[seq_id] = list(shared_pages) + pages
        self._free_low_water = min(self._free_low_water, len(self._free))
        monitor.stat_set("STAT_kv_pages_inuse", self.pages_in_use)
        row = np.full((self.pages_per_seq,), TRASH_PAGE, np.int32)
        row[:need] = self._owned[seq_id]
        return row

    def _decref(self, page: int) -> bool:
        """Drop one reference; True when the page actually returned to
        the free list (refcount hit 0) — zero-on-free applies to
        exactly these pages and DEFERS while any sharer remains."""
        n = self._ref.get(page, 0) - 1
        if n > 0:
            self._ref[page] = n
            return False
        self._ref.pop(page, None)
        self._cache_held.discard(page)
        self._free.append(page)
        return True

    def free(self, seq_id: int) -> List[int]:
        """Release a sequence's references; returns ONLY the pages whose
        refcount hit 0 (the engine zeroes those on device before reuse
        — pages another sequence or the prefix index still reads are
        NOT returned and must not be zeroed). Idempotent — a double
        free (evict racing natural EOS) is a no-op."""
        pages = self._owned.pop(seq_id, [])
        freed = [p for p in pages if self._decref(p)]
        self._free_high_water = max(self._free_high_water,
                                    len(self._free))
        monitor.stat_set("STAT_kv_pages_inuse", self.pages_in_use)
        return freed

    # -- prefix-cache references (ISSUE 12) --------------------------------

    def pin(self, pages: List[int]) -> None:
        """Temporarily incref pages (an admission holding its matched
        chain across an eviction pass); pair with `unpin`."""
        for p in pages:
            if self._ref.get(p, 0) < 1:
                raise InvalidArgumentError(f"page {p} is not allocated")
            self._ref[p] += 1

    def unpin(self, pages: List[int]) -> List[int]:
        """Drop a `pin`; returns any pages freed (refcount hit 0)."""
        freed = [p for p in pages if self._decref(p)]
        if freed:
            self._free_high_water = max(self._free_high_water,
                                        len(self._free))
            monitor.stat_set("STAT_kv_pages_inuse", self.pages_in_use)
        return freed

    def cache_hold(self, pages: List[int]) -> None:
        """The prefix index takes a reference on registered chain pages:
        they survive their producer sequence's free (content preserved
        for future hits) and become evictable once no live sequence
        shares them."""
        self.pin(pages)
        self._cache_held.update(pages)

    def cache_release(self, pages: List[int]) -> List[int]:
        """Drop the prefix index's reference (chain eviction); returns
        the pages freed NOW (refcount 0 → caller zeroes them). Pages a
        live sequence still shares stay allocated and zero later, when
        that sequence frees."""
        for p in pages:
            self._cache_held.discard(p)
        return self.unpin(pages)

    def cow_split(self, seq_id: int, old_page: int) -> int:
        """Copy-on-write split: swap one SHARED page in `seq_id`'s
        ownership for a fresh private page (the caller copies content —
        and the int8 scale row — on device, then writes through the
        private copy). Returns the new page id; the shared original
        keeps its other readers."""
        pages = self._owned.get(seq_id)
        if pages is None or old_page not in pages:
            raise InvalidArgumentError(
                f"sequence {seq_id} does not hold page {old_page}")
        if self._ref.get(old_page, 0) < 2:
            raise InvalidArgumentError(
                f"page {old_page} is not shared (refcount "
                f"{self._ref.get(old_page, 0)}); split is pointless")
        if not self._free:
            raise ResourceExhaustedError(
                "KV page pool exhausted: no free page for CoW split")
        new = self._free.pop()
        self._ref[new] = 1
        self._ref[old_page] -= 1
        pages[pages.index(old_page)] = new
        self._free_low_water = min(self._free_low_water, len(self._free))
        monitor.stat_set("STAT_kv_pages_inuse", self.pages_in_use)
        return new

    def refcounts(self) -> Dict[int, int]:
        """{page: refcount} snapshot (per-key atomic gets, same scraper
        contract as owners())."""
        out = {}
        for p in list(self._ref):
            n = self._ref.get(p)
            if n is not None:
                out[p] = n
        return out

    def cached_pages(self) -> List[int]:
        """Pages the prefix index currently holds (snapshot)."""
        return list(self._cache_held)

    def owned(self, seq_id: int) -> Optional[List[int]]:
        pages = self._owned.get(seq_id)
        return list(pages) if pages is not None else None

    def owners(self) -> Dict[int, List[int]]:
        """Page-ownership map `{seq_id: [page, ...]}` — which physical
        pages each live sequence holds (KV-pool introspection; the
        engine joins it against its slot table for `stats()["kv"]`).

        Read from scraper threads while the step thread allocs/frees:
        iterate a key snapshot + per-key atomic gets (each a single
        GIL-atomic dict op) instead of `.items()`, which would raise
        `dictionary changed size during iteration` mid-scrape. A page
        list never changes SIZE after alloc (cow_split swaps one item
        in place, a GIL-atomic store), so copying it is safe."""
        out = {}
        for sid in list(self._owned):
            pages = self._owned.get(sid)
            if pages is not None:
                out[sid] = list(pages)
        return out

    def headroom(self, token_counts) -> Dict[int, int]:
        """Admission-headroom estimate: for each representative request
        size (total tokens = prompt + max_new), how many MORE such
        requests `can_admit` would accept RIGHT NOW from the free list
        plus the evictable cached pages (0 when the shape can never fit
        the page table) — evictable pages ARE admission capacity (the
        engine evicts before alloc), so the router-pressure surface
        must not under-report them (ISSUE 12). The router tier
        compares this across replicas to place work."""
        out = {}
        free = self.reclaimable_pages
        for tokens in token_counts:
            need = self.pages_needed(tokens)
            if need > self.pages_per_seq or need <= 0:
                out[int(tokens)] = 0
            else:
                out[int(tokens)] = free // need
        return out

    def zero_rows(self, pages: List[int]) -> np.ndarray:
        """Fixed-width page-id row for the engine's jitted zeroing
        scatter (trash-padded so one compiled shape serves every free)."""
        row = np.full((self.pages_per_seq,), TRASH_PAGE, np.int32)
        row[:len(pages)] = pages[:self.pages_per_seq]
        return row

    def stats(self) -> dict:
        return {
            "dtype": self.dtype,
            "quantized": self.quantized,
            # per pool: logical shape, the layout the device holds it in,
            # device and logical bytes (`note_layout`)
            "pools": [dict(i) for i in self._pool_info],
            # the head pools' form by the head-width rule (None: the
            # family described its own pools)
            "pool_form": self.form.name if self.form is not None else None,
            "hbm_bytes": self.hbm_bytes(),
            # mesh-slice lanes (ISSUE 19): per-device pool bytes — what
            # ONE chip's HBM actually pays (== hbm_bytes when tp == 1)
            "tp": self.tp,
            "shard_hbm_bytes": self.shard_hbm_bytes(),
            "page_size": self.page_size,
            "usable_pages": self.usable_pages,
            "pages_in_use": self.pages_in_use,
            "free_pages": self.free_pages,
            "pages_per_seq": self.pages_per_seq,
            "sequences": len(self._owned),
            "occupancy": round(self.pages_in_use
                               / max(1, self.usable_pages), 4),
            "free_low_water": self._free_low_water,
            "free_high_water": self._free_high_water,
            # prefix-cache occupancy (ISSUE 12): cached = held by the
            # prefix index at all; evictable = held ONLY by it —
            # reclaimable is the truthful admission capacity
            "cached_pages": len(self._cache_held),
            "evictable_pages": self.evictable_pages,
            "reclaimable_pages": self.reclaimable_pages,
        }

    def manifest(self) -> dict:
        """Crash-manifest snapshot (ISSUE 15): pool stats plus the
        ownership and refcount maps, captured at engine death so the
        flight dump records exactly which sequences held which pages
        when the pools were lost — the rebuilt engine starts from a
        FRESH pool, so this is the only record of the dead layout."""
        return {"stats": self.stats(), "owners": self.owners(),
                "refcounts": self.refcounts()}
