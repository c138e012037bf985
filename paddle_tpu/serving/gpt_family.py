"""The GPT decode family: what `serving.GenerationEngine` asks a
`models.GPTForCausalLM` for (`serving/decode_family.py`).

A cached token is one K and one V row per head and layer: two head pools in
the form the head width takes (`ops/paged_ops.HeadPoolForm`: `[L, N, P, H*D]`, a
row in whole lane tiles, for GPT-2's 64-wide heads, `[L, H, N, P, D]` for 128-wide ones; int8 pages add
two scale pools), `paged_attention` over them by its shape rules, learned
positions (so the largest position is the table's length). Nothing below
writes a page or a head index out: whole pages, the tp specs and the shape
rules' view of a layer all come from the form. Every option of the engine is built for this family: the
prefix cache's tail prefill and copy-on-write, speculative verify, the host
tier, int8 pages, tensor parallelism.

The program bodies below are the engine's own of PRs 8-26, moved here whole
when the engine learned a second family (PR 27): the compiled programs are
the same.
"""
from __future__ import annotations

import numpy as np

from ..framework.errors import InvalidArgumentError
from .decode_family import sample_next
from .kv_cache import TRASH_PAGE, PagedKVCache

__all__ = ["GPTFamily"]


class GPTFamily:
    name = "gpt"
    step_counters = ()     # the decode program returns no counters

    def __init__(self, model):
        cfg = model.gpt.config
        self._model = model
        self.config = cfg
        self.num_layers = cfg.num_layers
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.scale = 1.0 / self.head_dim ** 0.5
        # learned positions: the table's length
        self.max_position = cfg.max_position_embeddings

    def weights(self):
        # raises for a GPT with `use_moe` (models/gpt.py)
        return self._model.decode_weights()

    def dtype(self, W):
        return np.asarray(W["lnf"][0]).dtype

    def check(self, cfg, tp):
        """Refuse, by name, what this family cannot serve."""
        if self.num_heads % tp != 0:
            raise InvalidArgumentError(
                f"num_heads={self.num_heads} not divisible by tp={tp} — "
                f"head-sharded lanes need equal slices")

    def shard_weights(self, W, mesh):
        from ..models.gpt import shard_decode_weights
        return shard_decode_weights(W, mesh)

    def make_cache(self, cfg, kv_dtype, mesh):
        return PagedKVCache(
            self.num_layers, self.num_heads, self.head_dim, cfg.page_size,
            cfg.num_pages, cfg.pages_per_seq, dtype=kv_dtype, mesh=mesh)

    def decode_attention(self, cfg, tp, pools):
        """Which of ops/paged_ops.py's three implementations the decode
        program's attention takes — a shape rule, so it is known before
        (and whether or not) anything is traced; under a tp mesh the rule
        sees the per-shard head count."""
        from ..ops.paged_ops import HeadPoolForm, paged_attention_path
        kp, H = pools[0], self.num_heads // tp
        return paged_attention_path(
            (cfg.max_slots, H, self.head_dim),
            HeadPoolForm(H, self.head_dim).layer_shape(kp.shape),
            (cfg.max_slots, cfg.pages_per_seq), kp.dtype)

    def build(self, ctx):
        """The family's program bodies (python callables; the engine jits,
        donates and names them) under their fixed names."""
        import jax
        import jax.numpy as jnp

        from ..models.gpt import (gpt_decode_step, gpt_logits,
                                  gpt_prefill, gpt_prefill_extend,
                                  gpt_spec_verify)
        from ..ops.paged_ops import (HeadPoolForm, page_rows_for_positions,
                                     paged_attention, paged_gather,
                                     paged_gather_layers,
                                     paged_gather_quantized,
                                     paged_pool_mask,
                                     paged_prefix_attention, paged_write,
                                     paged_write_quantized)

        tp, mesh = ctx.tp, ctx.mesh
        # mesh-slice lane (ISSUE 19): under shard_map every closure sees
        # PER-SHARD tensors, so H is the LOCAL head count (head_dim —
        # and with it `scale` — is untouched by head sharding) and
        # `psum` is the once-per-block partial-sum reduction the
        # row-parallel projections apply before their replicated bias
        H = self.num_heads // tp
        # where the page axis and the head axis of these pools are
        form = HeadPoolForm(H, self.head_dim)
        hd = (H, self.head_dim)
        P, scale = ctx.cfg.page_size, self.scale
        psum = (lambda x: jax.lax.psum(x, "tp")) if tp > 1 else None
        top_k = ctx.cfg.top_k
        quant = ctx.quant
        # pools per program signature: (kp, vp) or (kp, vp, ks, vs) —
        # the int8 mode's scale pools ride (and are donated) alongside
        # the pages so quantize-on-append updates both in place
        NP = ctx.npool
        max_position = self.max_position

        # the programs' names are fixed on purpose (gen_prefill,
        # gen_prefill_tail, gen_decode, gen_verify, gen_zero_pages,
        # gen_cow_copy, gen_tier_gather, gen_tier_write): a profiler
        # trace's `XLA Modules` line reads `jit_gen_decode(...)`, and
        # tools/trace_report.py sums device time per program by them

        note = ctx.note

        def write_pages(pools, layer, page_ids, offs, k, v,
                        requant=False):
            # requant=True only in the tail program: a CoW split page
            # arrives with content + scale, every other prefill target
            # is freshly zeroed (trace-time switch — the full-prefill
            # program carries no whole-page requant traffic)
            if quant:
                kp, vp, ksc, vsc = pools
                kp, ksc = paged_write_quantized(kp, ksc, layer, page_ids,
                                                offs, k, requant=requant)
                vp, vsc = paged_write_quantized(vp, vsc, layer, page_ids,
                                                offs, v, requant=requant)
                return (kp, vp, ksc, vsc)
            kp, vp = pools
            # a forced narrower page dtype (kv_cache_dtype="bfloat16"
            # under an fp32 model) is a deliberate storage downcast
            return (paged_write(kp, layer, page_ids, offs,
                                k.astype(kp.dtype)),
                    paged_write(vp, layer, page_ids, offs,
                                v.astype(vp.dtype)))

        def gen_prefill(W, *rest):
            pools, (pt_row, ids, length) = rest[:NP], rest[NP:]
            note(f"prefill[b={ids.shape[1]}]")
            h, ks, vs = gpt_prefill(W, ids, num_heads=H, scale=scale,
                                    reduce=psum)
            S_b = ids.shape[1]
            pos = jnp.arange(S_b)
            page_ids, offs = page_rows_for_positions(pt_row, pos, P)
            # bucket-pad tail positions (pos >= length) write to the
            # reserved scratch page, never the sequence's own pages —
            # the documented contract, and load-bearing in the int8
            # mode: the scatter-max page scales must not bake pad-token
            # K/V magnitudes into a real page's quantization grid (the
            # grid only ever widens, so the pollution would be
            # permanent; fp32 merely overwrites the junk later)
            valid = pos < length
            page_ids = jnp.where(valid, page_ids, TRASH_PAGE)
            offs = jnp.where(valid, offs, 0)
            pools = write_pages(pools, None, page_ids, offs,
                                ks[:, 0], vs[:, 0])
            idx = jnp.clip(length - 1, 0, S_b - 1)
            return (*pools, gpt_logits(W, h[0, idx]))

        def gen_prefill_tail(W, *rest):
            """Prefix-hit prefill: only the prompt TAIL runs the model —
            queries attend the cached prefix pages READ-ONLY plus their
            own in-flight K/V, and the writes land in the tail's pages
            (bucket-pad positions routed to the scratch page, exactly
            the full-prefill contract — a shared page never receives a
            pad write). One compiled program per tail bucket."""
            pools = rest[:NP]
            pt_row, ids, length, offset = rest[NP:]
            note(f"prefill_tail[b={ids.shape[1]}]")
            S_b = ids.shape[1]
            ar = jnp.arange(S_b)
            valid = ar < length
            # pad positions clamp to 0 so neither the wpe gather nor the
            # page-index arithmetic ever reads out of range; their
            # writes go to the scratch page below regardless
            positions = jnp.where(valid, offset + ar, 0)
            # gather the sequence's cached pages ONCE across all layers
            # (dequantizing in the int8 mode) — per-layer pool slices
            # would copy the whole layer buffer per layer, costing more
            # than the tail's compute
            if quant:
                kp, vp, ksc, vsc = pools
                kb_all = paged_gather_layers(kp, pt_row, ksc, heads=hd)
                vb_all = paged_gather_layers(vp, pt_row, vsc, heads=hd)
            else:
                kp, vp = pools
                kb_all = paged_gather_layers(kp, pt_row, heads=hd)
                vb_all = paged_gather_layers(vp, pt_row, heads=hd)

            def ctx_attend(layer, q, k, v):
                return paged_prefix_attention(
                    q, kb_all[layer][None], vb_all[layer][None],
                    k, v, offset, scale)

            h, ks, vs = gpt_prefill_extend(W, ids, positions, ctx_attend,
                                           num_heads=H, scale=scale,
                                           reduce=psum)
            page_ids, offs = page_rows_for_positions(pt_row, positions, P)
            page_ids = jnp.where(valid, page_ids, TRASH_PAGE)
            offs = jnp.where(valid, offs, 0)
            pools = write_pages(pools, None, page_ids, offs,
                                ks[:, 0], vs[:, 0], requant=True)
            idx = jnp.clip(length - 1, 0, S_b - 1)
            return (*pools, gpt_logits(W, h[0, idx]))

        def gen_cow_copy(*rest):
            """Copy-on-write page split: clone one page's content across
            every layer/head from `src` to `dst` — including the
            per-(layer, head, page) scale rows in the int8 mode, so the
            private copy dequantizes identically to the shared
            original."""
            pools = rest[:NP]
            src, dst = rest[NP], rest[NP + 1]
            note("cow_copy")
            return tuple(form.at_pages(p, dst).set(form.pages(p, src))
                         for p in pools)

        # the decode cache threaded through gpt_decode_step's hooks:
        # (pools, page table, pool-dense ownership mask or None)
        def write_kv(cache, layer, k, v, pos):
            pools, pt, mask = cache
            page_ids, offs = page_rows_for_positions(pt, pos, P)
            return (write_pages(pools, layer, page_ids, offs, k, v), pt,
                    mask)

        def attend(cache, layer, q, pos):
            # the whole pools and the layer: the kernel reads that layer in
            # place, the other paths cut it out themselves
            pools, pt, mask = cache
            return paged_attention(q, pools[0], pools[1], pt, pos, scale,
                                   *pools[2:], pool_mask=mask, layer=layer)

        pool_dense = ctx.decode_attention == "pool"

        def gen_decode(W, *rest):
            pools = rest[:NP]
            pt, tok, pos, active, temps, smask, key = rest[NP:]
            note(f"decode[m={tok.shape[0]}]")
            # pool-dense attention: the mask depends on the table and
            # `pos` alone, so every layer of the step shares this one
            mask = (paged_pool_mask(pt, pos,
                                    pools[0].shape[form.page_axis], P)
                    if pool_dense else None)
            logits, (pools, _, _) = gpt_decode_step(
                W, tok, pos, (pools, pt, mask), write_kv, attend,
                num_heads=H, scale=scale, reduce=psum)
            return (*pools, *sample_next(logits, active, temps, smask, key,
                                         top_k))

        def gen_verify(W, *rest):
            """Speculative verify step (ISSUE 14): score every live
            slot's [current token + k drafts] block — k+1 positions —
            in ONE pass over the paged cache (`gpt_spec_verify` on the
            `_gen_block_pass` seam), accept the longest greedily-
            agreeing draft prefix IN-GRAPH, and commit only the
            consumed positions' K/V: rejected draft lanes, inactive
            slots and clamped pad positions all scrub to the reserved
            scratch page. That routing IS the rollback — a rejected
            draft never dirties a real page, so the int8 scale grids
            never widen from a token that was not kept and the PR 12
            CoW/sharing invariants hold untouched (writes always land
            past any shared prefix). Block queries attend the cached
            pages READ-ONLY (per-slot prefix length = the slot's cache
            position) plus the block's own in-flight K/V — the
            `paged_prefix_attention` oracle, so greedy output is
            token-identical to the plain decode program. Returns
            (*pools, n_accepted [M], next_token [M], bad [M])."""
            pools = rest[:NP]
            pt, toks_blk, dmask, pos0, active, temps, smask, key = \
                rest[NP:]
            note(f"verify[k={toks_blk.shape[1] - 1}]")
            M, K1 = toks_blk.shape
            # pad/overflow positions clamp into wpe range; their writes
            # are scratch-routed below regardless (the engine truncates
            # real drafts to the request's token budget, so every
            # CONSUMED position is in range by construction)
            positions = jnp.clip(pos0[:, None] + jnp.arange(K1)[None, :],
                                 0, max_position - 1)

            def ctx_attend(layer, q, k, v):
                if quant:
                    kp, vp, ksc, vsc = pools
                    kb = paged_gather_quantized(kp[layer], ksc[layer],
                                                pt, q.dtype, hd)
                    vb = paged_gather_quantized(vp[layer], vsc[layer],
                                                pt, q.dtype, hd)
                else:
                    kp, vp = pools
                    kb = paged_gather(kp[layer], pt, hd)
                    vb = paged_gather(vp[layer], pt, hd)
                return paged_prefix_attention(q, kb, vb, k, v, pos0,
                                              scale)

            h, ks, vs = gpt_spec_verify(W, toks_blk, positions,
                                        ctx_attend, num_heads=H,
                                        reduce=psum)
            logits = gpt_logits(W, h)                       # [M, K1, V]
            greedy = jnp.argmax(logits, -1).astype(jnp.int32)
            # n_acc = longest prefix of drafts the model agrees with
            # (greedy[j] is the model's token AFTER position j, so
            # draft j+1 is accepted iff it equals greedy[j])
            agree = (greedy[:, :-1] == toks_blk[:, 1:]) & dmask
            n_acc = jnp.sum(jnp.cumprod(agree.astype(jnp.int32),
                                        axis=1), axis=1).astype(jnp.int32)
            # sampled slots take no drafts (greedy acceptance would
            # bias the distribution); they ride the verify program as
            # plain one-token decode with the decode program's
            # temperature/top-k sampling expression
            n_acc = jnp.where(smask, 0, n_acc)
            bonus = jnp.take_along_axis(greedy, n_acc[:, None], 1)[:, 0]
            lg0 = logits[:, 0] / jnp.maximum(temps[:, None], 1e-6)
            if top_k:
                kth = jax.lax.top_k(lg0, int(top_k))[0][..., -1:]
                lg0 = jnp.where(lg0 < kth, -1e30, lg0)
            sampled = jax.random.categorical(key, lg0).astype(jnp.int32)
            nxt = jnp.where(smask, sampled, bonus)
            nxt = jnp.where(active, nxt, 0)
            consumed = jnp.arange(K1)[None, :] <= n_acc[:, None]
            finite = jnp.all(jnp.isfinite(logits), axis=-1)  # [M, K1]
            bad = active & jnp.any(consumed & ~finite, axis=1)
            commit = consumed & active[:, None]
            page_ids, offs = page_rows_for_positions(pt, positions, P)
            page_ids = jnp.where(commit, page_ids, TRASH_PAGE)
            offs = jnp.where(commit, offs, 0)
            L, D = ks.shape[0], ks.shape[-1]
            # [L, M, H, K1, D] -> [L, H, M*K1, D]: the prefill-shaped
            # all-layers scatter
            ksf = jnp.moveaxis(ks, 1, 2).reshape(L, H, M * K1, D)
            vsf = jnp.moveaxis(vs, 1, 2).reshape(L, H, M * K1, D)
            # requant=True: commits land on the slot's current partial
            # page, which already holds content (and, int8, a non-zero
            # scale) — the tail-prefill contract, not the fresh-page one
            pools = write_pages(pools, None, page_ids.reshape(-1),
                                offs.reshape(-1), ksf, vsf, requant=True)
            return (*pools, n_acc, nxt, bad)

        def gen_zero_pages(*rest):
            # trash-padded page rows: the scratch page is re-zeroed with
            # every free, which also scrubs poisoned prefill tails; the
            # int8 mode resets the freed pages' SCALES too, so the next
            # owner starts from a clean quantization grid and a poisoned
            # page's scale can't survive its content
            pools, pages = rest[:NP], rest[NP]
            return tuple(form.zero_pages(p, pages, TRASH_PAGE)
                         for p in pools)

        def gen_tier_gather(*rest):
            """Demotion gather (ISSUE 18): copy ONE page's raw blocks —
            and, in the int8 mode, its per-(layer, head) scale rows —
            out of the pools for the host tier. NON-donating by
            contract: the pools are kept (the content is being copied
            off-device, the page frees through the ordinary eviction
            path right after)."""
            pools, page = rest[:NP], rest[NP]
            note("tier_gather")
            return tuple(form.pages(p, page) for p in pools)

        def gen_tier_write(*rest):
            """Promotion scatter (ISSUE 18): write one fixed-width
            chunk of host-tier pages — raw content, raw int8 scale rows
            — into the admission's fresh target pages. Pad rows route
            to the reserved scratch page with zero content, the
            standard pad contract, so the ONE compiled width
            (kv_tier_chunk_pages) covers every promotion length with
            zero retraces."""
            pools, pages, blocks = rest[:NP], rest[NP], rest[NP + 1:]
            note(f"tier_write[w={pages.shape[0]}]")
            return tuple(form.at_pages(p, pages).set(form.from_chunk(b))
                         for p, b in zip(pools, blocks))

        if tp > 1:
            # partition every program over the 'tp' mesh axis: W enters
            # under the Megatron specs, the pools (and int8 scale
            # grids) head-sharded, page tables / token ids / scalars /
            # PRNG keys replicated, and the logits (psum-reduced inside
            # the blocks) leave replicated — each donated sharded pool
            # aliases straight into its identically-sharded output
            from jax.sharding import PartitionSpec as PS

            from ..models.gpt import decode_weight_specs
            rep = PS()
            wspec = decode_weight_specs(ctx.W)
            # the head axis of a pool, of one page cut out of it, and of
            # a chunk of such pages stacked in front: the form's
            ranks = (form.pool_rank, form.pool_rank, 3, 3)[:NP]  # K, V[,
            #                                                their scales]
            pspecs = tuple(form.spec(r) for r in ranks)
            page_specs = tuple(form.spec(r - 1) for r in ranks)
            chunk_specs = tuple(form.spec(r, lead=1) for r in ranks)

            def shard(fn, extras, outs, with_w=True):
                ins = ((wspec,) if with_w else ()) + pspecs + extras
                return jax.shard_map(fn, mesh=mesh, in_specs=ins,
                                     out_specs=outs, check_vma=False)

            gen_prefill = shard(gen_prefill, (rep,) * 3, (*pspecs, rep))
            gen_prefill_tail = shard(gen_prefill_tail, (rep,) * 4,
                                    (*pspecs, rep))
            gen_decode = shard(gen_decode, (rep,) * 7,
                              (*pspecs, rep, rep))
            gen_verify = shard(gen_verify, (rep,) * 8,
                              (*pspecs, rep, rep, rep))
            gen_cow_copy = shard(gen_cow_copy, (rep,) * 2, pspecs,
                                 with_w=False)
            gen_zero_pages = shard(gen_zero_pages, (rep,), pspecs,
                                   with_w=False)
            # tier seam (ISSUE 18): the host store keeps FULL pages —
            # the gather's sharded out_specs reassemble every head
            # shard into one host block, and the write's chunk specs
            # split the staged full blocks back across the slice
            gen_tier_gather = shard(gen_tier_gather, (rep,), page_specs,
                                    with_w=False)
            gen_tier_write = shard(gen_tier_write, (rep, *chunk_specs),
                                   pspecs, with_w=False)

        return {"prefill": gen_prefill, "prefill_tail": gen_prefill_tail,
                "decode": gen_decode, "verify": gen_verify,
                "zero_pages": gen_zero_pages, "cow_copy": gen_cow_copy,
                "tier_gather": gen_tier_gather,
                "tier_write": gen_tier_write}
