"""The latent-attention decode family: what `serving.GenerationEngine` asks a
`models.GlmMoeLiteForCausalLM` for (`serving/decode_family.py`).

A cached token is ONE row per layer with no head axis, `[c_kv | k_r]`
(`latent_dim` wide, 576 as published): one pool `[L, N, P, Rp]` in the
model's dtype, Rp the row rounded up to whole 128-lane tiles (640; why:
`ops/paged_ops.py`). Prefill runs the expanded attention over the prompt and
writes its rows; decode writes the new row, absorbs the up-projection into
the query and attends the slot's own rows, read once for all its heads
(`ops/paged_ops.paged_latent_attention`: on a TPU one Pallas kernel that
walks the slot's own pages in the pool as far as `pos`, elsewhere and for
the shapes its rule refuses a gather of the slot's whole table; which one,
`stats()["decode_attention"]` says). Positions are rotary, so
the largest position is the configuration's `max_position_embeddings`; the
table's width (`pages_per_seq`) is what bounds a sequence in practice.

Built: prefill, decode, zero-pages. NOT built, and refused by name at
construction: the prefix cache's tail prefill and copy-on-write, chunked
prefill (it rides the tail program), speculative verify, the host tier,
int8 pages, tensor parallelism.

The decode program counts two things on the device and returns them with
the tokens (`step_counters`): `experts_hit`, the distinct experts that got
at least one live row, summed over the expert layers, and `latent_rows`,
the cached positions the step attends, summed over the live slots.
"""
from __future__ import annotations

import numpy as np

from ..framework.errors import InvalidArgumentError
from .decode_family import sample_next
from .kv_cache import TRASH_PAGE, PagedKVCache

__all__ = ["LatentFamily", "latent_decode"]


def latent_decode(W, pool, pt, tok, pos, active, cfg, page_size):
    """One decode step through the latent pages: the row of every slot's
    token written at `pos`, then attention over t <= pos of the slot's own
    pages. Returns (logits [B, V] float32, pool, experts_hit, latent_rows).
    The engine's decode program is this plus sampling; the tests read the
    logits here."""
    import jax.numpy as jnp

    from ..models.glm_moe import glm_decode_step
    from ..ops.paged_ops import (page_rows_for_positions,
                                 paged_latent_attention, paged_latent_write)

    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5

    def write_row(pool, layer, row, pos):
        page_ids, offs = page_rows_for_positions(pt, pos, page_size)
        return paged_latent_write(pool, layer, page_ids, offs, row)

    def attend_rows(pool, layer, q, pos):
        # the whole pool and the layer's index: the kernel reads that
        # layer's pages in place, with no layer cut out of the pool first
        return paged_latent_attention(q, pool, pt, pos, scale,
                                      cfg.kv_lora_rank, layer)

    logits, pool, hit = glm_decode_step(W, tok, pos, pool, write_row,
                                        attend_rows, cfg, live=active)
    rows = jnp.sum(jnp.where(active, pos + 1, 0)).astype(jnp.int32)
    return logits, pool, hit, rows


class LatentFamily:
    name = "latent"
    step_counters = ("experts_hit", "latent_rows")

    def __init__(self, model):
        self._model = model
        self.config = model.config
        self.max_position = self.config.max_position_embeddings

    def weights(self):
        return self._model.decode_weights()

    def dtype(self, W):
        return np.dtype(W["norm"].dtype)

    def check(self, cfg, tp):
        """Refuse, by name, the options this family does not build: no
        silent fallback, no other family's path taken by mistake."""
        asked = [("tp > 1 (tensor parallelism)", tp > 1),
                 ("kv_cache_dtype='int8'", cfg.kv_cache_dtype == "int8"),
                 ("prefix_cache", cfg.prefix_cache),
                 ("prefill_chunk", cfg.prefill_chunk > 0),
                 ("spec_k (speculative verify)", cfg.spec_k > 0),
                 ("kv_tier (host tier)", cfg.kv_tier)]
        for what, on in asked:
            if on:
                raise InvalidArgumentError(
                    f"GenerationEngine: {what} is not built for the "
                    f"latent-cache family ({type(self._model).__name__}); "
                    f"it serves prefill, decode and zero-pages over one "
                    f"latent pool")

    def make_cache(self, cfg, kv_dtype, mesh):
        from ..ops.paged_ops import latent_pool_width
        m = self.config
        # one row per token and layer, whole lane tiles wide, no head axis
        shape = (m.num_hidden_layers, cfg.num_pages, cfg.page_size,
                 latent_pool_width(m.latent_dim))
        return PagedKVCache.described([(shape, kv_dtype)], cfg.page_size,
                                      cfg.num_pages, cfg.pages_per_seq)

    def decode_attention(self, cfg, tp, pools):
        """`latent_kernel` or `latent_gather`: the shape-and-backend rule
        of ops/paged_ops.py (`paged_latent_path`), known before anything
        is traced."""
        from ..ops.paged_ops import paged_latent_path
        m, pool = self.config, pools[0]
        return paged_latent_path(
            (cfg.max_slots, m.num_heads, m.latent_dim), pool.shape[1:],
            (cfg.max_slots, cfg.pages_per_seq), pool.dtype)

    def build(self, ctx):
        import jax.numpy as jnp

        from ..models.glm_moe import glm_logits, glm_prefill
        from ..ops.paged_ops import (page_rows_for_positions,
                                     paged_latent_write)

        mcfg, note = self.config, ctx.note
        P, top_k = ctx.cfg.page_size, ctx.cfg.top_k

        def gen_prefill(W, pool, pt_row, ids, length):
            note(f"prefill[b={ids.shape[1]}]")
            S_b = ids.shape[1]
            pos = jnp.arange(S_b)
            valid = pos < length
            h, rows, _ = glm_prefill(W, ids[0], mcfg, live=valid)
            # bucket-pad positions (pos >= length) write to the reserved
            # scratch page, never the sequence's own pages
            page_ids, offs = page_rows_for_positions(pt_row, pos, P)
            page_ids = jnp.where(valid, page_ids, TRASH_PAGE)
            offs = jnp.where(valid, offs, 0)
            pool = paged_latent_write(pool, None, page_ids, offs, rows)
            idx = jnp.clip(length - 1, 0, S_b - 1)
            return pool, glm_logits(W, h[idx], mcfg)

        def gen_decode(W, pool, pt, tok, pos, active, temps, smask, key):
            note(f"decode[m={tok.shape[0]}]")
            logits, pool, hit, rows = latent_decode(
                W, pool, pt, tok, pos, active, mcfg, P)
            nxt, bad = sample_next(logits, active, temps, smask, key, top_k)
            return pool, nxt, bad, jnp.stack([hit, rows])

        def gen_zero_pages(pool, pages):
            # trash-padded page rows: the scratch page is re-zeroed with
            # every free, which also scrubs poisoned prefill tails
            return (pool.at[:, pages].set(0),)

        return {"prefill": gen_prefill, "decode": gen_decode,
                "zero_pages": gen_zero_pages}
