"""The hybrid decode family: what `serving.GenerationEngine` asks a
`models.FalconH1ForCausalLM` for (`serving/decode_family.py`).

Every block holds TWO kinds of per-sequence state, and the family names a
pool for each (`make_cache`):

    K, V     head pools `[L, Hkv, N, P, D]` in the model's dtype, by PAGES:
             grouped-query attention, so the pools hold the Hkv K/V heads
             (`PagedKVCache(num_kv_heads=...)`, `ops/paged_ops.HeadPoolForm`)
             and `paged_attention` reads them by its shape rule (the
             head-pool kernel over the whole pools for 128-wide heads on a
             TPU, the gather elsewhere; which one,
             `stats()["decode_attention"]` says)
    state    `[L, M, H, P, N]` FLOAT32, by SLOT: the mixer's recurrent state,
             a fixed 4 MB a layer a sequence at the published widths
    window   `[L, K, M, C]` in the model's dtype, by SLOT: the last K
             pre-activation rows of the causal convolution (K in front of
             the slots: the device's default layout is then dense and the
             one the decode program wants; with `[L, M, K, C]` the compiler
             chose K-major itself and a layout that is not the default does
             not outlive a compile cache, ops/paged_ops.py)

Slot `i` of the decode batch is row `i` of the slot pools, so the family
sets `slot_state` and its prefill program is told the slot: it writes the
prompt's K/V rows to the pages and, to `slot`, the state after position
`length - 1` and the window at `length` (bucket padding is exact: `dt` is 0
past `length` and the window is taken at `length`, models/falcon_h1.py).
Nothing zeroes a slot when it frees: the next prefill overwrites all of it,
and decode leaves a dead slot's state and window as they are (`active`), so
a non-finite value cannot outlive its request. The state is float32, the
option of the family's public inference code (its default is the model's
dtype): a sequence carries it over hundreds of steps, and a head whose
`dt A` is under 2^-8 loses its decay and its input to bfloat16's rounding at
every write. On the chip a slot's state is ten times further from the
reference's in bfloat16 after 32 steps (`chip_smoke.py --phases hybrid`), and
the benchmark's check fails a state held in bfloat16 (tools/plant_fault.py
`bf16_state`; PERF.md section 6 has both readings).

Built: prefill, decode, zero-pages. NOT built, and refused by name at
construction: the prefix cache's tail prefill and copy-on-write (a shared
prefix needs a state snapshot at its end), chunked prefill (it rides the
tail program, and would carry a state from chunk to chunk), speculative
verify (a rejected draft would have to roll the state back), the host tier,
int8 pages, tensor parallelism. A supervised restart replays a request
through prefill, which rebuilds its state.

The decode program counts two things on the device and returns them with
the tokens (`step_counters`): `state_slots`, the live slots whose state the
step read and wrote, and `kv_rows`, the cached positions the step attends,
summed over the live slots.
"""
from __future__ import annotations

import numpy as np

from ..framework.errors import InvalidArgumentError
from .decode_family import sample_next
from .kv_cache import TRASH_PAGE, PagedKVCache

__all__ = ["HybridFamily", "hybrid_decode", "hybrid_prefill"]


def store_state(state_pool, slot, states):
    """Prefill's write of one sequence's states [L, H, P, N] to `slot`."""
    return state_pool.at[:, slot].set(states.astype(state_pool.dtype))


def store_window(window_pool, slot, windows):
    """Prefill's write of one sequence's windows [L, K, C] to `slot`."""
    return window_pool.at[:, :, slot].set(windows.astype(window_pool.dtype))


def hybrid_prefill(W, pools, pt_row, ids, length, slot, cfg, page_size):
    """One prompt (ids [S_b], `length` real positions) through pages and
    slot: its K/V rows written to the pages of `pt_row` (the bucket's
    padding to the scratch page), its state and window written to `slot`.
    Returns (pools, logits [V] float32 of position `length - 1`). The
    engine's prefill program is this; the tests read the logits here."""
    import jax
    import jax.numpy as jnp

    from ..models import falcon_h1
    from ..ops.paged_ops import page_rows_for_positions, paged_write

    kp, vp, sp, cp = pools
    S_b = ids.shape[0]
    h, ks, vs, states, windows = falcon_h1.fh1_prefill(W, ids, cfg, length)
    pos = jnp.arange(S_b)
    valid = pos < length
    page_ids, offs = page_rows_for_positions(pt_row, pos, page_size)
    page_ids = jnp.where(valid, page_ids, TRASH_PAGE)
    offs = jnp.where(valid, offs, 0)
    kp = paged_write(kp, None, page_ids, offs, ks.astype(kp.dtype))
    vp = paged_write(vp, None, page_ids, offs, vs.astype(vp.dtype))
    with jax.named_scope("state_write"):
        sp = store_state(sp, slot, states)
        cp = store_window(cp, slot, windows)
    idx = jnp.clip(length - 1, 0, S_b - 1)
    return (kp, vp, sp, cp), falcon_h1.fh1_logits(W, h[idx], cfg)


def hybrid_decode(W, pools, pt, tok, pos, active, cfg, page_size):
    """One decode step through pages and slot state: every slot's K/V row
    written at `pos` and attended over t <= pos of its own pages, its
    window shifted and its state updated in place (a dead slot's left as
    they are). Returns (logits [M, V] float32, pools, state_slots,
    kv_rows). The engine's decode program is this plus sampling; the tests
    read the logits here."""
    import jax.numpy as jnp

    from ..models.falcon_h1 import fh1_decode_step
    from ..ops import ssm_ops
    from ..ops.paged_ops import (page_rows_for_positions, paged_attention,
                                 paged_write)

    scale = cfg.head_dim ** -0.5

    def write_kv(cache, layer, k, v, pos):
        kp, vp, sp, cp = cache
        page_ids, offs = page_rows_for_positions(pt, pos, page_size)
        return (paged_write(kp, layer, page_ids, offs, k.astype(kp.dtype)),
                paged_write(vp, layer, page_ids, offs, v.astype(vp.dtype)),
                sp, cp)

    def attend_kv(cache, layer, q, pos):
        kp, vp = cache[:2]
        return paged_attention(q, kp, vp, pt, pos, scale,
                               kv_heads=cfg.num_key_value_heads, layer=layer)

    def conv_step(cache, layer, lw, xbc):
        kp, vp, sp, cp = cache
        window = cp[layer]
        conv, shifted = ssm_ops.causal_conv_window_step(
            window, xbc, lw["conv_w"], lw["conv_b"])
        shifted = jnp.where(active[None, :, None], shifted, window)
        return (kp, vp, sp, cp.at[layer].set(shifted)), conv

    def state_step(cache, layer, decay, dtx, B, C):
        kp, vp, sp, cp = cache
        # a dead slot: decay 1 and dt x 0 already (`live`); its B made
        # finite, so that 0 x B adds nothing whatever its window held
        B = jnp.where(active[:, None, None], B, 0)
        sp, y = ssm_ops.ssm_decode_update(sp, layer, decay, dtx, B, C)
        return (kp, vp, sp, cp), y

    logits, pools = fh1_decode_step(W, tok, pos, tuple(pools), write_kv,
                                    attend_kv, conv_step, state_step, cfg,
                                    live=active)
    slots = jnp.sum(active).astype(jnp.int32)
    rows = jnp.sum(jnp.where(active, pos + 1, 0)).astype(jnp.int32)
    return logits, pools, slots, rows


class HybridFamily:
    name = "hybrid"
    # the prefill program takes the request's slot after `length`
    slot_state = True
    step_counters = ("state_slots", "kv_rows")

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
                    f"hybrid family ({type(self._model).__name__}); it "
                    f"serves prefill, decode and zero-pages over K/V pages "
                    f"and a state and a window per slot")
        m = self.config
        chunk = m.mamba_chunk_size
        for b in cfg.prefill_buckets:
            if b > chunk and b % chunk:
                raise InvalidArgumentError(
                    f"GenerationEngine: prefill bucket {b} is not a "
                    f"multiple of the scan's chunk ({chunk}) for the "
                    f"hybrid family ({type(self._model).__name__})")

    def make_cache(self, cfg, kv_dtype, mesh):
        m = self.config
        L, M = m.num_hidden_layers, cfg.max_slots
        return PagedKVCache(
            L, m.num_heads, m.head_dim, cfg.page_size, cfg.num_pages,
            cfg.pages_per_seq, dtype=kv_dtype,
            num_kv_heads=m.num_key_value_heads,
            # the state float32 whatever the model computes in; the window
            # in the pages' dtype
            slot_pools=[((L, M) + m.state_shape, "float32"),
                        ((L, m.mamba_d_conv, M, m.conv_dim), kv_dtype)])

    def decode_attention(self, cfg, tp, pools):
        """`kernel` or `reference`: the shape rule of ops/paged_ops.py
        over Hq query heads and the pools' Hkv, known before anything is
        traced."""
        from ..ops.paged_ops import HeadPoolForm, paged_attention_path
        m, kp = self.config, pools[0]
        form = HeadPoolForm(m.num_key_value_heads, m.head_dim)
        return paged_attention_path(
            (cfg.max_slots, m.num_heads, m.head_dim),
            form.layer_shape(kp.shape),
            (cfg.max_slots, cfg.pages_per_seq), kp.dtype)

    def describe(self, cfg, pools):
        """`stats()["ssm_decode_path"]`: `kernel` or `reference`, the
        shape-and-backend rule of ops/ssm_ops.py."""
        from ..ops.ssm_ops import ssm_decode_path
        return {"ssm_decode_path": ssm_decode_path(
            pools[2].shape, pools[2].dtype, self.config.mamba_n_groups)}

    def build(self, ctx):
        import jax.numpy as jnp

        from ..ops.paged_ops import HeadPoolForm

        mcfg, note = self.config, ctx.note
        P, top_k = ctx.cfg.page_size, ctx.cfg.top_k
        form = HeadPoolForm(mcfg.num_key_value_heads, mcfg.head_dim)

        def gen_prefill(W, kp, vp, sp, cp, pt_row, ids, length, slot):
            note(f"prefill[b={ids.shape[1]}]")
            pools, logits = hybrid_prefill(W, (kp, vp, sp, cp), pt_row,
                                           ids[0], length, slot, mcfg, P)
            return (*pools, logits)

        def gen_decode(W, kp, vp, sp, cp, pt, tok, pos, active, temps,
                       smask, key):
            note(f"decode[m={tok.shape[0]}]")
            logits, pools, slots, rows = hybrid_decode(
                W, (kp, vp, sp, cp), pt, tok, pos, active, mcfg, P)
            nxt, bad = sample_next(logits, active, temps, smask, key, top_k)
            return (*pools, nxt, bad, jnp.stack([slots, rows]))

        def gen_zero_pages(kp, vp, sp, cp, pages):
            # the freed pages and the scratch page; the slot pools have no
            # page to zero (module docstring)
            return (form.zero_pages(kp, pages, TRASH_PAGE),
                    form.zero_pages(vp, pages, TRASH_PAGE), sp, cp)

        return {"prefill": gen_prefill, "decode": gen_decode,
                "zero_pages": gen_zero_pages}
