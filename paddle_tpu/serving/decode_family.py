"""Decode families: what `GenerationEngine` asks a model for.

The engine's scheduler, page allocator, streams, step ring and supervisor
know pages, slots and tokens, and no model. Everything that depends on what
a model IS — its weight pytree, what one cached token (and, for a family with
state by slot, one sequence's state) looks like, the bodies
of the prefill / decode / zero-pages programs, the largest position it can
be asked for, and which of the engine's options it can serve — comes from
the model's family, which the model hands over from `decode_family()`:

    name             "gpt" (serving/gpt_family.py), "latent"
                     (serving/latent_family.py), "hybrid"
                     (serving/hybrid_family.py)
    max_position     largest number of positions of one sequence
    slot_state       (optional, default False) True for a family that keeps
                     a fixed-size state per SLOT beside its pages (the
                     hybrid family: a state-space state and a convolution
                     window). Slot `i` of the decode batch is row `i` of
                     such a pool; the prefill program is then told the
                     request's slot (one more argument, below); nothing
                     zeroes a slot on free, the next prefill overwrites it
    step_counters    names of the `StepRecord` fields the decode program
                     counts on the device: it returns them as ONE int32
                     vector after the tokens and the poison flags, and the
                     step thread sums them into the iteration's record
    weights()        the weight pytree the programs take as an ARGUMENT; it
                     aliases the parameters' arrays
    dtype(W)         the dtype the model computes in ("auto" pages take it)
    check(cfg, tp)   raises a named InvalidArgumentError for an option of
                     the engine that the family does not build
    shard_weights(W, mesh)   (a family whose `check` admits tp > 1)
    make_cache(cfg, kv_dtype, mesh) -> PagedKVCache
                     the pools are the cache's `pools`, in the order the
                     programs take and return them: pools WITH a page axis
                     (`kind` "pages": the allocator's) and, after them,
                     pools WITHOUT one, indexed by slot (`kind` "slots":
                     `PagedKVCache(slot_pools=...)`); the engine lays
                     them out on the device as its step program was
                     compiled to take them and holds every program to
                     that layout (`generation.jit_program`), whatever
                     the family
    decode_attention(cfg, tp, pools) -> str
                     the name `stats()["decode_attention"]` shows
    describe(cfg, pools) -> dict   (optional) further names of what the
                     family's programs were built with, merged into
                     `stats()` (the hybrid family: `ssm_decode_path`)
    build(ctx)       the program bodies by name: "prefill", "decode",
                     "zero_pages" always; "prefill_tail", "cow_copy",
                     "verify", "tier_gather", "tier_write" where the family
                     serves the option (else None). Signatures:
                       prefill(W, *pools, pt_row, ids, length)
                           -> (*pools, logits of the last real position)
                       prefill(W, *pools, pt_row, ids, length, slot)
                           the same, of a family with `slot_state`
                       decode(W, *pools, pt, tok, pos, active, temps,
                              smask, key) -> (*pools, next, bad[, counters])
                       zero_pages(*pools, pages) -> pools
"""
from __future__ import annotations

from ..framework import monitor
from ..framework.errors import InvalidArgumentError

__all__ = ["ProgramContext", "family_of", "sample_next"]


class ProgramContext:
    """What a family's `build` sees of the engine: configuration, mesh,
    the pools' count and the compile ledger's `note` — scalars and the
    LEDGER, never the engine object: the program pack outlives any one
    incarnation, and a closure pinning a dead engine would pin its pools."""

    def __init__(self, cfg, tp, mesh, npool, quant, decode_attention, W,
                 ledger):
        self.cfg, self.tp, self.mesh = cfg, tp, mesh
        self.npool, self.quant = npool, quant
        self.decode_attention, self.W = decode_attention, W

        def note(key: str):
            # runs at TRACE time only (python side effect under jit),
            # so the pack-owned ledger counts compiles exactly — the
            # same accounting trick as Predictor.compile_count
            ledger[key] = ledger.get(key, 0) + 1
            monitor.stat_add("STAT_gen_compiles")
        self.note = note


def sample_next(logits, active, temps, smask, key, top_k):
    """The tail of every family's decode program: greedy or sampled next
    token per slot (0 for an empty slot) and the per-slot poison flag.
    logits [M, V]; returns (next [M] int32, bad [M] bool)."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)
        lg = logits / jnp.maximum(temps[:, None], 1e-6)
        if top_k:
            kth = jax.lax.top_k(lg, int(top_k))[0][..., -1:]
            lg = jnp.where(lg < kth, -1e30, lg)
        sampled = jax.random.categorical(key, lg).astype(jnp.int32)
        nxt = jnp.where(smask, sampled, greedy)
        bad = active & ~jnp.all(jnp.isfinite(logits), axis=-1)
        return jnp.where(active, nxt, 0), bad


def family_of(model):
    make = getattr(model, "decode_family", None)
    if make is None:
        raise InvalidArgumentError(
            f"GenerationEngine serves a model that has a decode family "
            f"(models.GPTForCausalLM, models.GlmMoeLiteForCausalLM, "
            f"models.FalconH1ForCausalLM); got "
            f"{type(model).__name__}")
    return make()
