"""The main path's kernels, compiled at the benchmark's real widths for a
DESCRIBED TPU v5e chip (no chip attached): what the chip's compiler would
refuse, it refuses here, at no chip time. By the rules of the
on-chip-measurement guide: the topology is described inside a module-scoped
fixture that skips, never at import, and every such test lives in this one
file (only one process at a time may load the TPU's library).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_ops as po

# the train cell's attention: 32 sequences x 12 heads = 384 rows of
# 512 x 64, bf16, not causal, attention dropout 0.1 (ernie-base.pretrain-s512)
B, H, S, D = 32, 12, 512, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def uncached():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def flash(q, k, v, bias, seed):
    return po.flash_attention_raw(q, k, v, bias, seed, False, D ** -0.5, 0.1)


def flash_grads(q, k, v, bias, seed):
    return jax.grad(lambda *a: flash(*a, bias, seed).astype(jnp.float32)
                    .sum(), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("fn, kernels", [
    (flash, ("flash_fwd",)),
    (flash_grads, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")),
], ids=["forward", "backward"])
def test_flash_compiles_for_the_v5e_at_the_train_cells_shapes(
        one_chip, uncached, monkeypatch, fn, kernels):
    monkeypatch.setattr(po, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((B, S), jnp.float32, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = jax.jit(fn).lower(x, x, x, bias, seed).compile().as_text()
    assert text.count("tpu_custom_call") >= len(kernels)
    for name in kernels:        # the kernels' own names, as a trace shows
        assert name in text, name


# -- the serve cell's decode program (gpt2-xl.batch-saturated) --------------
# 48 layers, 25 heads of 64, vocabulary 50257, float32; 16 slots, pages of
# 16 tokens, 128 pages, 64 table entries (benchmark/configs/gpt2-xl.json)
GEN_L, GEN_V, GEN_E, GEN_H = 48, 50257, 1600, 25
GEN_SLOTS, GEN_PAGE, GEN_PAGES, GEN_PP = 16, 16, 128, 64
# `temp_size_in_bytes` of the same program at the parent of PR 26 (the
# per-slot gather), compiled here for the same described chip
GEN_PARENT_TEMP = 5_175_807_488


def test_gen_decode_reads_the_pool_once_at_the_serve_cells_shapes(
        one_chip, uncached):
    """The engine's own `gen_decode`, lowered at the cell's shapes: the
    closure depends on heads, page size and the pool's page count, not on
    depth or vocabulary, so a one-layer engine builds it and the 48-layer
    shapes go in as arguments. Pool-dense by the shape rule (128 pages
    <= 16 x 64): no per-slot gather of the 1,024-position extent, the
    ownership mask computed once and outside the layers, no more
    temporaries than the gather path needed."""
    from paddle_tpu import serving
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    net = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=GEN_E, num_heads=GEN_H, num_layers=1,
        intermediate_size=4 * GEN_E, max_position_embeddings=1024,
        dropout=0.0))
    net.eval()
    eng = serving.GenerationEngine(
        net, max_slots=GEN_SLOTS, page_size=GEN_PAGE, num_pages=GEN_PAGES,
        prefill_buckets=(128,), warmup=False, name="v5e_compile_probe")
    try:
        assert eng.stats()["decode_attention"] == "pool"

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                        sharding=one_chip)

        def like(a):
            return sds(a.shape, a.dtype)

        W1 = eng._W
        W = {"wte": sds((GEN_V, GEN_E), jnp.float32), "wpe": like(W1["wpe"]),
             "lnf": tuple(like(a) for a in W1["lnf"]),
             "blocks": [tuple(like(a) for a in W1["blocks"][0])
                        for _ in range(GEN_L)]}
        pool = sds((GEN_L,) + tuple(eng._kp.shape[1:]), eng._kp.dtype)
        key = jax.eval_shape(lambda: jax.random.key_data(jax.random.key(0)))
        M = GEN_SLOTS
        compiled = eng._decode_jit.lower(
            W, pool, pool, sds((M, GEN_PP), jnp.int32), sds((M,), jnp.int32),
            sds((M,), jnp.int32), sds((M,), jnp.bool_),
            sds((M,), jnp.float32), sds((M,), jnp.bool_),
            sds(key.shape, key.dtype)).compile()
    finally:
        eng.shutdown(drain=False)
    text = compiled.as_text()
    assert "kv_attend" in text and "kv_mask" in text    # names reach the text
    assert "kv_gather" not in text
    assert f"f32[{M},{GEN_H},1024,64]" not in text      # the gathered K / V
    # one mask: built at the program's top, never under a layer's scope, and
    # its [slots, entries, pages] compare appears once
    assert not re.search(r"layer_\d+/attn/kv_mask", text)
    owns = [ln for ln in text.splitlines()
            if "kv_mask/eq" in ln and " compare(" in ln]
    assert len(owns) == 1 and f"pred[{M},{GEN_PP},{GEN_PAGES}]" in owns[0]
    assert compiled.memory_analysis().temp_size_in_bytes <= GEN_PARENT_TEMP


# -- the latent family's decode program (glm-4.7-flash.reasoning-saturated) --
# published widths, 64 experts, vocabulary 154,880, bfloat16; 32 slots, pages
# of 16 tokens, 8,192 pages, 256 table entries
# (benchmark/configs/glm-4.7-flash.json). The closure depends on the widths,
# not on depth, so one dense + one expert layer compile in seconds.
LAT_SLOTS, LAT_PAGE, LAT_PAGES, LAT_PP = 32, 16, 8192, 256


def test_latent_decode_compiles_for_the_v5e_without_a_pool_copy(
        one_chip, uncached):
    """What the chip's compiler would refuse it refuses here: the grouped
    expert product (`lax.ragged_dot` -> XLA:TPU's own kernel) is traced
    under "default" precision because Mosaic refuses its bfloat16 operands
    under the framework's "highest" pin; a cached row takes 640 lanes so
    that no program copies the whole pool in and out (at 576 each did:
    1.17 GB of temporaries for `zero_pages` alone); each slot's rows are
    gathered once a layer for its 20 heads."""
    import types

    from paddle_tpu.models.glm_moe import (GlmMoeLiteConfig,
                                           glm_weight_shapes)
    from paddle_tpu.serving.decode_family import ProgramContext
    from paddle_tpu.serving.generation import GenerationConfig
    from paddle_tpu.serving.latent_family import LatentFamily

    cfg = GlmMoeLiteConfig(num_hidden_layers=2)
    fam = LatentFamily(types.SimpleNamespace(config=cfg))
    ecfg = GenerationConfig(max_slots=LAT_SLOTS, page_size=LAT_PAGE,
                            num_pages=LAT_PAGES, pages_per_seq=LAT_PP,
                            prefill_buckets=(256,), warmup=False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    W = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                               glm_weight_shapes(cfg))
    pool = sds((2, LAT_PAGES, LAT_PAGE, 640), "bfloat16")
    path = fam.decode_attention(ecfg, 1, (pool,))
    assert path == "latent_gather"
    fns = fam.build(ProgramContext(ecfg, 1, None, 1, False, path, W, {}))
    key = jax.eval_shape(lambda: jax.random.key_data(jax.random.key(0)))
    M = LAT_SLOTS
    decode = jax.jit(fns["decode"], donate_argnums=(1,)).lower(
        W, pool, sds((M, LAT_PP), "int32"), sds((M,), "int32"),
        sds((M,), "int32"), sds((M,), "bool"), sds((M,), "float32"),
        sds((M,), "bool"), sds(key.shape, key.dtype)).compile()
    zero = jax.jit(fns["zero_pages"], donate_argnums=(0,)).lower(
        pool, sds((LAT_PP,), "int32")).compile()
    text = decode.as_text()
    for scope in ("layer_1/mla/latent_attend", "layer_1/mla/absorb",
                  "layer_1/mla/latent_write", "layer_1/moe/experts",
                  "layer_1/moe/router", "layer_0/mlp", "lm_head", "sample"):
        assert scope in text, scope
    assert "ragged-dot" in text and "tpu_custom_call" in text
    assert "kv_mask" not in text            # no pool-dense ownership mask
    # no copy of the whole pool, in either program
    whole = f"bf16[2,{LAT_PAGES},{LAT_PAGE},640]"
    for t in (text, zero.as_text()):
        assert not [ln for ln in t.splitlines()
                    if f"= {whole}" in ln and " copy(" in ln]
    assert zero.memory_analysis().temp_size_in_bytes < 1 << 20
    # a layer's gathered rows [32, 4096, 640] bfloat16 (168 MB) and their
    # scores; nothing pool-dense: [640, 131072] float32 would be 335 MB
    assert decode.memory_analysis().temp_size_in_bytes < 400 << 20
