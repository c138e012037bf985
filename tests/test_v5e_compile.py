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
import numpy as np
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


# -- the serve cell's programs (gpt2-xl.batch-saturated) ---------------------
# 48 layers, 25 heads of 64, vocabulary 50257, float32; 16 slots, pages of
# 16 tokens, 128 pages, 64 table entries (benchmark/configs/gpt2-xl.json)
GEN_L, GEN_V, GEN_E, GEN_H = 48, 50257, 1600, 25
GEN_SLOTS, GEN_PAGE, GEN_PAGES, GEN_PP = 16, 16, 128, 64
# one v5e chip's memory, as the compiler counts it, and the 1 GiB of
# temporaries a program of this engine may take (the decode program took
# 4.9 GB at the parent of PR 28, when every program relaid the whole pools)
V5E_HBM, GEN_TEMP_LIMIT = 15.75e9, 1 << 30


def step_inputs(sds, M, PP, key):
    """The decode program's arguments after the pools, as shapes (ISSUE 34:
    page table, the device's tokens of the step before, the host's tokens
    and their mask, positions, activity, temperatures, sample mask, base
    key, step number)."""
    return (sds((M, PP), "int32"), sds((M,), "int32"), sds((M,), "int32"),
            sds((M,), "bool"), sds((M,), "int32"), sds((M,), "bool"),
            sds((M,), "float32"), sds((M,), "bool"),
            sds(key.shape, key.dtype), sds((), "int32"))


def xl_programs(one_chip, num_pages, names):
    """The engine's own programs, compiled at the cell's shapes THROUGH THE
    ENGINE'S JIT BOUNDARY (`jit_program`): the closures depend on heads,
    page size and the pool's page count, not on depth or vocabulary, so a
    one-layer engine builds them and the 48-layer shapes go in as
    arguments. As the engine does it: the decode program with the pools'
    layout left to the compiler, the others pinned to what it chose.
    Returns ({name: compiled}, the pool's shape, the chosen formats)."""
    from jax.experimental.layout import Format, Layout

    from paddle_tpu.device import layout_name

    from paddle_tpu import serving
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    net = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=GEN_E, num_heads=GEN_H, num_layers=1,
        intermediate_size=4 * GEN_E, max_position_embeddings=1024,
        dropout=0.0))
    net.eval()
    eng = serving.GenerationEngine(
        net, max_slots=GEN_SLOTS, page_size=GEN_PAGE, num_pages=num_pages,
        prefill_buckets=(128,), warmup=False, name="v5e_compile_probe")
    try:
        assert eng.stats()["decode_attention"] == "pool"
        assert eng.stats()["pages"]["pool_form"] == "[L,N,P,H*D]"

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
        auto = (Format(Layout.AUTO, one_chip),) * 2
        out = {"decode": eng._jit_program("decode", auto).lower(
            W, pool, pool, *step_inputs(sds, M, GEN_PP, key)).compile()}
        fmts = tuple(out["decode"].input_formats[0][1:3])
        assert fmts == tuple(out["decode"].output_formats[:2])
        # left to choose, the compiler keeps the layout the pools lie in
        # by default: the engine compiles its decode program ONCE, moves
        # no pool, and nothing depends on a layout surviving the compile
        # cache (a row of 1,600 lanes instead of 1,664 fails here: the
        # default then puts the pages in the lanes)
        for f in fmts:
            assert layout_name(f, pool.shape, pool.dtype) == "default"
        if "prefill" in names:
            out["prefill"] = eng._jit_program("prefill", fmts).lower(
                W, pool, pool, sds((GEN_PP,), jnp.int32),
                sds((1, 128), jnp.int32), sds((), jnp.int32)).compile()
        if "zero_pages" in names:
            out["zero_pages"] = eng._jit_program(
                "zero_pages", fmts, with_w=False).lower(
                pool, pool, sds((GEN_PP,), jnp.int32)).compile()
    finally:
        eng.shutdown(drain=False)
    return out, pool.shape, fmts


def whole_pool_copies(compiled, shape, dtype="f32"):
    whole = f"= {dtype}[{','.join(map(str, shape))}]"
    return [ln.strip()[:160] for ln in compiled.as_text().splitlines()
            if whole in ln and " copy(" in ln]


def test_the_serve_cells_programs_copy_no_pool_at_their_boundary(
        one_chip, uncached):
    """`gen_decode`, `gen_prefill` (bucket 128) and `gen_zero_pages` at the
    cell's shapes: no copy whose result is a whole pool (the parent of
    PR 28 relaid K and V whole on entry and again on exit of every one:
    13.0 of the decode program's 32.6 ms on the chip) and under 1 GiB of
    temporaries each (decode 4.9 GB there); the pools dense, one row of 25
    heads x 64 in 13 whole lane tiles. And what PR 26 brought stays:
    pool-dense by the shape rule (128 pages <= 16 x 64), no per-slot gather
    of the 1,024-position extent, the ownership mask computed once and
    outside the layers."""
    progs, shape, fmts = xl_programs(one_chip, GEN_PAGES,
                                     ("decode", "prefill", "zero_pages"))
    assert shape == (GEN_L, GEN_PAGES, GEN_PAGE, 1664)     # 13 lane tiles
    for name, compiled in progs.items():
        assert not whole_pool_copies(compiled, shape), name
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < GEN_TEMP_LIMIT, name
        # both pools donated and aliased into their outputs
        assert mem.alias_size_in_bytes >= 2 * 4 * int(np.prod(shape)), name
    # the pools' bytes on the device: within 1.1 times the bytes of what
    # they store (13 lane tiles of 128 for 25 x 64 values: 1.04)
    mem = progs["zero_pages"].memory_analysis()
    stored = 2 * 4 * GEN_L * GEN_PAGES * GEN_PAGE * GEN_H * 64
    assert mem.argument_size_in_bytes < 1.1 * stored
    text = progs["decode"].as_text()
    M = GEN_SLOTS
    assert "kv_attend" in text and "kv_mask" in text    # names reach the text
    assert "kv_gather" not in text
    assert f"f32[{M},{GEN_H},1024,64]" not in text      # the gathered K / V
    # one mask: built at the program's top, never under a layer's scope, and
    # its [slots, entries, pages] compare appears once
    assert not re.search(r"layer_\d+/attn/kv_mask", text)
    owns = [ln for ln in text.splitlines()
            if "kv_mask/eq" in ln and " compare(" in ln]
    assert len(owns) == 1 and f"pred[{M},{GEN_PP},{GEN_PAGES}]" in owns[0]


@pytest.mark.parametrize("num_pages", [256, 512])
def test_gen_decode_fits_the_chip_with_a_larger_pool(
        one_chip, uncached, num_pages):
    """The pool the cell could not have (PERF.md section 4: 4.3 GiB of
    temporaries at 256 pages, no fit at 288): the decode program with its
    6.22 GB of weights, both pools and its temporaries fits one chip at 256
    and at 512 pages, copies no pool and is still pool-dense (512 <= 16 x
    64). The pool's size is the benchmark's to raise (PERF.md section 7)."""
    progs, shape, _ = xl_programs(one_chip, num_pages, ("decode",))
    mem = progs["decode"].memory_analysis()
    assert mem.argument_size_in_bytes > 6.22e9      # the weights are in it
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < V5E_HBM
    assert mem.temp_size_in_bytes < GEN_TEMP_LIMIT
    assert not whole_pool_copies(progs["decode"], shape)


# the cell's pool as benchmark/configs/gpt2-xl.json has it: 320 pages
GEN_CELL_PAGES = 320


def xl_kernel_decode(one_chip, monkeypatch, num_pages):
    """The GPT family's decode program at the cell's shapes with the
    fused-row kernel's side of the rule taken, as a TPU backend takes it
    (the rule asks the backend, which is the CPU here: the test answers
    for it), through the engine's jit boundary with the pools' layout left
    to the compiler: (lowered, compiled). Built from the family, not an
    engine, which would compile the kernel for the CPU."""
    import types

    from jax.experimental.layout import Format, Layout

    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.ops import latent_attention_kernel, paged_ops
    from paddle_tpu.serving.decode_family import ProgramContext
    from paddle_tpu.serving.generation import (GenerationConfig, jit_program,
                                               with_step_inputs)
    from paddle_tpu.serving.gpt_family import GPTFamily

    monkeypatch.setattr(paged_ops, "_pallas_runs", lambda: True)
    monkeypatch.setattr(latent_attention_kernel, "_interpret", lambda: False)
    cfg = GPTConfig(vocab_size=256, hidden_size=GEN_E, num_heads=GEN_H,
                    num_layers=1, intermediate_size=4 * GEN_E,
                    max_position_embeddings=1024, dropout=0.0)
    W1 = GPTForCausalLM(cfg).decode_weights()
    fam = GPTFamily(types.SimpleNamespace(gpt=types.SimpleNamespace(
        config=cfg)))
    ecfg = GenerationConfig(max_slots=GEN_SLOTS, page_size=GEN_PAGE,
                            num_pages=num_pages, pages_per_seq=GEN_PP,
                            prefill_buckets=(128,), warmup=False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    def like(a):
        return sds(np.shape(a), np.asarray(a).dtype)

    W = {"wte": sds((GEN_V, GEN_E), jnp.float32), "wpe": like(W1["wpe"]),
         "lnf": tuple(like(a) for a in W1["lnf"]),
         "blocks": [tuple(like(a) for a in W1["blocks"][0])
                    for _ in range(GEN_L)]}
    pool = sds((GEN_L, num_pages, GEN_PAGE, 1664), jnp.float32)
    path = fam.decode_attention(ecfg, 1, (pool, pool))
    assert path == "kernel"
    fns = fam.build(ProgramContext(ecfg, 1, None, 2, False, path, W, {}))
    key = jax.eval_shape(lambda: jax.random.key_data(jax.random.key(0)))
    lowered = jit_program(
        with_step_inputs(fns["decode"]), "decode",
        (Format(Layout.AUTO, one_chip),) * 2).lower(
        W, pool, pool, *step_inputs(sds, GEN_SLOTS, GEN_PP, key))
    return lowered, lowered.compile()


def test_the_row_kernel_is_traced_once_for_the_48_layers(
        one_chip, uncached, monkeypatch):
    """gpt2-xl's decode program at the re-based 320 pages with the
    fused-row kernel: ONE lowered body of `row_decode_attention`
    (a `jax.jit` of its own, the layer a scalar operand: set-up time is an
    end-to-end metric), 48 custom calls of that name under the layers'
    `attn` scopes over the whole pools in place, no ownership mask, no
    pool copied, the pools in their default layout, and temporaries no
    larger than the pool-dense program's at the same pool (the CPU
    backend's side of the rule, compiled here too) but for where the
    scheduler puts the tied head's transposed `wte`."""
    from paddle_tpu.device import layout_name

    lowered, decode = xl_kernel_decode(one_chip, monkeypatch, GEN_CELL_PAGES)
    assert lowered.as_text().count("row_decode_attention") == 1
    text = decode.as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "row_decode_attention" in ln]
    assert len(calls) == GEN_L
    assert all(re.search(r"layer_\d+/attn", ln) for ln in calls)
    assert "kv_mask" not in text
    shape = (GEN_L, GEN_CELL_PAGES, GEN_PAGE, 1664)
    assert not whole_pool_copies(decode, shape)
    pool = jax.ShapeDtypeStruct(shape, jnp.float32)
    fmts = decode.input_formats[0][1:3]
    assert [layout_name(f, pool.shape, pool.dtype) for f in fmts] == \
        ["default"] * 2
    # the pool-dense program of the same cell takes 385.2 MB, the kernel's
    # 407.1 MB. Most of either is ONE buffer, the tied head's transposed
    # `wte` (334.6 MB), and what else is live beside it: the pool-dense
    # program copies it at its end, after the layers' scores and masks are
    # gone (2.2 MB beside it); the kernel's, with no such temporaries,
    # copies it at its top, beside the layers' weight prefetches (22.8 MB)
    monkeypatch.undo()
    progs, _, _ = xl_programs(one_chip, GEN_CELL_PAGES, ("decode",))
    dense = progs["decode"].memory_analysis().temp_size_in_bytes
    assert decode.memory_analysis().temp_size_in_bytes <= dense + 24e6


# -- the latent family's decode program (glm-4.7-flash.reasoning-saturated) --
# published widths, 64 experts, vocabulary 154,880, bfloat16; 32 slots, pages
# of 16 tokens, 8,192 pages, 256 table entries
# (benchmark/configs/glm-4.7-flash.json). The closure depends on the widths,
# not on depth, so one dense + one expert layer compile in seconds.
LAT_SLOTS, LAT_PAGE, LAT_PAGES, LAT_PP = 32, 16, 8192, 256


def test_latent_decode_compiles_for_the_v5e_without_a_pool_copy(
        one_chip, uncached, monkeypatch):
    """What the chip's compiler would refuse it refuses here: the grouped
    expert product (`lax.ragged_dot` -> XLA:TPU's own kernel) is traced
    under "default" precision because Mosaic refuses its bfloat16 operands
    under the framework's "highest" pin; a cached row takes 640 lanes so
    that no program copies the whole pool in and out (at 576 each did:
    1.17 GB of temporaries for `zero_pages` alone). Decode attention is the
    Pallas kernel of `ops/latent_attention_kernel.py` (PR 30), which walks
    each slot's own pages in the pool: one custom call a layer, no gather
    of the slots' tables (a layer's `[32, 4096, 640]` rows were 168 MB and
    most of the program's 0.35 GB of temporaries), and no layer cut out of
    the pool for it either (handed `pool[layer]` the compiler copied the
    layer, 167 MB: the kernel takes the whole pool and the layer's index).
    Behind the engine's jit boundary (`jit_program`, PR 28) the compiler,
    left to choose, keeps the DEFAULT layout for this pool. The rule asks
    the backend, which is the CPU here: the test answers for it."""
    import types

    from jax.experimental.layout import Format, Layout

    from paddle_tpu.device import layout_name
    from paddle_tpu.ops import latent_attention_kernel as lk
    from paddle_tpu.ops import paged_ops
    from paddle_tpu.serving.generation import (jit_program,
                                               with_step_inputs)

    from paddle_tpu.models.glm_moe import (GlmMoeLiteConfig,
                                           glm_weight_shapes)
    from paddle_tpu.serving.decode_family import ProgramContext
    from paddle_tpu.serving.generation import GenerationConfig
    from paddle_tpu.serving.latent_family import LatentFamily

    monkeypatch.setattr(paged_ops, "_pallas_runs", lambda: True)
    monkeypatch.setattr(lk, "_interpret", lambda: False)
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
    assert path == "latent_kernel"
    fns = fam.build(ProgramContext(ecfg, 1, None, 1, False, path, W, {}))
    key = jax.eval_shape(lambda: jax.random.key_data(jax.random.key(0)))
    M = LAT_SLOTS
    decode = jit_program(
        with_step_inputs(fns["decode"]), "decode",
        (Format(Layout.AUTO, one_chip),), counters=True).lower(
        W, pool, *step_inputs(sds, M, LAT_PP, key)).compile()
    fmts = (decode.input_formats[0][1],)
    assert fmts == tuple(decode.output_formats[:1])
    assert layout_name(fmts[0], pool.shape, pool.dtype) == "default"
    zero = jit_program(fns["zero_pages"], "zero_pages", fmts,
                       with_w=False).lower(
        pool, sds((LAT_PP,), "int32")).compile()
    text = decode.as_text()
    for scope in ("layer_1/mla/latent_attend", "layer_1/mla/absorb",
                  "layer_1/mla/latent_write", "layer_1/moe/experts",
                  "layer_1/moe/router", "layer_0/mlp", "lm_head", "sample"):
        assert scope in text, scope
    assert "ragged-dot" in text
    assert "kv_mask" not in text            # no pool-dense ownership mask
    # the kernel by its own name, as a trace shows it: one custom call a
    # layer, under the layer's `latent_attend` scope
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "latent_decode_attention" in ln]
    assert len(calls) == 2
    assert all("/mla/latent_attend" in ln for ln in calls)
    # no copy of the whole pool, in either program
    whole = f"bf16[2,{LAT_PAGES},{LAT_PAGE},640]"
    for t in (text, zero.as_text()):
        assert not [ln for ln in t.splitlines()
                    if f"= {whole}" in ln and " copy(" in ln]
    assert zero.memory_analysis().temp_size_in_bytes < 1 << 20
    # no buffer of slots x entries x page x 640 elements, in any shape: the
    # gathered rows, and (32 x 256 entries = 8,192 pages) one layer of the
    # pool cut out for the kernel
    gathered = LAT_SLOTS * LAT_PP * LAT_PAGE * 640
    shapes = set(re.findall(r"\b(?:bf16|f32)\[[\d,]+\]", text))
    assert whole in shapes
    assert not [s for s in shapes if int(np.prod(
        [int(d) for d in s[s.index("[") + 1:-1].split(",")])) == gathered]
    # 0.35 GB with the gather (PR 27-29); compiled here for the kernel:
    # 4.9 MB
    assert decode.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("dtype, page", [("bfloat16", 16), ("float32", 8),
                                         ("float32", 16)])
def test_the_latent_kernel_compiles_for_the_v5e_at_the_shapes_its_rule_admits(
        one_chip, uncached, monkeypatch, dtype, page):
    """The kernel alone at the cell's widths (32 slots, 20 heads, rows of
    640 lanes, values 512, a table of 256): bfloat16 as served, and the
    float32 pools the rule also admits (true-float32 products, "highest"),
    with the smallest page it admits for each; reading one layer of a whole
    pool in place."""
    from paddle_tpu.ops import latent_attention_kernel as lk
    from paddle_tpu.ops import paged_ops

    monkeypatch.setattr(lk, "_interpret", lambda: False)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    q, table = sds((LAT_SLOTS, 20, 640), dtype), sds((LAT_SLOTS, LAT_PP),
                                                     "int32")
    pool = sds((7, 4096, page, 640), dtype)
    assert paged_ops.paged_latent_kernel_supported(
        q.shape, pool.shape[1:], table.shape, pool.dtype)
    compiled = jax.jit(lambda q, pool, pt, n: lk.latent_decode_attention(
        q, pool, pt, n, 0.0625, 512, layer=3)).lower(
        q, pool, table, sds((LAT_SLOTS,), "int32")).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "latent_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# -- the hybrid family's programs (falcon-h1-34b.chat-saturated) --------------
# widths as published (benchmark/configs/falcon-h1-34b.json), 2 of the 6
# blocks; 96 slots, pages of 16 tokens, 3,456 pages, 96 table entries
HYB_SLOTS, HYB_PAGE, HYB_PAGES, HYB_PP, HYB_L = 96, 16, 3456, 96, 2


def hybrid_decode_program(one_chip, monkeypatch, L):
    """The hybrid family's decode program at the cell's shapes and `L`
    blocks, through the engine's jit boundary with the pools' layout left
    to the compiler: (sds, W, pools, fns, lowered, compiled)."""
    import types

    from jax.experimental.layout import Format, Layout

    from paddle_tpu.models.falcon_h1 import FalconH1Config, fh1_weight_shapes
    from paddle_tpu.ops import latent_attention_kernel, ssm_ops
    from paddle_tpu.serving.decode_family import ProgramContext
    from paddle_tpu.serving.generation import (GenerationConfig, jit_program,
                                               with_step_inputs)
    from paddle_tpu.serving.hybrid_family import HybridFamily

    monkeypatch.setattr(ssm_ops, "_interpret", lambda: False)
    monkeypatch.setattr(latent_attention_kernel, "_interpret", lambda: False)
    monkeypatch.setattr(ssm_ops.jax, "default_backend", lambda: "tpu")
    cfg = FalconH1Config(num_hidden_layers=L)
    fam = HybridFamily(types.SimpleNamespace(config=cfg))
    ecfg = GenerationConfig(max_slots=HYB_SLOTS, page_size=HYB_PAGE,
                            num_pages=HYB_PAGES, pages_per_seq=HYB_PP,
                            prefill_buckets=(1024,), warmup=False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    W = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                               fh1_weight_shapes(cfg))
    M = HYB_SLOTS
    pools = (sds((L, 4, HYB_PAGES, HYB_PAGE, 128), "bfloat16"),
             sds((L, 4, HYB_PAGES, HYB_PAGE, 128), "bfloat16"),
             sds((L, M, 32, 128, 256), "float32"),
             sds((L, 4, M, 5120), "bfloat16"))
    path = fam.decode_attention(ecfg, 1, pools)
    assert path == "kernel"
    assert fam.describe(ecfg, pools) == {"ssm_decode_path": "kernel"}
    fns = fam.build(ProgramContext(ecfg, 1, None, 4, False, path, W, {}))
    key = jax.eval_shape(lambda: jax.random.key_data(jax.random.key(0)))
    lowered = jit_program(
        with_step_inputs(fns["decode"]), "decode",
        tuple(Format(Layout.AUTO, one_chip) for _ in pools),
        counters=True).lower(W, *pools, *step_inputs(sds, M, HYB_PP, key))
    return sds, W, pools, fns, lowered, lowered.compile()


def test_hybrid_programs_compile_for_the_v5e_without_a_pool_copy(
        one_chip, uncached, monkeypatch):
    """The three programs of the hybrid family at the cell's shapes, through
    the engine's jit boundary. Left to choose, the compiler keeps the
    DEFAULT layout for all four pools — what the explicit head index of
    `paged_ops._write_rows` and the K-major convolution window exist to make
    true (with the head axis left as a scatter window it chose
    `[L, N, P, H, D]` for K and V, and held to the default it copied both
    340 MB pools in and out of every program; PR 36) — so no program copies
    a pool. The state update is the Pallas kernel `ssm_decode_update`, one
    custom call a layer over the pool in place; attention is the repo's
    head-pool kernel `head_decode_attention` over 4 K/V heads under 20
    query heads (JAX's paged kernel until PR 37). The rules ask the
    backend, which is the CPU here: the test answers for it."""
    from paddle_tpu.device import layout_name
    from paddle_tpu.serving.generation import jit_program

    L = HYB_L
    sds, W, pools, fns, _, decode = hybrid_decode_program(one_chip,
                                                          monkeypatch, L)
    M = HYB_SLOTS
    fmts = tuple(decode.input_formats[0][1:5])
    assert fmts == tuple(decode.output_formats[:4])
    assert [layout_name(f, p.shape, p.dtype)
            for f, p in zip(fmts, pools)] == ["default"] * 4
    prefill = jit_program(fns["prefill"], "prefill", fmts,
                          slot_state=True).lower(
        W, *pools, sds((HYB_PP,), "int32"), sds((1, 1024), "int32"),
        sds((), "int32"), sds((), "int32")).compile()
    zero = jit_program(fns["zero_pages"], "zero_pages", fmts,
                       with_w=False).lower(
        *pools, sds((HYB_PP,), "int32")).compile()
    text = decode.as_text()
    for scope in ("layer_1/ssm/in_proj", "layer_1/ssm/conv",
                  "layer_1/ssm/state_update", "layer_1/ssm/gate_norm",
                  "layer_1/ssm/out_proj", "layer_1/attn/qkv",
                  "layer_1/attn/rope", "layer_1/attn/attend",
                  "layer_1/attn/out", "layer_1/mlp", "kv_write", "lm_head",
                  "sample"):
        assert scope in text, scope
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "ssm_decode_update" in ln]
    assert len(calls) == HYB_L
    assert all("/ssm/state_update" in ln for ln in calls)
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "head_decode_attention" in ln]
    assert len(calls) == HYB_L
    assert all("/attn/attend" in ln for ln in calls)
    ptext = prefill.as_text()
    for scope in ("layer_1/ssm/scan", "layer_1/ssm/conv", "state_write",
                  "layer_1/attn/attend"):
        assert scope in ptext, scope
    # no copy of a whole pool, in any of the three
    shapes = [f"bf16[{L},4,{HYB_PAGES},{HYB_PAGE},128]",
              f"f32[{L},{M},32,128,256]", f"bf16[{L},4,{M},5120]"]
    for t in (text, ptext, zero.as_text()):
        assert not [ln for ln in t.splitlines() if " copy(" in ln
                    and any(f"= {s}" in ln for s in shapes)]
    assert zero.memory_analysis().temp_size_in_bytes < 1 << 20
    # 0.21 GB at six layers (the step's activations and a layer's slices)
    assert decode.memory_analysis().temp_size_in_bytes < 256 << 20
    assert prefill.memory_analysis().temp_size_in_bytes < 320 << 20


def test_the_head_kernel_is_traced_once_for_the_six_layers(
        one_chip, uncached, monkeypatch):
    """The cell's decode program at its six blocks (PR 37): ONE lowered
    body of `head_decode_attention` (a `jax.jit` of its own, the layer a
    scalar operand: set-up time is an end-to-end metric), six custom calls
    over the whole pools in place, no pool copied at the boundary, and
    temporaries no larger than JAX's paged kernel left them (213.5 MB at
    the parent; 214.8 MB here, the padded queries and results)."""
    _, _, _, _, lowered, decode = hybrid_decode_program(one_chip,
                                                        monkeypatch, 6)
    assert lowered.as_text().count("head_decode_attention") == 1
    text = decode.as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "head_decode_attention" in ln]
    assert len(calls) == 6
    assert not [ln for ln in text.splitlines() if " copy(" in ln
                and f"{HYB_PAGES},{HYB_PAGE},128]" in ln]
    assert decode.memory_analysis().temp_size_in_bytes < 216e6
