"""The main path's kernels, compiled at the benchmark's real widths for a
DESCRIBED TPU v5e chip (no chip attached): what the chip's compiler would
refuse, it refuses here, at no chip time. By the rules of the
on-chip-measurement guide: the topology is described inside a module-scoped
fixture that skips, never at import, and every such test lives in this one
file (only one process at a time may load the TPU's library).
"""
import os

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
