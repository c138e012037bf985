"""The head-pool decode kernel (`ops/latent_attention_kernel.head_decode_attention`,
PR 37) in the Pallas interpreter, against the gather (`paged_gather` +
`cached_attention`): grouped queries, both dtypes the rule admits, every
length that ends a page or a round, the whole pools read at a layer, row
isolation, and the decode step of the hybrid family through it.

What only the chip can show (that Mosaic compiles the admitted shapes, and
how fast) is `tests/test_chip_kernels.py`, `tests/test_v5e_compile.py` and
PERF.md section 6."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.monitor import stat_get
from paddle_tpu.ops import paged_ops
from paddle_tpu.ops.latent_attention_kernel import (head_block_pages,
                                                    head_decode_attention)


@pytest.fixture()
def interpreted():
    paddle.set_flags({"FLAGS_flash_attention_interpret": True})
    yield
    paddle.set_flags({"FLAGS_flash_attention_interpret": False})


def _case(H, Hkv, D, P, dtype, seed=0, L=2, PP=4, N=24):
    """Pools [L, Hkv, N, P, D], a query, and five slots whose lengths are
    1, P, P + 1, a round's end plus one (rounds of 2 pages) and the whole
    table; each slot's pages distinct, page 0 the trash page."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((5, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((L, Hkv, N, P, D)), dtype)
    v = jnp.asarray(rng.standard_normal((L, Hkv, N, P, D)), dtype)
    lengths = np.array([1, P, P + 1, 2 * P + 1, PP * P], np.int32)
    pages = rng.permutation(np.arange(1, N))
    pt = np.zeros((5, PP), np.int32)
    for b, n in enumerate(-(-lengths // P)):
        pt[b, :n], pages = pages[:n], pages[n:]
    return q, k, v, jnp.asarray(pt), jnp.asarray(lengths)


def _gather(q, k, v, pt, lengths, scale, heads=None):
    """The plain form: each slot's table gathered whole, query head i over
    K/V head `heads[i]` (i // G unless given), masked softmax, float32."""
    H, Hkv = q.shape[1], k.shape[0]
    heads = np.arange(H) // (H // Hkv) if heads is None else heads
    kb = paged_ops.paged_gather(k, pt)[:, heads].astype(jnp.float32)
    vb = paged_ops.paged_gather(v, pt)[:, heads].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        return paged_ops.cached_attention(q.astype(jnp.float32), kb, vb,
                                          lengths - 1, scale)


@pytest.mark.parametrize("dtype, P", [(jnp.float32, 8), (jnp.float32, 16),
                                      (jnp.bfloat16, 16)])
@pytest.mark.parametrize("D", [128, 256])
@pytest.mark.parametrize("H, Hkv", [(4, 4), (10, 2), (16, 2)])
def test_the_kernel_is_the_gather(interpreted, H, Hkv, D, dtype, P):
    """Groups of 1, 5 and 8; rounds of 2 pages, so the five lengths end a
    first page, open a second, open a second round, fill the table."""
    q, k, v, pt, lengths = _case(H, Hkv, D, P, dtype, seed=H + D + P)
    scale = D ** -0.5
    got = head_decode_attention(q, k, v, pt, lengths, scale, layer=1,
                                block_pages=2)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _gather(q, k[1], v[1], pt, lengths, scale)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol)


def test_whole_pools_at_a_layer_are_that_layers_pools(interpreted):
    q, k, v, pt, lengths = _case(10, 2, 128, 8, jnp.float32, seed=7, L=3)
    for layer in range(3):
        whole = head_decode_attention(q, k, v, pt, lengths, 0.1, layer=layer,
                                      block_pages=2)
        one = head_decode_attention(q, k[layer], v[layer], pt, lengths, 0.1,
                                    block_pages=2)
        np.testing.assert_array_equal(whole, one)
    # and the derived round (the whole 4-entry table here) agrees with 2
    assert head_block_pages(8, 2, 128, 4, 4) == 4
    np.testing.assert_allclose(
        head_decode_attention(q, k, v, pt, lengths, 0.1, layer=2),
        head_decode_attention(q, k, v, pt, lengths, 0.1, layer=2,
                              block_pages=2), atol=1e-6)


def test_a_non_finite_row_reaches_only_the_slot_that_attends_it(interpreted):
    """NaN in the trash page, in the rows past `pos` of a slot's own last
    page, and in a row that slot 4 attends: slot 4 reads NaN, every other
    slot reads what it reads with clean pools — also where the round's
    buffer still holds slot 4's poisoned rows when a later slot's shorter
    round is copied over it."""
    q, k, v, pt, lengths = _case(10, 2, 128, 8, jnp.float32, seed=3)
    clean = head_decode_attention(q, k, v, pt, lengths, 0.1, layer=0,
                                  block_pages=2)
    k, v = k.at[:, :, 0].set(jnp.nan), v.at[:, :, 0].set(jnp.nan)
    last = pt[2, 1]                       # slot 2 holds 9 rows: 1 of page 2
    k = k.at[0, :, last, 1:].set(jnp.nan)
    v = v.at[0, :, last, 1:].set(jnp.inf)
    k = k.at[0, :, pt[4, 3], 5].set(jnp.nan)  # a row slot 4 attends
    # slot 4 comes last; put it first so its rows are in the buffers when
    # the shorter slots' rounds are copied over them
    order = np.array([4, 0, 1, 2, 3])
    got = head_decode_attention(q[order], k, v, pt[order], lengths[order],
                                0.1, layer=0, block_pages=2)
    got = np.asarray(got)[np.argsort(order)]
    assert np.isnan(got[4]).all()
    assert np.isfinite(got[:4]).all()
    np.testing.assert_allclose(got[:4], clean[:4], atol=1e-6)


def test_a_striped_group_map_is_not_the_kernels(interpreted):
    """`tools/plant_fault.py wrong_group`'s map (query head i over K/V head
    i % Hkv) is not the grouped map i // G the kernel reads: the gather
    through each map, and the kernel through the dispatch with the plant."""
    import importlib.util
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "plant_fault", os.path.join(root, "tools", "plant_fault.py"))
    plant = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plant)
    q, k, v, pt, lengths = _case(10, 2, 128, 8, jnp.float32, seed=5)
    H, Hkv = 10, 2
    pos = lengths - 1
    sound = paged_ops.paged_attention(q, k, v, pt, pos, 0.1, layer=1)
    blocked = _gather(q, k[1], v[1], pt, lengths, 0.1)
    striped = _gather(q, k[1], v[1], pt, lengths, 0.1,
                      heads=np.arange(H) % Hkv)
    np.testing.assert_allclose(sound, blocked, atol=2e-5)
    assert float(jnp.max(jnp.abs(striped - blocked))) > 0.1
    attend = paged_ops.paged_attention
    try:
        plant.wrong_group()
        planted = paged_ops.paged_attention(q, k, v, pt, pos, 0.1,
                                            kv_heads=Hkv, layer=1)
    finally:
        paged_ops.paged_attention = attend
    np.testing.assert_allclose(planted, striped, atol=2e-5)


def test_the_dispatch_takes_the_kernel_where_pallas_runs(interpreted):
    """`paged_attention` with whole pools and a layer: the kernel branch
    under the interpreter (counted), the gather without it, one answer."""
    q, k, v, pt, lengths = _case(10, 2, 128, 16, jnp.bfloat16, seed=9, PP=4)
    pos = lengths - 1
    assert paged_ops.paged_attention_path(
        q.shape, k.shape[1:], pt.shape, k.dtype) == "kernel"
    k0, r0 = (stat_get("STAT_paged_attn_kernel"),
              stat_get("STAT_paged_attn_reference"))
    kern = jax.jit(lambda *a: paged_ops.paged_attention(*a, 0.1, layer=1))(
        q, k, v, pt, pos)
    assert stat_get("STAT_paged_attn_kernel") == k0 + 1
    paddle.set_flags({"FLAGS_flash_attention_interpret": False})
    assert paged_ops.paged_attention_path(
        q.shape, k.shape[1:], pt.shape, k.dtype) == "reference"
    ref = jax.jit(lambda *a: paged_ops.paged_attention(*a, 0.1, layer=1))(
        q, k, v, pt, pos)
    assert stat_get("STAT_paged_attn_reference") == r0 + 1
    assert kern.dtype == ref.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(kern, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


def test_the_hybrid_decode_step_through_the_kernel_is_the_gathers(
        interpreted):
    """A falcon block of 128-wide heads, 10 query heads over 2 K/V heads:
    one decode step of `hybrid_decode` over the same pools, with the kernel
    (the interpreter on) and with the gather (off), gives the same logits
    and the same pools."""
    from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM
    from paddle_tpu.serving.hybrid_family import hybrid_decode

    paddle.seed(37)
    cfg = FalconH1Config.tiny(num_heads=10, num_key_value_heads=2,
                              head_dim=128)
    W = FalconH1ForCausalLM(cfg).decode_weights()
    L, M, P, N, PP = cfg.num_hidden_layers, 3, 8, 16, 4
    rng = np.random.default_rng(11)
    pools = (jnp.asarray(rng.standard_normal((L, 2, N, P, 128)), jnp.float32),
             jnp.asarray(rng.standard_normal((L, 2, N, P, 128)), jnp.float32),
             jnp.asarray(0.1 * rng.standard_normal((L, M) + cfg.state_shape),
                         jnp.float32),
             jnp.asarray(0.1 * rng.standard_normal(
                 (L, cfg.mamba_d_conv, M, cfg.conv_dim)), jnp.float32))
    pt = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 0, 0, 0]], jnp.int32)
    tok = jnp.asarray([3, 5, 7], jnp.int32)
    pos = jnp.asarray([30, 9, 0], jnp.int32)
    active = jnp.ones((M,), bool)

    def step():
        return jax.jit(lambda W, pools: hybrid_decode(
            W, pools, pt, tok, pos, active, cfg, P))(W, pools)

    k0 = stat_get("STAT_paged_attn_kernel")
    kern = step()
    assert stat_get("STAT_paged_attn_kernel") == k0 + L
    paddle.set_flags({"FLAGS_flash_attention_interpret": False})
    ref = step()
    assert stat_get("STAT_paged_attn_kernel") == k0 + L
    np.testing.assert_allclose(kern[0], ref[0], rtol=1e-4, atol=1e-4)
    for a, b in zip(kern[1], ref[1]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_the_tp_wrapper_shards_the_kernel_by_kv_head(interpreted):
    """`sharded_paged_attention` (K/V heads split over a tp mesh, each shard
    dispatching `paged_attention` on its own heads) takes the kernel branch
    on each shard and gives the unsharded answer: 10 query heads over 2 K/V
    heads, one K/V head and its group of 5 a shard."""
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    from jax.sharding import Mesh
    q, k, v, pt, lengths = _case(10, 2, 128, 8, jnp.float32, seed=13)
    pos = lengths - 1
    whole = paged_ops.paged_attention(q, k[0], v[0], pt, pos, 0.1)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    k0 = stat_get("STAT_paged_attn_kernel")
    got = paged_ops.sharded_paged_attention(mesh, 0.1)(q, k[0], v[0], pt, pos)
    assert stat_get("STAT_paged_attn_kernel") == k0 + 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole), atol=1e-5)
