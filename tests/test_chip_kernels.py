"""Compiled-on-TPU kernel checks (`chip` marker; skipped unless
PADDLE_TPU_TEST_ON_CHIP=1 on a TPU host):

    PADDLE_TPU_TEST_ON_CHIP=1 python -m pytest tests -m chip

The dispatch gates (`paged_kernel_supported`, `flash_supported`,
`splash_supported`) promise that every shape they admit COMPILES on the chip.
Only the chip can hold them to it: Mosaic's compile and its VMEM limit do not
exist in interpret mode or in a CPU-side lowering. These are the shapes the
gates were set from (PR 21) — rerun them when a gate is widened or jax moves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.monitor import stat_get
from paddle_tpu.ops import paged_ops
from paddle_tpu.ops import pallas_ops as po
from paddle_tpu.ops import splash_ops as so

pytestmark = pytest.mark.chip


# (sequences, q heads, kv heads, head dim, page size, table pages): PR 21's
# shapes, and since PR 37 (the repo's head-pool kernel) groups of 5 and the
# falcon cell's 96 slots of 20 query heads over 4 K/V heads, a 96-entry
# table; pages of 8 rows are whole tiles of float32 only
PAGED_SHAPES = [
    (8, 8, 8, 128, 16, 8), (8, 8, 8, 256, 16, 8), (8, 8, 8, 512, 16, 8),
    (8, 8, 8, 128, 8, 8), (8, 8, 8, 128, 32, 8), (8, 8, 8, 128, 16, 64),
    (1, 8, 8, 128, 16, 8), (8, 3, 3, 128, 16, 8), (8, 32, 4, 128, 16, 8),
    (8, 8, 4, 128, 16, 8), (4, 20, 4, 128, 16, 96), (96, 20, 4, 128, 16, 96),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("B,H,Hkv,D,P,PP", PAGED_SHAPES)
def test_paged_rule_admits_only_what_compiles(B, H, Hkv, D, P, PP, dtype):
    assert jax.default_backend() == "tpu"
    if dtype == jnp.bfloat16 and P % 16:
        assert not paged_ops.paged_kernel_supported(
            (B, H, D), (Hkv, 72, P, D), (B, PP), dtype)
        pytest.skip("bfloat16 pages of 8 rows: not the kernel's, by rule")
    rng = np.random.RandomState(0)
    N = max(72, PP + 8)
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((2, Hkv, N, P, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((2, Hkv, N, P, D)), dtype)
    table = jnp.asarray(rng.randint(1, N, size=(B, PP)), jnp.int32)
    pos = jnp.asarray(rng.randint(0, PP * P, size=(B,)), jnp.int32)
    scale = 1.0 / D ** 0.5
    assert paged_ops.paged_kernel_supported(q.shape, kp.shape[1:],
                                            table.shape, dtype)
    k0 = stat_get("STAT_paged_attn_kernel")
    out = jax.jit(lambda *a: paged_ops.paged_attention(*a, scale, layer=1))(
        q, kp, vp, table, pos)
    assert stat_get("STAT_paged_attn_kernel") == k0 + 1   # not the reference
    g = H // Hkv
    kd = jnp.repeat(paged_ops.paged_gather(kp[1], table), g, axis=1)
    vd = jnp.repeat(paged_ops.paged_gather(vp[1], table), g, axis=1)
    want = paged_ops.cached_attention(
        q.astype(jnp.float32), kd.astype(jnp.float32),
        vd.astype(jnp.float32), pos, scale)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - want))) < 0.02


# (sequences, heads, head dim, page size, table pages, pool pages) of the
# fused-row kernel: the gpt2-xl cell's 16 slots x 25 heads of 64 in
# 1,664 lanes over 320 pages and a 64-entry table, GPT-2 small's 12 x 64 in
# 768 lanes, a row of 6 x 96 heads in 640 lanes, and 64 heads of 64, the
# most the rule's 1 MiB of block-diagonal queries admits at that width
ROW_SHAPES = [(16, 25, 64, 16, 64, 320), (8, 12, 64, 16, 64, 128),
              (4, 6, 96, 16, 8, 72), (16, 25, 64, 8, 64, 320),
              (16, 64, 64, 16, 64, 128)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,D,P,PP,N", ROW_SHAPES)
def test_row_rule_admits_only_what_compiles(B, H, D, P, PP, N, dtype):
    assert jax.default_backend() == "tpu"
    layer = (H, N, P, D)
    if dtype == jnp.bfloat16 and P % 16:
        assert not paged_ops.paged_row_kernel_supported(
            (B, H, D), layer, (B, PP), dtype)
        pytest.skip("bfloat16 pages of 8 rows: not the kernel's, by rule")
    assert paged_ops.paged_row_kernel_supported((B, H, D), layer, (B, PP),
                                                dtype)
    rng = np.random.RandomState(0)
    R = paged_ops.latent_pool_width(H * D)

    def pool():
        rows = rng.standard_normal((2, N, P, H * D))
        return jnp.asarray(np.pad(rows, [(0, 0)] * 3 + [(0, R - H * D)]),
                           dtype)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp, vp = pool(), pool()
    table = jnp.asarray(rng.randint(1, N, size=(B, PP)), jnp.int32)
    pos = jnp.asarray(rng.randint(0, PP * P, size=(B,)), jnp.int32)
    scale = 1.0 / D ** 0.5
    k0 = stat_get("STAT_paged_attn_kernel")
    out = jax.jit(lambda *a: paged_ops.paged_attention(*a, scale, layer=1))(
        q, kp, vp, table, pos)
    assert stat_get("STAT_paged_attn_kernel") == k0 + 1   # not pool-dense
    kd = paged_ops.paged_gather(kp[1], table, (H, D)).astype(jnp.float32)
    vd = paged_ops.paged_gather(vp[1], table, (H, D)).astype(jnp.float32)
    want = paged_ops.cached_attention(q, kd, vd, pos, scale)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5


def _attend(which, B, H, S, D, dtype):
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
               for _ in range(3))
    bias, seed = jnp.zeros((B, S), jnp.float32), jnp.zeros((), jnp.int32)
    seg = jnp.asarray(np.repeat(np.arange(4), S // 4)[None].repeat(B, 0),
                      jnp.int32)
    scale = 1.0 / D ** 0.5
    if which == "flash":
        def f(q, k, v):
            return po.flash_attention_raw(q, k, v, bias, seed, True, scale,
                                          0.0)
    else:
        def f(q, k, v):
            return so.splash_attention_raw(q, k, v, seg, seg, seed, True,
                                           scale, 0.0)
    out = jax.jit(f)(q, k, v)
    grads = jax.jit(jax.grad(
        lambda *a: f(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(
            q, k, v)
    return (q, k, v, seg, scale), out, grads


# the largest shapes `vmem_resident_ok` admits, per dtype and head dim, and
# a narrow head
@pytest.mark.parametrize("which", ["flash", "splash"])
@pytest.mark.parametrize("S,D,dtype", [
    (4096, 128, jnp.float32), (8192, 128, jnp.bfloat16),
    (4096, 64, jnp.float32), (8192, 64, jnp.bfloat16),
    (512, 40, jnp.float32)])
def test_flash_and_splash_compile_at_the_vmem_bound(which, S, D, dtype):
    itemsize = jnp.dtype(dtype).itemsize
    gate = po.flash_supported if which == "flash" else so.splash_supported
    assert gate((1, 2, S, D), min_seq=512, itemsize=itemsize)
    if S * 128 * itemsize == 2 * 1024 * 1024:        # at the bound
        assert not gate((1, 2, S * 2, D), min_seq=512, itemsize=itemsize)
    _, out, grads = _attend(which, 1, 2, S, D, dtype)
    for x in (out,) + tuple(grads):
        assert bool(jnp.isfinite(x.astype(jnp.float32)).all())


def test_splash_bf16_parity_on_tpu():
    """Compiled splash kernel, forward and dq/dk/dv, against the dense
    segment-masked reference (the flash twin is
    test_flash_attention.py::test_bf16_parity_on_tpu)."""
    (q, k, v, seg, scale), out, grads = _attend("splash", 2, 4, 1024, 64,
                                                jnp.bfloat16)

    def dense(q, k, v):
        return so.sdpa_segment_reference(q, k, v, seg, seg, True, scale)
    want = jax.jit(dense)(q, k, v)
    wgrads = jax.jit(jax.grad(
        lambda *a: dense(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) < 0.05
    for a, b in zip(grads, wgrads):
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))) < 0.3
