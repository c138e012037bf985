"""Splash (segment-aware) attention kernel parity tests (interpreter
mode on CPU).

Guards paddle_tpu/ops/splash_ops.py against the dense segment-masked
reference: fwd + dq/dk/dv parity across multi-segment rows with
NON-tile-aligned segment boundaries, the single-segment degenerate case
(must equal the existing flash kernel), the block-skip bound math, the
splash dispatch gate in F.scaled_dot_product_attention, and the
flag-tunable tile sizes shared with the flash kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.framework.monitor import stat_get
from paddle_tpu.ops import pallas_ops as po
from paddle_tpu.ops import splash_ops as so


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = get_flags(["FLAGS_flash_attention_interpret",
                     "FLAGS_use_flash_attention",
                     "FLAGS_use_splash_attention",
                     "FLAGS_flash_attention_min_seq",
                     "FLAGS_splash_attention_min_seq",
                     "FLAGS_flash_block_q", "FLAGS_flash_block_kv"])
    set_flags({"FLAGS_flash_attention_interpret": True,
               "FLAGS_use_flash_attention": True,
               "FLAGS_use_splash_attention": True,
               "FLAGS_flash_attention_min_seq": 128,
               "FLAGS_splash_attention_min_seq": 128})
    yield
    set_flags(old)


def _mk(shape, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32), dtype)


def _dense_seg_ref(q, k, v, q_seg, kv_seg, causal, scale):
    """Test-local dense reference (independent of the module's) with the
    segment-within-causal mask and zero output for fully-masked rows."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    allowed = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        allowed = allowed & jnp.tril(jnp.ones((Sq, Sk), bool))[None, None]
    p = jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return jnp.where(jnp.any(allowed, -1)[..., None], out, 0.0)


def _segments(S, boundaries):
    """Segment-id row from NON-tile-aligned boundary offsets."""
    seg = np.zeros((S,), np.int32)
    for b in boundaries:
        seg[b:] += 1
    return seg


def _splash(q, k, v, qs, ks, causal, scale):
    seed = jnp.zeros((), jnp.int32)
    return so.splash_attention_raw(q, k, v, qs, ks, seed, causal, scale,
                                   0.0)


# rows mixing segment counts; boundaries deliberately off the 128-tile
# grid (37, 150, 201, ...) and one row whose last segment spans blocks
SEG_LAYOUTS = [
    [(37, 150, 201), (113,)],
    [(5, 130, 140, 250), ()],      # many tiny segments + one-segment row
]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("layout", SEG_LAYOUTS)
def test_splash_forward_parity(causal, layout):
    B, H, S, D = len(layout), 2, 256, 32
    q, k, v = _mk((B, H, S, D), 1), _mk((B, H, S, D), 2), _mk(
        (B, H, S, D), 3)
    seg = jnp.asarray(np.stack([_segments(S, b) for b in layout]))
    scale = 1.0 / D ** 0.5
    out = _splash(q, k, v, seg, seg, causal, scale)
    ref = _dense_seg_ref(q, k, v, seg, seg, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("layout", SEG_LAYOUTS)
def test_splash_grad_parity(causal, layout):
    B, H, S, D = len(layout), 2, 256, 16
    q, k, v = _mk((B, H, S, D), 4), _mk((B, H, S, D), 5), _mk(
        (B, H, S, D), 6)
    seg = jnp.asarray(np.stack([_segments(S, b) for b in layout]))
    scale = 1.0 / D ** 0.5

    def loss_splash(q, k, v):
        return jnp.sum(jnp.sin(_splash(q, k, v, seg, seg, causal, scale)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_dense_seg_ref(q, k, v, seg, seg, causal,
                                              scale)))

    gf = jax.grad(loss_splash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, gr, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{nm} mismatch")


def test_splash_small_blocks_parity():
    """Force 128-tiles so a 256-seq row spans multiple kv blocks and the
    searchsorted bounds actually skip work, then re-check parity (the
    bound math, not just the mask, is under test)."""
    set_flags({"FLAGS_flash_block_q": 128, "FLAGS_flash_block_kv": 128})
    B, H, S, D = 2, 2, 256, 16
    q, k, v = _mk((B, H, S, D), 7), _mk((B, H, S, D), 8), _mk(
        (B, H, S, D), 9)
    seg = jnp.asarray(np.stack([_segments(S, (37, 150, 201)),
                                _segments(S, (128,))]))
    scale = 1.0 / D ** 0.5
    for causal in (False, True):
        out = _splash(q, k, v, seg, seg, causal, scale)
        ref = _dense_seg_ref(q, k, v, seg, seg, causal, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        gf = jax.grad(lambda q, k, v: jnp.sum(
            _splash(q, k, v, seg, seg, causal, scale) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            _dense_seg_ref(q, k, v, seg, seg, causal, scale) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)


def test_single_segment_degenerate_equals_flash():
    """All-zero segment ids == unmasked flash attention: same math, same
    loop bounds — the outputs must agree to flash-kernel precision."""
    B, H, S, D = 2, 2, 256, 32
    q, k, v = _mk((B, H, S, D), 10), _mk((B, H, S, D), 11), _mk(
        (B, H, S, D), 12)
    seg = jnp.zeros((B, S), jnp.int32)
    bias = jnp.zeros((B, S), jnp.float32)
    seed = jnp.zeros((), jnp.int32)
    scale = 1.0 / D ** 0.5
    for causal in (False, True):
        o_s = _splash(q, k, v, seg, seg, causal, scale)
        o_f = po.flash_attention_raw(q, k, v, bias, seed, causal, scale,
                                     0.0)
        np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_f),
                                   rtol=1e-6, atol=1e-6)


def test_fully_masked_row_outputs_zero():
    """A query row whose segment id exists nowhere in kv emits ZEROS
    (not the uniform mix a -1e30 softmax degenerates to) — kernel and
    dense reference agree on the degenerate semantics."""
    B, H, S, D = 1, 1, 128, 8
    q, k, v = _mk((B, H, S, D), 13), _mk((B, H, S, D), 14), _mk(
        (B, H, S, D), 15)
    q_seg = jnp.full((B, S), 5, jnp.int32)
    kv_seg = jnp.full((B, S), 7, jnp.int32)
    out = _splash(q, k, v, q_seg, kv_seg, False, 0.125)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.zeros_like(np.asarray(out)))
    ref = so.sdpa_segment_reference(q, k, v, q_seg, kv_seg, False, 0.125)
    np.testing.assert_array_equal(np.asarray(ref),
                                  np.zeros_like(np.asarray(ref)))


# ---------------------------------------------------------------------------
# block-skip bounds
# ---------------------------------------------------------------------------

def _brute_bounds(q_seg, kv_seg, block_q, block_k, causal):
    """Needed kv-block span per q block from the full allowed matrix."""
    B, Sq = q_seg.shape
    Sk = kv_seg.shape[1]
    allowed = q_seg[:, :, None] == kv_seg[:, None, :]
    if causal:
        allowed &= np.tril(np.ones((Sq, Sk), bool))[None]
    nqb = Sq // block_q
    spans = np.zeros((B, nqb, 2), np.int64)
    for b in range(B):
        for i in range(nqb):
            cols = np.flatnonzero(
                allowed[b, i * block_q:(i + 1) * block_q].any(axis=0))
            if len(cols):
                spans[b, i] = (cols[0] // block_k,
                               cols[-1] // block_k + 1)
    return spans


@pytest.mark.parametrize("causal", [False, True])
def test_block_bounds_cover_and_skip(causal):
    S, bq, bk = 512, 128, 128
    rows = [_segments(S, (37, 150, 201, 430)),
            _segments(S, (250, 260)), _segments(S, ())]
    seg = np.stack(rows)
    kv_lo, kv_hi, q_lo, q_hi = (np.asarray(a) for a in so._block_bounds(
        jnp.asarray(seg), jnp.asarray(seg), bq, bk, causal))
    spans = _brute_bounds(seg, seg, bq, bk, causal)
    # every needed block is inside the computed span (correctness)...
    assert (kv_lo <= spans[:, :, 0]).all()
    assert (kv_hi >= spans[:, :, 1]).all()
    # ...and the multi-segment layouts genuinely skip blocks (the win)
    nkb = S // bk
    visited = int((kv_hi - kv_lo).sum())
    full = seg.shape[0] * (S // bq) * nkb
    assert visited < full
    # transposed bounds: q span of every kv block covers the transpose
    spans_t = _brute_bounds(seg, seg, bk, bq, False) if not causal else None
    if causal:
        # causal floor: kv block kb is never visited by q blocks before
        # the diagonal
        for kb in range(nkb):
            assert (q_lo[:, kb] >= (kb * bk) // bq).all()
    else:
        assert (q_lo <= spans_t[:, :, 0]).all()
        assert (q_hi >= spans_t[:, :, 1]).all()


# ---------------------------------------------------------------------------
# dispatch gate + flags
# ---------------------------------------------------------------------------

def test_splash_supported_gates():
    assert so.splash_supported((2, 2, 256, 32), min_seq=128)
    assert not so.splash_supported((2, 2, 256, 32), min_seq=512)
    # strict self-attention: S_q != S_kv refused
    assert not so.splash_supported((2, 2, 256, 32), (2, 2, 128, 32),
                                   (2, 2, 128, 32), min_seq=128)
    # alignment / head-dim rules carried over from flash
    assert not so.splash_supported((2, 2, 200, 32), min_seq=128)
    assert not so.splash_supported((2, 2, 256, 12), min_seq=128)
    # reads FLAGS_splash_attention_min_seq when min_seq omitted
    set_flags({"FLAGS_splash_attention_min_seq": 512})
    assert not so.splash_supported((2, 2, 256, 32))
    assert so.splash_supported((2, 2, 512, 32))


def test_functional_segment_dispatch_and_counter():
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import Tensor
    B, H, S, D = 2, 2, 256, 32
    q, k, v = (Tensor(_mk((B, H, S, D), s)) for s in (16, 17, 18))
    seg = np.stack([_segments(S, (100,)), _segments(S, (37, 201))])
    n0 = stat_get("STAT_splash_dispatches")
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                         segment_ids=Tensor(seg))
    assert stat_get("STAT_splash_dispatches") == n0 + 1
    ref = _dense_seg_ref(q._value, k._value, v._value, jnp.asarray(seg),
                         jnp.asarray(seg), True, 1.0 / D ** 0.5)
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_functional_segment_dense_fallback_below_min_seq():
    """Short packed rows ride the dense segment-masked fallback — same
    numbers, no splash dispatch."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import Tensor
    set_flags({"FLAGS_splash_attention_min_seq": 512})
    B, H, S, D = 1, 2, 128, 16
    q, k, v = (Tensor(_mk((B, H, S, D), s)) for s in (19, 20, 21))
    seg = np.stack([_segments(S, (50, 90))])
    n0 = stat_get("STAT_splash_dispatches")
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                         segment_ids=Tensor(seg))
    assert stat_get("STAT_splash_dispatches") == n0  # dense path
    ref = _dense_seg_ref(q._value, k._value, v._value, jnp.asarray(seg),
                         jnp.asarray(seg), True, 1.0 / D ** 0.5)
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sdpa_positional_name_compat():
    """The reference-compatible positional contract (..., training,
    name) must survive the segment_ids addition — name stays the 8th
    positional parameter."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import Tensor
    q = Tensor(_mk((1, 1, 128, 8), 30))
    out = F.scaled_dot_product_attention(q, q, q, None, 0.0, False, True,
                                         "attn1")
    assert tuple(out.shape) == (1, 1, 128, 8)


def test_segment_ids_exclusive_with_attn_mask():
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import Tensor
    q = Tensor(_mk((1, 1, 128, 8), 22))
    mask = Tensor(np.zeros((1, 1, 1, 128), np.float32))
    seg = Tensor(np.zeros((1, 128), np.int32))
    with pytest.raises(ValueError, match="mutually exclusive"):
        F.scaled_dot_product_attention(q, q, q, attn_mask=mask,
                                       segment_ids=seg)


def test_non_monotonic_segment_ids_rejected():
    seg_bad = np.asarray([[0, 1, 0, 1] * 32], np.int32)
    q = _mk((1, 1, 128, 8), 23)
    with pytest.raises(ValueError, match="NON-DECREASING"):
        so.splash_attention(q, q, q, seg_bad, seg_bad)


def test_pick_blocks_reads_flags():
    assert po._pick_blocks(1024, 1024) == (512, 512)  # sweep default
    set_flags({"FLAGS_flash_block_q": 256, "FLAGS_flash_block_kv": 128})
    assert po._pick_blocks(1024, 1024) == (256, 128)
    # preference larger than the seq clamps to what divides it
    set_flags({"FLAGS_flash_block_q": 1024, "FLAGS_flash_block_kv": 1024})
    assert po._pick_blocks(512, 512) == (512, 512)
    assert po._pick_blocks(1024, 2048) == (1024, 1024)
    set_flags({"FLAGS_flash_block_q": 200})
    with pytest.raises(ValueError, match="multiples of 128"):
        po._pick_blocks(512, 512)


# ---------------------------------------------------------------------------
# shard_map threading (SNIPPETS [1] pattern)
# ---------------------------------------------------------------------------

def test_sharded_splash_attention_parity():
    from jax.sharding import Mesh

    from paddle_tpu.parallel.mesh import set_mesh
    from paddle_tpu.parallel.spmd import sharded_splash_attention
    devs = np.array(jax.devices())
    if devs.size < 8:
        pytest.skip("needs the 8-virtual-device test mesh")
    mesh = Mesh(devs[:8].reshape(8), ("dp",))
    try:
        set_mesh(mesh)
        B, H, S, D = 8, 2, 128, 16
        q, k, v = _mk((B, H, S, D), 24), _mk((B, H, S, D), 25), _mk(
            (B, H, S, D), 26)
        seg = jnp.asarray(np.stack([_segments(S, (40, 100))] * B))
        f = sharded_splash_attention(mesh, causal=True)
        out = f(q, k, v, seg, seg)
        ref = _dense_seg_ref(q, k, v, seg, seg, True, 1.0 / D ** 0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        set_mesh(None)
