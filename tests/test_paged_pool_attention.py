"""Pool-dense decode attention (`ops/paged_ops.py`, ISSUE 26): all rows
scored against the layer's whole pool under a page-ownership mask.

The oracle is the path it replaces — `paged_gather` + `cached_attention` —
over tables a live engine produces: pages in shuffled physical order, prefix
pages shared by two rows, `pos` on and beside page boundaries, inactive rows
parked on the trash page, junk in free pages. Row isolation is tested on the
K side and on the V side, and the shape rule on both sides of `N = B*PP`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.monitor import stat_get
from paddle_tpu.ops import paged_ops
from paddle_tpu.serving.kv_cache import TRASH_PAGE

H, P, D, PP = 3, 4, 8, 6
N = 20                     # pool pages, trash page included
JUNK = 3.0e4               # finite and large: an unmasked read shows at once


def _case(seed, pos, shared=0, inactive=()):
    """Random pools and a table as the engine lays one out: row b owns
    pages for positions 0..pos[b], drawn from a shuffled free list (so
    physical order is unrelated to logical order), its unused entries
    parked on the trash page; the first `shared` entries of rows 0 and 1
    are the same physical pages; free pages and the trash page hold
    junk."""
    rng = np.random.RandomState(seed)
    B = len(pos)
    free = [n for n in rng.permutation(N) if n != TRASH_PAGE]
    table = np.full((B, PP), TRASH_PAGE, np.int32)
    for b in range(B):
        if b in inactive:
            continue
        for j in range(pos[b] // P + 1):
            table[b, j] = (table[0, j] if b == 1 and j < shared
                           else free.pop())
    k = rng.standard_normal((H, N, P, D)).astype(np.float32)
    v = rng.standard_normal((H, N, P, D)).astype(np.float32)
    for n in free + [TRASH_PAGE]:
        k[:, n] = JUNK * rng.choice([-1.0, 1.0], size=(H, P, D))
        v[:, n] = JUNK * rng.choice([-1.0, 1.0], size=(H, P, D))
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    pos = np.asarray([0 if b in inactive else pos[b] for b in range(B)],
                     np.int32)
    return tuple(jnp.asarray(a) for a in (q, k, v, table, pos))


def _gather_reference(q, k, v, table, pos, scale=0.3):
    return paged_ops.cached_attention(
        q, paged_ops.paged_gather(k, table), paged_ops.paged_gather(v, table),
        pos, scale)


def _pool(q, k, v, table, pos, scale=0.3):
    mask = paged_ops.paged_pool_mask(table, pos, k.shape[1], k.shape[2])
    return paged_ops.paged_pool_attention(q, k, v, mask, scale)


@pytest.mark.parametrize("pos, shared, inactive", [
    ((0, 1, 2, 3), 0, ()),                  # inside the first page
    ((3, 4, 5, 7), 0, ()),                  # on, and one past, a boundary
    ((8, 11, 12, 23), 0, ()),               # first and last offsets; full table
    ((13, 9, 6, 2), 2, ()),                 # rows 0 and 1 share two prefix pages
    ((15, 15, 1, 0), 3, ()),                # identical lengths, three shared
    ((10, 5, 7, 9), 0, (1, 3)),             # inactive rows parked on trash
    ((5, 6, 7, 8), 0, (0, 1, 2, 3)),        # nobody live
], ids=["first-page", "page-boundary", "page-ends", "shared-prefix",
        "shared-equal", "inactive-rows", "all-inactive"])
@pytest.mark.parametrize("seed", [0, 1])
def test_pool_dense_matches_the_gather_reference(seed, pos, shared, inactive):
    q, k, v, table, posa = _case(seed, pos, shared, inactive)
    want = np.asarray(_gather_reference(q, k, v, table, posa))
    got = np.asarray(jax.jit(_pool)(q, k, v, table, posa))
    assert np.isfinite(want).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_mask_is_the_positions_the_reference_leaves_unmasked():
    q, k, v, table, pos = _case(7, (13, 9, 6, 2), shared=2, inactive=(3,))
    mask = np.asarray(paged_ops.paged_pool_mask(table, pos, N, P))
    want = np.zeros((4, N * P), bool)
    for b in range(4):
        for t in range(int(pos[b]) + 1):
            want[b, int(table[b, t // P]) * P + t % P] = True
    np.testing.assert_array_equal(mask, want)
    # the shared pages are owned by both rows, the trash page by the
    # inactive row alone and only at its first offset
    assert (mask[0] & mask[1]).sum() == 2 * P
    assert mask[3].sum() == 1 and mask[3, TRASH_PAGE * P]


@pytest.mark.parametrize("side", ["k", "v"])
@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
def test_poisoned_pages_fail_their_owner_alone(side, poison):
    """Row 2's own pages are poisoned whole (K, or V): row 2 reads
    non-finite in every head, every other row — including the two that
    share prefix pages with each other — is BIT-identical to the clean run,
    although the pool path multiplies every row with every page."""
    q, k, v, table, pos = _case(3, (13, 9, 6, 10), shared=2, inactive=())
    clean = np.asarray(jax.jit(_pool)(q, k, v, table, pos))
    owned = np.asarray(table[2, :int(pos[2]) // P + 1])
    pools = {"k": np.array(k), "v": np.array(v)}
    pools[side][:, owned] = poison
    got = np.asarray(jax.jit(_pool)(q, jnp.asarray(pools["k"]),
                                    jnp.asarray(pools["v"]), table, pos))
    assert not np.isfinite(got[2]).any()
    for b in (0, 1, 3):
        np.testing.assert_array_equal(got[b], clean[b])
    # and the oracle agrees on who fails
    ref = np.asarray(_gather_reference(q, jnp.asarray(pools["k"]),
                                       jnp.asarray(pools["v"]), table, pos))
    assert not np.isfinite(ref[2]).any() and np.isfinite(ref[[0, 1, 3]]).all()


def test_poison_in_one_head_of_v_fails_that_head_only():
    q, k, v, table, pos = _case(4, (13, 9, 6, 10))
    clean = np.asarray(jax.jit(_pool)(q, k, v, table, pos))
    vn = np.array(v)
    vn[1, int(table[0, 1]), 2, 5] = np.nan          # one element: t = 6 of row 0
    got = np.array(jax.jit(_pool)(q, k, jnp.asarray(vn), table, pos))
    assert np.isnan(got[0, 1]).all()
    got[0, 1] = clean[0, 1]
    np.testing.assert_array_equal(got, clean)


def test_nan_in_free_and_trash_pages_reaches_nobody():
    q, k, v, table, pos = _case(5, (13, 9, 6, 10), inactive=(1,))
    clean = np.asarray(jax.jit(_pool)(q, k, v, table, pos))
    used = set(np.asarray(table).ravel().tolist()) - {TRASH_PAGE}
    free = [n for n in range(N) if n not in used and n != TRASH_PAGE]
    kn, vn = np.array(k), np.array(v)
    kn[:, free], vn[:, free] = np.nan, np.nan
    # the trash page past its first row: the table's padding points here
    kn[:, TRASH_PAGE, 1:], vn[:, TRASH_PAGE, 1:] = np.nan, np.nan
    got = np.asarray(jax.jit(_pool)(q, jnp.asarray(kn), jnp.asarray(vn),
                                    table, pos))
    np.testing.assert_array_equal(got, clean)


def test_shape_rule_on_both_sides_of_the_gathers_extent():
    ok = paged_ops.paged_pool_dense_supported
    q, table = (8, 12, 64), (8, 64)                       # B*PP = 512
    assert ok(q, (12, 512, 16, 64), table)                # N = B*PP: on the rule
    assert ok(q, (12, 128, 16, 64), table)
    assert not ok(q, (12, 513, 16, 64), table)            # one page more
    assert not ok(q, (12, 520, 16, 64), table)
    # the benchmark's serve cell: 16 slots, 64 entries, 128 pages of 16 x 64
    assert ok((16, 25, 64), (25, 128, 16, 64), (16, 64))
    # int8 pools dequantize on gather; 128-wide heads are the kernel's
    assert not ok(q, (12, 128, 16, 64), table, jnp.int8)
    assert ok(q, (12, 128, 16, 64), table, jnp.bfloat16)
    assert not ok((8, 16, 128), (16, 128, 16, 128), table)
    assert paged_ops.paged_kernel_supported((8, 16, 128), (16, 128, 16, 128),
                                            table)
    # a shape the head-pool kernel refuses (bfloat16 pages of 8 rows are no
    # whole sublane tile) is the pool path's, one K/V head per query head
    assert not paged_ops.paged_kernel_supported(
        (8, 16, 128), (16, 128, 8, 128), table, jnp.bfloat16)
    assert ok((8, 16, 128), (16, 128, 8, 128), table, jnp.bfloat16)
    # grouped-query pools have no dense reference either
    assert not ok((8, 12, 64), (4, 128, 16, 64), table)
    path = paged_ops.paged_attention_path
    assert path(q, (12, 512, 16, 64), table) == "pool"
    assert path(q, (12, 513, 16, 64), table) == "reference"
    assert path(q, (12, 128, 16, 64), table, jnp.int8) == "reference"
    # off-TPU the kernel's shapes take the gather, as before
    assert path((8, 16, 128), (16, 128, 16, 128), table) == "reference"


def test_dispatch_counts_one_trace_per_program():
    q, k, v, table, pos = _case(6, (13, 9, 6, 10))
    assert paged_ops.paged_pool_dense_supported(q.shape, k.shape, table.shape)
    f = jax.jit(lambda *a: paged_ops.paged_attention(*a, 0.3))
    p0, r0, k0 = (stat_get("STAT_paged_attn_pool"),
                  stat_get("STAT_paged_attn_reference"),
                  stat_get("STAT_paged_attn_kernel"))
    got = np.asarray(f(q, k, v, table, pos))
    f(q, k, v, table, pos + 1)                  # same program: no new trace
    assert stat_get("STAT_paged_attn_pool") == p0 + 1
    assert stat_get("STAT_paged_attn_reference") == r0
    assert stat_get("STAT_paged_attn_kernel") == k0
    np.testing.assert_allclose(
        got, np.asarray(_gather_reference(q, k, v, table, pos)),
        rtol=1e-5, atol=1e-5)
    # a pool one page past B*PP = 24 takes the gather, counted as such
    big = jnp.zeros((H, 4 * PP + 1, P, D), jnp.float32)
    jax.jit(lambda *a: paged_ops.paged_attention(*a, 0.3))(
        q, big, big, table, pos)
    assert stat_get("STAT_paged_attn_pool") == p0 + 1
    assert stat_get("STAT_paged_attn_reference") == r0 + 1


def test_a_mask_built_once_serves_every_layer():
    """What the engine's decode program does: one `paged_pool_mask`, passed
    to every layer's `paged_attention`."""
    q, k, v, table, pos = _case(8, (13, 9, 6, 10), shared=1)

    def two_layers(q, k, v, table, pos):
        mask = paged_ops.paged_pool_mask(table, pos, N, P)
        a = paged_ops.paged_attention(q, k, v, table, pos, 0.3,
                                      pool_mask=mask)
        return paged_ops.paged_attention(a, v, k, table, pos, 0.3,
                                         pool_mask=mask)

    got = np.asarray(jax.jit(two_layers)(q, k, v, table, pos))
    a = _gather_reference(q, k, v, table, pos)
    want = np.asarray(_gather_reference(a, v, k, table, pos))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
