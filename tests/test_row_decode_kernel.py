"""The fused-row decode kernel
(`ops/latent_attention_kernel.row_decode_attention`) in the Pallas
interpreter, against the gather (`paged_gather` +
`cached_attention` at "highest"): heads side by side in the lanes of one
row (25 x 64 in 1,664 lanes, as gpt2-xl's pools; 6 x 96 in 640), every
length that ends a page or a round, an inactive slot on the trash page and
a page two slots share, junk in the padding lanes, row isolation, the whole
pools read at a layer, the shape rule and its counter, and the GPT decode
program through it.

What only the chip can show (that Mosaic compiles the admitted shapes, and
how fast) is `tests/test_chip_kernels.py`, `tests/test_v5e_compile.py` and
PERF.md section 6."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.monitor import stat_get
from paddle_tpu.ops import paged_ops
from paddle_tpu.ops.latent_attention_kernel import (row_block_pages,
                                                    row_decode_attention)


@pytest.fixture()
def interpreted():
    paddle.set_flags({"FLAGS_flash_attention_interpret": True})
    yield
    paddle.set_flags({"FLAGS_flash_attention_interpret": False})


def _case(H, D, P, dtype=jnp.float32, seed=0, L=2, PP=4, N=24):
    """Fused pools [L, N, P, row] (zero past H*D), a query, and five slots
    whose lengths are 1, P, P + 1, a round's end plus one (rounds of 2
    pages) and the whole table; each slot's pages distinct, page 0 the
    trash page."""
    rng = np.random.default_rng(seed)
    R = paged_ops.latent_pool_width(H * D)
    q = jnp.asarray(rng.standard_normal((5, H, D)), dtype)

    def pool():
        rows = rng.standard_normal((L, N, P, H * D))
        return jnp.asarray(np.pad(rows, [(0, 0)] * 3 + [(0, R - H * D)]),
                           dtype)
    lengths = np.array([1, P, P + 1, 2 * P + 1, PP * P], np.int32)
    pages = rng.permutation(np.arange(1, N))
    pt = np.zeros((5, PP), np.int32)
    for b, n in enumerate(-(-lengths // P)):
        pt[b, :n], pages = pages[:n], pages[n:]
    return q, pool(), pool(), jnp.asarray(pt), jnp.asarray(lengths)


def _gather(q, k, v, pt, lengths, scale):
    """The plain form over ONE fused layer: each slot's table gathered
    whole as [B, H, T, D], masked softmax, float32 at "highest"."""
    hd = q.shape[1:]
    kb = paged_ops.paged_gather(k, pt, hd).astype(jnp.float32)
    vb = paged_ops.paged_gather(v, pt, hd).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        return paged_ops.cached_attention(q.astype(jnp.float32), kb, vb,
                                          lengths - 1, scale)


@pytest.mark.parametrize("dtype, P", [(jnp.float32, 8), (jnp.float32, 16),
                                      (jnp.bfloat16, 16)])
@pytest.mark.parametrize("H, D", [(25, 64), (6, 96)])
def test_the_kernel_is_the_gather(interpreted, H, D, dtype, P):
    """Rounds of 2 pages, so the five lengths end a first page, open a
    second, open a second round, fill the table."""
    q, k, v, pt, lengths = _case(H, D, P, dtype, seed=H + D + P)
    scale = D ** -0.5
    got = row_decode_attention(q, k, v, pt, lengths, scale, layer=1,
                               block_pages=2)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _gather(q, k[1], v[1], pt, lengths, scale)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol)


def test_an_inactive_slot_and_a_shared_page_read_what_the_gather_reads(
        interpreted):
    """A dead slot's table is all trash page (length 1: its first row); two
    slots share their first page (each at entry 0) and go on through pages
    of their own."""
    q, k, v, pt, lengths = _case(25, 64, 16, seed=3)
    pt = pt.at[0].set(0)                            # slot 0: dead, length 1
    pt = pt.at[3, 0].set(pt[4, 0])                  # slots 3 and 4 share
    got = row_decode_attention(q, k, v, pt, lengths, 0.125, layer=0,
                               block_pages=2)
    np.testing.assert_allclose(got, _gather(q, k[0], v[0], pt, lengths,
                                            0.125), atol=2e-5)


def test_junk_in_the_padding_lanes_adds_nothing(interpreted):
    """Lanes 1,600..1,663 of every row (never written but as zero by the
    engine) hold large finite junk: the answer is the clean pools'."""
    q, k, v, pt, lengths = _case(25, 64, 16, seed=5)
    clean = row_decode_attention(q, k, v, pt, lengths, 0.125, layer=1,
                                 block_pages=2)
    junk = jnp.asarray(np.random.default_rng(6).standard_normal(
        k.shape[:-1] + (64,)) * 1e4, jnp.float32)
    k, v = k.at[..., 1600:].set(junk), v.at[..., 1600:].set(-junk)
    got = row_decode_attention(q, k, v, pt, lengths, 0.125, layer=1,
                               block_pages=2)
    np.testing.assert_array_equal(got, clean)


def test_a_non_finite_row_reaches_only_the_slot_that_attends_it(interpreted):
    """NaN in the trash page, in a neighbour's pages and in the rows past
    `pos` of a slot's own last page: no other slot moves; a NaN in one
    head's lanes of a V row slot 4 attends makes that head of slot 4 NaN.
    Slot 4 comes first, so that its poisoned rows are still in the round's
    buffer when the shorter slots' rounds are copied over them."""
    q, k, v, pt, lengths = _case(25, 64, 16, seed=7)
    order = np.array([4, 0, 1, 2, 3])
    q, pt, lengths = q[order], pt[order], lengths[order]

    def run(k, v):
        return np.asarray(row_decode_attention(q, k, v, pt, lengths, 0.125,
                                               layer=0, block_pages=2))
    clean = run(k, v)
    k, v = k.at[:, 0].set(jnp.nan), v.at[:, 0].set(jnp.nan)
    last = pt[3, 1]                   # the slot of 17 rows: 1 of its page 2
    k = k.at[0, last, 1:].set(jnp.nan)
    v = v.at[0, last, 1:].set(jnp.inf)
    other = np.setdiff1d(np.arange(24), np.asarray(pt))
    k, v = k.at[0, other].set(jnp.nan), v.at[0, other].set(jnp.nan)
    v = v.at[0, pt[0, 2], 5, 3 * 64:4 * 64].set(jnp.nan)  # slot 4, head 3
    got = run(k, v)
    np.testing.assert_allclose(got[1:], clean[1:], atol=1e-6)
    assert np.isnan(got[0, 3]).all()
    heads = np.arange(25) != 3
    np.testing.assert_allclose(got[0, heads], clean[0, heads], atol=1e-6)


def test_whole_pools_at_a_layer_are_that_layers_pools(interpreted):
    q, k, v, pt, lengths = _case(25, 64, 8, seed=9, L=3)
    for layer in range(3):
        whole = row_decode_attention(q, k, v, pt, lengths, 0.1, layer=layer,
                                     block_pages=2)
        one = row_decode_attention(q, k[layer], v[layer], pt, lengths, 0.1,
                                   block_pages=2)
        np.testing.assert_array_equal(whole, one)
    # the derived round (the whole 4-entry table here) agrees with 2
    assert row_block_pages(8, 1664, 4, 4) == 4
    np.testing.assert_allclose(
        row_decode_attention(q, k, v, pt, lengths, 0.1, layer=2),
        row_decode_attention(q, k, v, pt, lengths, 0.1, layer=2,
                             block_pages=2), atol=1e-6)


def test_the_rounds_of_the_cells_shapes():
    """gpt2-xl's pools: a page of K plus V is 213 KB, the round derived
    from it (8 pages, 128 rows: the fastest of 4, 8 and 16 on the chip)
    divides the 64-entry table, and the rule admits the cell's 16 slots at
    any pool size (the kernel reads what the slots hold)."""
    bp = row_block_pages(16, 1664, 4, 64)
    assert bp == 8 and 64 % bp == 0
    ok = paged_ops.paged_row_kernel_supported
    for pages in (128, 320, 4096):
        assert ok((16, 25, 64), (25, pages, 16, 64), (16, 64))
    assert ok((16, 25, 64), (25, 320, 16, 64), (16, 64), jnp.bfloat16)
    assert not ok((16, 25, 64), (25, 320, 16, 64), (16, 64), jnp.int8)
    assert not ok((16, 25, 64), (25, 320, 8, 64), (16, 64), jnp.bfloat16)
    assert not ok((16, 25, 64), (5, 320, 16, 64), (16, 64))   # grouped
    assert not ok((8, 16, 128), (16, 320, 16, 128), (8, 64))  # split form
    assert not ok((4096, 25, 64), (25, 320, 16, 64), (4096, 64))  # VMEM
    assert ok((16, 64, 64), (64, 320, 16, 64), (16, 64))          # 1 MiB
    assert not ok((16, 72, 64), (72, 320, 16, 64), (16, 64))      # queries


def test_the_rule_chooses_by_shape_and_backend(interpreted):
    """Where a Pallas kernel runs (here the interpreter) fused float rows
    take the kernel, counted once a trace; on the CPU they stay pool-dense
    as before (the shapes are still `paged_pool_dense_supported`'s); int8
    rows take the gather everywhere."""
    q, k, v, pt, lengths = _case(25, 64, 16, seed=11, N=20)
    pos = lengths - 1
    path = paged_ops.paged_attention_path
    layer = (25,) + k.shape[1:3] + (64,)
    assert path(q.shape, layer, pt.shape, k.dtype) == "kernel"
    assert path(q.shape, layer, pt.shape, jnp.int8) == "reference"
    f = jax.jit(lambda *a: paged_ops.paged_attention(*a, 0.125, layer=1))
    k0, p0 = stat_get("STAT_paged_attn_kernel"), stat_get("STAT_paged_attn_pool")
    kern = f(q, k, v, pt, pos)
    f(q, k, v, pt, pos + 0)                     # the same program: no trace
    assert stat_get("STAT_paged_attn_kernel") == k0 + 1
    paddle.set_flags({"FLAGS_flash_attention_interpret": False})
    assert path(q.shape, layer, pt.shape, k.dtype) == "pool"
    assert paged_ops.paged_pool_dense_supported(q.shape, layer, pt.shape)
    pool = jax.jit(lambda *a: paged_ops.paged_attention(*a, 0.125, layer=1))(
        q, k, v, pt, pos)
    assert stat_get("STAT_paged_attn_pool") == p0 + 1
    assert stat_get("STAT_paged_attn_kernel") == k0 + 1
    np.testing.assert_allclose(kern, pool, atol=2e-5)


# the GPT family's own plant (its `wrong_page` / `wrong_table` are every
# family's: tests/test_plant_fault.py)
GPT_PLANTS = ("wrong_lanes",)


def test_wrong_lanes_is_another_heads_attention(interpreted):
    """`tools/plant_fault.py wrong_lanes`: through the dispatch, query head
    i reads the lanes of head (i + 1) mod H, which is not the sound
    answer."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "plant_fault", os.path.join(root, "tools", "plant_fault.py"))
    plant = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plant)
    q, k, v, pt, lengths = _case(25, 64, 16, seed=13)
    pos = lengths - 1
    sound = paged_ops.paged_attention(q, k, v, pt, pos, 0.125, layer=0)
    heads = (np.arange(25) + 1) % 25
    want = _gather(q, k[0], v[0], pt, lengths, 0.125)
    kb = paged_ops.paged_gather(k[0], pt, (25, 64))[:, heads]
    vb = paged_ops.paged_gather(v[0], pt, (25, 64))[:, heads]
    with jax.default_matmul_precision("highest"):
        shifted = paged_ops.cached_attention(q, kb, vb, pos, 0.125)
    np.testing.assert_allclose(sound, want, atol=2e-5)
    attend = paged_ops.paged_attention
    try:
        plant.wrong_lanes()
        planted = paged_ops.paged_attention(q, k, v, pt, pos, 0.125, layer=0)
    finally:
        paged_ops.paged_attention = attend
    np.testing.assert_allclose(planted, shifted, atol=2e-5)
    assert float(jnp.max(jnp.abs(planted - sound))) > 0.1


def test_the_gpt_engine_decodes_through_the_kernel(interpreted):
    """`GenerationEngine` over a tiny GPT (4 heads of 16 in one 128-lane
    row): the decode program takes the kernel (one trace a layer, counted),
    holds no ownership mask, and its greedy tokens are `generate`'s."""
    from paddle_tpu import serving
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(39)
    cfg = GPTConfig.tiny()
    net = GPTForCausalLM(cfg)
    net.eval()
    rng = np.random.RandomState(39)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).astype("int64")
               for n in (3, 9, 17)]
    k0 = stat_get("STAT_paged_attn_kernel")
    eng = serving.GenerationEngine(net, max_slots=2, page_size=8,
                                   num_pages=40, prefill_buckets=(16, 32),
                                   name="row_kernel_engine")
    try:
        assert eng.stats()["decode_attention"] == "kernel"
        outs = [f.result(timeout=300) for f in
                [eng.submit(p, max_new_tokens=6) for p in prompts]]
    finally:
        eng.shutdown()
    assert stat_get("STAT_paged_attn_kernel") - k0 == cfg.num_layers
    for out, p in zip(outs, prompts):
        want = net.generate(paddle.to_tensor(p[None]),
                            max_new_tokens=6).numpy()[0]
        np.testing.assert_array_equal(out, want)
