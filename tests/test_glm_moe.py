"""GLM-4.7-Flash (`models/glm_moe.py`) and what it brought: the router, the
grouped experts, latent pools, the latent decode family behind
`serving.GenerationEngine`. CPU, tiny sizes, seeded weights. The comparisons
with the plain float32 reference are in tests/benchmark/test_glm_reference.py.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.models import (GlmMoeLiteConfig, GlmMoeLiteForCausalLM,
                               GPTConfig, GPTForCausalLM)
from paddle_tpu.models import glm_moe
from paddle_tpu.ops import moe_ops, paged_ops
from paddle_tpu.profiler import step_log
from paddle_tpu.serving.kv_cache import PagedKVCache


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(27)
    cfg = GlmMoeLiteConfig.tiny()
    net = GlmMoeLiteForCausalLM(cfg)
    net.eval()
    return cfg, net


def rand(shape, seed, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


# -- the router ---------------------------------------------------------------

def test_the_router_selects_on_s_plus_b_and_weights_by_s():
    """A bias that changes the choice: expert 3 has the lowest score of all
    and is chosen for its bias; its weight is its OWN score over the chosen
    scores' sum, times the scale — the bias is in neither."""
    x = jnp.eye(4, dtype=jnp.float32)[:1]                 # one token
    logits = jnp.asarray([[2.0, 1.0, 0.0, -3.0]])
    w = jnp.zeros((4, 4)).at[0].set(logits[0])            # x . w = logits
    s = jax.nn.sigmoid(logits[0])
    idx0, g0 = moe_ops.moe_route(x, w, jnp.zeros(4), 2, 1.8)
    assert sorted(np.asarray(idx0[0])) == [0, 1]
    idx, g = moe_ops.moe_route(x, w, jnp.asarray([0., 0., 0., 5.]), 2, 1.8)
    assert sorted(np.asarray(idx[0])) == [0, 3]           # s + b chose 3
    by = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(g[0]).tolist()))
    assert by[3] == pytest.approx(float(1.8 * s[3] / (s[0] + s[3])), rel=1e-6)
    assert by[0] == pytest.approx(float(1.8 * s[0] / (s[0] + s[3])), rel=1e-6)
    assert float(g.sum()) == pytest.approx(1.8, rel=1e-6)  # normalised, scaled
    assert g.dtype == jnp.float32 and idx.dtype == jnp.int32


# -- grouped experts ----------------------------------------------------------

def experts(E=6, d=16, f=24, seed=0):
    return (rand((E, d, f), seed, 0.3), rand((E, d, f), seed + 1, 0.3),
            rand((E, f, d), seed + 2, 0.3))


def test_grouped_experts_are_the_plain_loop_with_an_idle_and_a_busy_expert():
    """Expert 5 gets no token, expert 2 gets ALL of them (plus a second
    choice that varies): sorted grouped product == the loop over every
    expert weighted by the gate."""
    T, k = 9, 2
    wg, wu, wd = experts()
    x = rand((T, 16), 7)
    idx = jnp.stack([jnp.full((T,), 2), jnp.asarray([0, 1, 3, 4, 0, 1, 3,
                                                     4, 0])], 1)
    gates = jnp.abs(rand((T, k), 8)) + 0.1
    y, hit = moe_ops.moe_grouped_experts(x, idx, gates, wg, wu, wd)
    want = moe_ops.moe_dense_experts(x, idx, gates, wg, wu, wd)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-5)
    assert int(hit) == 5                                   # 0,1,2,3,4: not 5


def test_a_dead_row_reads_no_expert_and_counts_for_none():
    T, k = 4, 2
    wg, wu, wd = experts()
    x = rand((T, 16), 9)
    idx = jnp.asarray([[0, 1], [2, 3], [4, 5], [4, 5]])
    gates = jnp.ones((T, k))
    live = jnp.asarray([True, True, False, False])
    y, hit = moe_ops.moe_grouped_experts(x, idx, gates, wg, wu, wd, live=live)
    want = moe_ops.moe_dense_experts(x, idx, gates, wg, wu, wd)
    np.testing.assert_allclose(y[:2], want[:2], atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(y[2:], 0.0)     # dead rows: nothing added
    assert int(hit) == 4                          # experts 4 and 5 untouched


# -- latent pools --------------------------------------------------------------
# Two paths, one rule (`paged_ops.paged_latent_path`): the per-slot gather,
# which this backend takes, and the Pallas kernel, which a TPU backend takes
# and which the interpreter runs here under the flash kernels' flag.

@contextlib.contextmanager
def interpreter(on):
    """The flag that lets a Pallas kernel run on this backend, held to `on`."""
    from paddle_tpu.framework.flags import get_flags, set_flags
    old = get_flags(["FLAGS_flash_attention_interpret"])
    set_flags({"FLAGS_flash_attention_interpret": on})
    try:
        yield
    finally:
        set_flags(old)


@pytest.fixture(params=["gather", "kernel"])
def path(request):
    """The same case on both paths. A page of 8 float32 rows is a shape of
    the kernel's rule; the flag is what lets a Pallas kernel run here."""
    with interpreter(request.param == "kernel"):
        yield "latent_" + request.param


def latent_setup(B=4, PP=4, P=8, N=20, R=40, rank=32, seed=0,
                 pos=(5, 16, 0, 31)):
    """Slot 0 ends inside its first page, slot 1 on the first row of its
    third page (17 rows: a page boundary and one), slot 2 is DEAD (every
    entry the trash page 0, pos 0, as the engine parks it), slot 3 fills
    its table. `pos=(0, 15, ...)` gives lengths 1 and 16."""
    rs = np.random.RandomState(seed)
    width = paged_ops.latent_pool_width(R)
    pool = jnp.zeros((N, P, width), jnp.float32)
    pool = pool.at[:, :, :R].set(rs.randn(N, P, R).astype("float32"))
    pt = np.asarray(rs.permutation(np.arange(1, N))[:B * PP], np.int32
                    ).reshape(B, PP)
    pos = np.asarray(pos, np.int32)
    for b_ in range(B):             # past a slot's pages: the trash page
        pt[b_, pos[b_] // P + 1:] = 0
    pt[2] = 0
    q = jnp.asarray(rs.randn(B, 2, R), jnp.float32)
    return q, pool, jnp.asarray(pt), jnp.asarray(pos), rank, P, N


def attend(q, pool, pt, pos, rank, path, **kw):
    assert paged_ops.paged_latent_path(q.shape, pool.shape[-3:], pt.shape,
                                       pool.dtype) == path
    return paged_ops.paged_latent_attention(q, pool, pt, pos, 0.3, rank, **kw)


def plain_attention(q, pool, pt, pos, rank, b_):
    """Slot `b_`'s cached rows in position order, by hand."""
    pool_np, q_np = np.asarray(pool), np.asarray(q)
    rows = np.concatenate([pool_np[int(pg)] for pg in pt[b_]])
    rows = rows[:int(pos[b_]) + 1]
    s = (q_np[b_] @ rows[:, :q_np.shape[-1]].T) * 0.3
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ rows[:, :rank]


@pytest.mark.parametrize("pos", [(5, 16, 0, 31), (0, 15, 0, 8)],
                         ids=["6-17-dead-full", "1-16-dead-9"])
def test_latent_attention_is_plain_attention_over_each_slots_own_rows(
        path, pos):
    q, pool, pt, pos, rank, P, N = latent_setup(pos=pos)
    a = attend(q, pool, pt, pos, rank, path)
    assert a.shape == (4, 2, rank) and a.dtype == jnp.float32
    for b_ in range(4):     # the dead slot attends the trash page's row 0
        np.testing.assert_allclose(
            a[b_], plain_attention(q, pool, pt, pos, rank, b_),
            atol=1e-5, rtol=1e-5)
    assert paged_ops.latent_pool_width(576) == 640
    assert paged_ops.latent_pool_width(640) == 640


def test_the_whole_pool_and_a_layers_index_read_that_layer_in_place(path):
    """`layer` given: the pool is `[L, N, P, Rp]` and no layer is cut out
    for the kernel (compiled for the v5e a slice would be copied, 167 MB a
    layer: tests/test_v5e_compile.py)."""
    q, pool, pt, pos, rank, P, N = latent_setup()
    whole = jnp.stack([pool * 0 + 7.0, pool, pool[::-1]])
    np.testing.assert_array_equal(
        attend(q, whole, pt, pos, rank, path, layer=1),
        attend(q, pool, pt, pos, rank, path))


@pytest.mark.parametrize("block_pages", [1, 2, 4])
def test_the_kernel_carries_the_softmax_across_blocks_of_pages(block_pages):
    """One, two and four pages a round of copies: a slot's rows come in
    4, 2 and 1 rounds, the next slot's first round in flight behind the
    last; a poisoned page nobody holds changes nothing."""
    from paddle_tpu.ops.latent_attention_kernel import latent_decode_attention
    q, pool, pt, pos, rank, P, N = latent_setup()
    free = sorted(set(range(1, N)) - set(np.asarray(pt).ravel().tolist()))
    pool = pool.at[free[0]].set(jnp.nan)
    with interpreter(True):
        a = latent_decode_attention(
            paged_ops._pad_lanes(q, pool.shape[-1]), pool, pt, pos + 1, 0.3,
            rank, block_pages=block_pages)
    for b_ in range(4):
        np.testing.assert_allclose(
            a[b_], plain_attention(q, pool, pt, pos, rank, b_),
            atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("owner", [1, 0])
def test_a_poisoned_latent_page_reaches_its_owner_only(path, owner):
    q, pool, pt, pos, rank, P, N = latent_setup()
    page = int(pt[owner, 0])               # its owner attends it, others not
    pool = pool.at[page].set(jnp.nan)
    out = np.asarray(attend(q, pool, pt, pos, rank, path))
    assert np.isnan(out[owner]).all()
    for other in {0, 1, 3} - {owner}:
        assert np.isfinite(out[other]).all()


@pytest.mark.parametrize("poison", [np.nan, np.inf])
def test_a_poisoned_trash_page_and_rows_past_pos_reach_nobody(path, poison):
    """What a slot's pages hold past `pos`, and the trash page that fills a
    table's tail and takes the dead slots' writes, are READ by a path that
    moves whole pages and attended by nobody: dropped by selection in both
    products, never multiplied by a zero probability."""
    q, pool, pt, pos, rank, P, N = latent_setup()
    clean = np.asarray(attend(q, pool, pt, pos, rank, path))
    bad = pool.at[0, 1:].set(poison)    # row 0 is the dead slot's own row
    for b_ in (0, 1, 3):                # the rest of each live slot's last page
        last, off = int(pt[b_, int(pos[b_]) // P]), int(pos[b_]) % P + 1
        bad = bad.at[last, off:].set(poison)
    out = np.asarray(attend(q, bad, pt, pos, rank, path))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)
    # the trash page's first row is the one row a dead slot attends: it
    # reaches the dead slot, whose result nobody reads, and no live one
    out = np.asarray(attend(q, bad.at[0, 0].set(poison), pt, pos, rank, path))
    assert np.isnan(out[2]).all()
    np.testing.assert_array_equal(out[[0, 1, 3]], clean[[0, 1, 3]])


@pytest.mark.parametrize("shapes, why", [
    (dict(P=4), "a page of 4 rows is no whole sublane tile"),
    (dict(P=8, dtype="bfloat16"), "bfloat16 rows pack 16 to a tile"),
    (dict(Rp=576), "a row of 576 lanes is no whole lane tile"),
    (dict(PP=192), "128 pages a round do not divide a table of 192"),
    (dict(dtype="int8"), "int8 rows"),
    (dict(R=700), "a query wider than the row"),
])
def test_the_kernels_rule_refuses(shapes, why):
    from paddle_tpu.ops.latent_attention_kernel import latent_block_pages
    args = dict(B=4, H=2, R=40, N=64, P=8, Rp=128, PP=16, dtype="float32")

    def rule(a):
        return ((a["B"], a["H"], a["R"]), (a["N"], a["P"], a["Rp"]),
                (a["B"], a["PP"]), jnp.dtype(a["dtype"]))
    assert paged_ops.paged_latent_kernel_supported(*rule(args))
    assert not paged_ops.paged_latent_kernel_supported(
        *rule(dict(args, **shapes))), why
    # the benchmark's shapes: 32 pages = 512 rows = 640 KiB a round
    assert latent_block_pages(16, 640, 2, 256) == 32
    assert paged_ops.paged_latent_kernel_supported(
        (32, 20, 576), (8192, 16, 640), (32, 256), jnp.bfloat16)
    # and the backend's half of the rule: no Pallas kernel runs here
    # unless the interpreter's flag is on
    for on, want in ((False, "latent_gather"), (True, "latent_kernel")):
        with interpreter(on):
            assert paged_ops.paged_latent_path(*rule(args)) == want
            assert paged_ops.paged_latent_path(
                *rule(dict(args, **shapes))) == "latent_gather"


def test_a_latent_cache_is_one_pool_without_a_head_axis():
    c = PagedKVCache.described([((3, 8, 16, 640), "bfloat16")], 16, 8, 4)
    assert len(c.pools) == 1 and c.pools[0].shape == (3, 8, 16, 640)
    assert [p["shape"] for p in c.stats()["pools"]] == [[3, 8, 16, 640]]
    assert c.stats()["pool_form"] is None and not c.quantized
    assert c.hbm_bytes() == 3 * 8 * 16 * 640 * 2
    assert c.page_host_bytes() == 3 * 16 * 640 * 2
    c.alloc(7, 40)                                     # same allocator
    assert c.pages_in_use == 3 and c.free_pages == 4
    heads = PagedKVCache(3, 2, 8, 16, 8, 4)
    # 8-wide heads: a token's two heads side by side in one row of a
    # whole lane tile
    assert [p["shape"] for p in heads.stats()["pools"]] == [[3, 8, 16, 128]] * 2
    assert heads.page_host_bytes() == PagedKVCache.page_hbm_bytes(
        3, 2, 8, 16)
    q = PagedKVCache(3, 2, 8, 16, 8, 4, dtype="int8")
    assert q.page_host_bytes() == PagedKVCache.page_hbm_bytes(
        3, 2, 8, 16, dtype="int8")


# -- the model ----------------------------------------------------------------

def test_weight_shapes_are_the_decode_pytree_and_count_the_parameters(tiny):
    cfg, net = tiny
    W, shapes = net.decode_weights(), glm_moe.glm_weight_shapes(cfg)
    assert jax.tree_util.tree_structure(W) == \
        jax.tree_util.tree_structure(shapes)
    for a, b in zip(jax.tree_util.tree_leaves(W),
                    jax.tree_util.tree_leaves(shapes)):
        assert a.shape == b.shape and a.dtype == b.dtype
    # the pytree ALIASES the parameters: no second copy
    assert W["embed"] is net.model.embed_tokens.weight._value
    assert W["layers"][1]["ffn"]["gate"] is \
        net.model.layers[1].mlp.experts.gate_proj._value
    # the published widths at the benchmark's depth: the issue's own count
    full = glm_moe.glm_weight_shapes(GlmMoeLiteConfig(num_hidden_layers=7))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(full))
    assert n - 6 * 64 == 4_530_936_576        # less the six 64-wide biases
    names = list(net.state_dict())
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in names
    assert "lm_head.weight" in names and "model.embed_tokens.weight" in names


def test_absorbed_decode_is_the_expanded_attention(tiny):
    """One layer's attention over 9 cached rows: the decode form (W_UK into
    the query, W_UV after the sum) against the expanded form (keys and values
    from c_kv . W_kvb), same rows, same query."""
    cfg, net = tiny
    lw = net.decode_weights()["layers"][0]
    S, H = 9, cfg.num_heads
    r, nope, rope = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
    rows = rand((S, r + rope), 1)
    q_nope, q_rope = rand((S, H, nope), 2), rand((S, H, rope), 3)
    want = glm_moe._expanded_attend(cfg, S)(0, lw, q_nope, q_rope, rows)
    scale = (nope + rope) ** -0.5
    q = glm_moe.glm_absorb(lw, q_nope, q_rope, cfg)            # [S, H, R]
    s = jnp.einsum("qhr,kr->hqk", q, rows) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    o_lat = jnp.einsum("hqk,kr->qhr", jax.nn.softmax(s, -1), rows[:, :r])
    got = glm_moe._unabsorb(lw, o_lat, cfg)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


def test_rope_rotates_pairs_by_position_and_keeps_the_norm():
    x = rand((5, 3, 8), 4)
    pos = jnp.asarray([0, 1, 7, 100, 4095])
    y = glm_moe.rope_rotate(x, pos, 1e6)
    np.testing.assert_allclose(y[0], x[0], atol=1e-6)          # position 0
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # a rotated q . k depends on the positions' difference alone
    a = glm_moe.rope_rotate(x[:1], jnp.asarray([9]), 1e6)
    b = glm_moe.rope_rotate(x[1:2], jnp.asarray([4]), 1e6)
    c = glm_moe.rope_rotate(x[:1], jnp.asarray([105]), 1e6)
    d = glm_moe.rope_rotate(x[1:2], jnp.asarray([100]), 1e6)
    np.testing.assert_allclose((a * b).sum(-1), (c * d).sum(-1), atol=1e-4)


# -- behind the engine ---------------------------------------------------------

def greedy(net, prompt, n, width=48):
    """`generate`-style greedy decoding of the Layer's FULL forward: every
    token from a whole causal pass over what there is so far (padded on the
    right to one width, which no real position attends to)."""
    seq = list(prompt)
    for _ in range(n):
        ids = np.zeros((1, width), "int32")
        ids[0, :len(seq)] = seq
        lg = net(paddle.to_tensor(ids)).numpy()[0, len(seq) - 1]
        seq.append(int(lg.argmax()))
    return np.asarray(seq, np.int32)


@pytest.mark.parametrize("page_size", [4, 8], ids=["page4", "page8"])
def test_the_engine_serves_the_model_token_for_token(tiny, path, page_size):
    """Pages of 8 rows are a shape of the kernel's rule and pages of 4 are
    not: with the interpreter's flag on the first engine takes the kernel
    and the second the gather, with it off both take the gather; all four
    serve the Layer's own greedy tokens."""
    from paddle_tpu.framework import monitor
    cfg, net = tiny
    rs = np.random.RandomState(3)
    # prompts that end inside a page, on a page boundary (8, 16), fill a
    # bucket (16, 32) and spill into the second bucket
    prompts = [rs.randint(0, cfg.vocab_size, n).astype("int32")
               for n in (5, 8, 13, 16, 3, 20, 32)]
    took = path if page_size == 8 else "latent_gather"
    counters = ("STAT_paged_attn_latent_kernel", "STAT_paged_attn_latent")
    before = [monitor.stat_get(c) for c in counters]
    eng = serving.GenerationEngine(
        net, name="glm_e2e", max_slots=4, page_size=page_size,
        num_pages=256 // page_size, pages_per_seq=64 // page_size,
        prefill_buckets=(16, 32), max_new_tokens=12)
    try:
        st = eng.stats()
        assert st["decode_attention"] == took
        assert st["compiles"] == {"prefill[b=16]": 1, "prefill[b=32]": 1,
                                  "decode[m=4]": 1}
        # one trace of the decode program: its three layers' attention, all
        # on the path the engine names, none on the other
        traced = [monitor.stat_get(c) - b for c, b in zip(counters, before)]
        assert traced == ([3, 0] if took == "latent_kernel" else [0, 3])
        assert [p["shape"] for p in st["pools"]] == [
            [3, 256 // page_size, page_size, 128]]
        streams = [eng.submit_stream(p, max_new_tokens=9) for p in prompts]
        outs = [np.asarray(s.result(120)) for s in streams]
        assert eng.stats()["compiles"] == st["compiles"]    # none after
        recs = step_log.steps_payload()["engines"]["glm_e2e"]["records"]
    finally:
        eng.shutdown()
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, greedy(net, p, 9))
    assert eng.stats()["pages"]["pages_in_use"] == 0
    # the device counters came back with the tokens
    dec = [r for r in recs if r["decode_ms"] > 0]
    E = cfg.n_routed_experts * cfg.num_expert_layers
    assert dec and all(0 < r["experts_hit"] <= E for r in dec)
    assert all(r["latent_rows"] >= r["live"] for r in dec)
    assert any(r["latent_rows"] > 4 * 9 for r in dec)


def test_a_pool_larger_than_the_tables_gives_the_same_tokens(tiny):
    cfg, net = tiny
    p = np.random.RandomState(5).randint(0, cfg.vocab_size, 11).astype("int32")
    eng = serving.GenerationEngine(
        net, name="glm_gather", max_slots=2, page_size=4, num_pages=64,
        pages_per_seq=8, prefill_buckets=(16,), max_new_tokens=8)
    try:
        assert eng.stats()["decode_attention"] == "latent_gather"
        out = eng.generate(p, max_new_tokens=8)
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(out, greedy(net, p, 8))


def test_a_table_the_block_does_not_divide_takes_the_gather(tiny, path):
    """192 entries of 8 rows: a round of copies would be 128 pages, which
    does not divide the table, so the rule refuses and the engine gathers,
    whether or not a Pallas kernel could run; same tokens."""
    cfg, net = tiny
    p = np.random.RandomState(6).randint(0, cfg.vocab_size, 11).astype("int32")
    eng = serving.GenerationEngine(
        net, name="glm_refused_shape", max_slots=2, page_size=8,
        num_pages=400, pages_per_seq=192, prefill_buckets=(16,),
        max_new_tokens=8)
    try:
        assert eng.stats()["decode_attention"] == "latent_gather"
        out = eng.generate(p, max_new_tokens=8)
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(out, greedy(net, p, 8))


@pytest.mark.parametrize("option, name", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_k=2), "spec_k"),
    (dict(prefill_chunk=8), "prefill_chunk"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(prefix_cache=True, kv_tier=True, kv_tier_host_bytes=1 << 20),
     "prefix_cache"),
    (dict(tp=2), "tensor parallelism"),
])
def test_options_the_latent_family_does_not_build_raise_by_name(
        tiny, option, name):
    cfg, net = tiny
    with pytest.raises(InvalidArgumentError, match=name):
        serving.GenerationEngine(net, name="glm_refused", max_slots=2,
                                 page_size=4, num_pages=16,
                                 prefill_buckets=(16,), warmup=False,
                                 **option)


def test_a_model_without_a_family_and_a_gpt_with_moe_are_refused_by_name():
    with pytest.raises(InvalidArgumentError, match="decode family"):
        serving.GenerationEngine(paddle.nn.Linear(4, 4), warmup=False)
    moe = GPTForCausalLM(GPTConfig.tiny(use_moe=True, num_experts=2))
    with pytest.raises(NotImplementedError, match="models/glm_moe.py"):
        serving.GenerationEngine(moe, warmup=False)


def test_a_gpt_engine_counts_nothing_and_keeps_its_head_pools():
    paddle.seed(1)
    net = GPTForCausalLM(GPTConfig.tiny(dropout=0.0))
    net.eval()
    eng = serving.GenerationEngine(net, name="gpt_fam", max_slots=2,
                                   page_size=4, num_pages=32,
                                   prefill_buckets=(16,), max_new_tokens=4)
    try:
        assert eng._family.name == "gpt" and len(eng._pools()) == 2
        assert eng._kp is eng._pools()[0] and eng._vp is eng._pools()[1]
        eng.generate(np.arange(5, dtype=np.int32), max_new_tokens=4)
        recs = step_log.steps_payload()["engines"]["gpt_fam"]["records"]
    finally:
        eng.shutdown()
    assert all(r["experts_hit"] == 0 and r["latent_rows"] == 0 for r in recs)
