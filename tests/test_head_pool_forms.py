"""The head pools' two forms and the layout contract (PR 28).

64-wide heads (every head width that is not a whole number of 128-lane
tiles) keep their pools as `[L, N, P, H*D]`, a token's heads side by side
in one dense row; 128-wide heads keep `[L, H, N, P, D]`, which JAX's paged
kernel reads in place. `ops/paged_ops.HeadPoolForm` is the one place that
knows where the page axis and the head axis are; every function of
`ops/paged_ops.py` takes either form and every option of the engine is
built over both. The engine's programs take and return the pools in ONE
layout, the one its step program was compiled for, and `stats()["pools"]`
says which.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.ops import paged_ops as po
from paddle_tpu.serving.kv_cache import PagedKVCache

L, H, N, P, D, B, PP, S = 2, 3, 10, 4, 8, 3, 3, 6


HD = (H, D)
ROW = po.HeadPoolForm(H, D).row       # 24 values in one 128-lane tile


def fuse(split):
    """[L, H, N, P, D] -> [L, N, P, row], the heads side by side and zero
    past them (a scale pool [L, H, N] -> [L, N, H])."""
    if split.ndim == 3:
        return jnp.swapaxes(split, 1, 2)
    x = jnp.moveaxis(split, 1, 3)
    x = x.reshape(x.shape[:3] + (-1,))
    return jnp.pad(x, [(0, 0)] * 3 + [(0, ROW - x.shape[-1])])


@pytest.fixture(scope="module")
def data():
    rs = np.random.RandomState(0)
    f32 = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)   # noqa: E731
    table = rs.permutation(np.arange(1, N))[:B * PP].reshape(B, PP)
    return {
        "kp": f32(L, H, N, P, D), "vp": f32(L, H, N, P, D),
        "kq": jnp.asarray(rs.randint(-127, 127, (L, H, N, P, D)), jnp.int8),
        "sc": jnp.asarray(np.abs(rs.randn(L, H, N)) * 0.02, jnp.float32),
        "pt": jnp.asarray(table, jnp.int32),
        "pos": jnp.asarray([5, 9, 2], jnp.int32),
        "q": f32(B, H, D), "row": f32(B, H, D), "rows": f32(L, H, S, D)}


# -- ops/paged_ops.py: the same rows written, the same rows read -------------

def test_the_shape_rule_and_where_the_axes_are():
    """64-wide -> `[L,N,P,H*D]`, 128-wide -> `[L,H,N,P,D]`, whose layer is
    still a shape of the paged kernel: the rule is the head width's, read
    from the shape, and the cache reports it."""
    narrow, wide = po.HeadPoolForm(25, 64), po.HeadPoolForm(16, 128)
    assert po.head_pools_fused(64) and not po.head_pools_fused(128)
    assert not po.head_pools_fused(256) and po.head_pools_fused(96)
    assert narrow.fused and narrow.name == "[L,N,P,H*D]"
    # 1,600 values in 13 whole lane tiles: with a lane-exact row the
    # device's default layout is row-major whatever the number of pages
    assert narrow.used == 1600 and narrow.row == 1664 == ROW * 13
    assert narrow.pool_shape(48, 128, 16) == (48, 128, 16, 1664)
    assert narrow.scale_shape(48, 128) == (48, 128, 25)
    # on a tp mesh every shard's heads take whole tiles of their own
    assert po.HeadPoolForm(4, 16, shards=2).row == 2 * 128
    assert po.HeadPoolForm(12, 64).row == 768 == po.HeadPoolForm(12, 64).used
    assert narrow.page_axis == 1
    assert not wide.fused and wide.name == "[L,H,N,P,D]"
    assert wide.pool_shape(4, 128, 16) == (4, 16, 128, 16, 128)
    assert wide.scale_shape(4, 128) == (4, 16, 128)
    assert wide.page_axis == 2
    # what the shape rules read is (H, N, P, D) for both
    assert narrow.layer_shape((48, 128, 16, 1664)) == (25, 128, 16, 64)
    layer = wide.layer_shape((4, 16, 128, 16, 128))
    assert layer == (16, 128, 16, 128)
    assert po.paged_kernel_supported((8, 16, 128), layer, (8, 64))
    assert po.paged_kernel_supported((8, 16, 128), layer, (8, 64),
                                     jnp.bfloat16)
    # a split layer of 8-row pages is whole tiles of float32, not bfloat16
    assert not po.paged_kernel_supported(
        (8, 16, 128), wide.layer_shape((4, 16, 128, 8, 128)), (8, 64),
        jnp.bfloat16)
    assert not po.paged_kernel_supported(
        (16, 25, 64), narrow.layer_shape((48, 128, 16, 1664)), (16, 64))
    # the head axis a tp mesh shards: of a pool, a scale pool, one page
    # cut out of either, and a chunk of pages stacked in front
    assert tuple(narrow.spec(4)) == (None, None, None, "tp")
    assert tuple(narrow.spec(3)) == (None, None, "tp")
    assert tuple(narrow.spec(4, lead=1)) == (None, None, None, "tp")
    assert tuple(wide.spec(5)) == (None, "tp", None, None, None)
    assert tuple(wide.spec(4)) == (None, "tp", None, None)
    assert tuple(wide.spec(5, lead=1)) == (None, None, "tp", None, None)
    # the caches follow the rule, and say so
    c64 = PagedKVCache(2, 4, 64, 16, 8, 4)
    c128 = PagedKVCache(2, 2, 128, 16, 8, 4, dtype="int8")
    assert c64.stats()["pool_form"] == "[L,N,P,H*D]"
    assert c64.k_pages.shape == (2, 8, 16, 256)
    assert PagedKVCache(2, 25, 64, 16, 8, 4).k_pages.shape == (2, 8, 16, 1664)
    assert c128.stats()["pool_form"] == "[L,H,N,P,D]"
    assert c128.k_pages.shape == (2, 2, 8, 16, 128)
    assert c128.k_scales.shape == (2, 2, 8)


def test_on_a_tpu_backend_wide_heads_still_take_the_kernel(monkeypatch):
    wide = po.HeadPoolForm(16, 128)
    layer = wide.layer_shape(wide.pool_shape(4, 128, 16))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert po.paged_attention_path((8, 16, 128), layer, (8, 64)) == "kernel"
    # narrow heads take the fused rows' kernel there, at any pool
    # size; int8 rows stay on the gather, and a page of 8 bfloat16 rows (no
    # whole sublane tile) stays pool-dense
    narrow = po.HeadPoolForm(25, 64)
    for pages in (128, 320, 2048):
        layer = narrow.layer_shape(narrow.pool_shape(48, pages, 16))
        assert po.paged_attention_path((16, 25, 64), layer,
                                       (16, 64)) == "kernel"
    assert po.paged_attention_path((16, 25, 64), layer, (16, 64),
                                   jnp.int8) == "reference"
    layer = narrow.layer_shape(narrow.pool_shape(48, 128, 8))
    assert po.paged_attention_path((16, 25, 64), layer, (16, 128),
                                   jnp.bfloat16) == "pool"


def test_gathers_read_the_same_rows_from_either_form(data):
    kp, kq, sc, pt = data["kp"], data["kq"], data["sc"], data["pt"]
    np.testing.assert_array_equal(
        po.paged_gather(kp[1], pt), po.paged_gather(fuse(kp)[1], pt, HD))
    np.testing.assert_array_equal(
        po.paged_gather_layers(kp, pt[0]),
        po.paged_gather_layers(fuse(kp), pt[0], heads=HD))
    np.testing.assert_array_equal(
        po.paged_gather_quantized(kq[0], sc[0], pt),
        po.paged_gather_quantized(fuse(kq)[0], fuse(sc)[0], pt, heads=HD))
    np.testing.assert_array_equal(
        po.paged_gather_layers(kq, pt[0], sc),
        po.paged_gather_layers(fuse(kq), pt[0], fuse(sc), heads=HD))


def test_writes_put_the_same_rows_into_either_form(data):
    kp, pt, pos = data["kp"], data["pt"], data["pos"]
    pid, off = po.page_rows_for_positions(pt, pos, P)
    np.testing.assert_array_equal(
        fuse(po.paged_write(kp, 1, pid, off, data["row"])),
        po.paged_write(fuse(kp), 1, pid, off, data["row"]))
    pids, offs = po.page_rows_for_positions(pt[0], jnp.arange(S), P)
    np.testing.assert_array_equal(
        fuse(po.paged_write(kp, None, pids, offs, data["rows"])),
        po.paged_write(fuse(kp), None, pids, offs, data["rows"]))


@pytest.mark.parametrize("requant", [False, True])
def test_quantized_writes_agree_page_for_page_and_scale_for_scale(
        data, requant):
    kq, sc, pt, pos = data["kq"], data["sc"], data["pt"], data["pos"]
    pids, offs = po.page_rows_for_positions(pt[0], jnp.arange(S), P)
    p1, s1 = po.paged_write_quantized(kq, sc, None, pids, offs,
                                      data["rows"], requant=requant)
    p2, s2 = po.paged_write_quantized(fuse(kq), fuse(sc), None, pids, offs,
                                      data["rows"], requant=requant)
    np.testing.assert_array_equal(fuse(p1), p2)
    np.testing.assert_array_equal(fuse(s1), s2)
    pid, off = po.page_rows_for_positions(pt, pos, P)
    p1, s1 = po.paged_write_quantized(kq, sc, 1, pid, off, data["row"])
    p2, s2 = po.paged_write_quantized(fuse(kq), fuse(sc), 1, pid, off,
                                      data["row"])
    np.testing.assert_array_equal(fuse(p1), p2)
    np.testing.assert_array_equal(fuse(s1), s2)


def test_attention_is_the_same_over_either_form(data):
    kp, vp, kq, sc = data["kp"], data["vp"], data["kq"], data["sc"]
    q, pt, pos = data["q"], data["pt"], data["pos"]
    mask = po.paged_pool_mask(pt, pos, N, P)
    split = po.paged_pool_attention(q, kp[0], vp[0], mask, 0.3)
    np.testing.assert_allclose(
        split, po.paged_pool_attention(q, fuse(kp)[0], fuse(vp)[0], mask,
                                       0.3), rtol=1e-5, atol=1e-6)
    # through the dispatch (the gather here: 10 pages > 3 x 3 entries),
    # float and int8 pools
    np.testing.assert_allclose(
        po.paged_attention(q, kp[0], vp[0], pt, pos, 0.3),
        po.paged_attention(q, fuse(kp)[0], fuse(vp)[0], pt, pos, 0.3),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        po.paged_attention(q, kq[0], kq[0], pt, pos, 0.3, sc[0], sc[0]),
        po.paged_attention(q, fuse(kq)[0], fuse(kq)[0], pt, pos, 0.3,
                           fuse(sc)[0], fuse(sc)[0]), rtol=1e-5, atol=1e-6)


def test_a_poisoned_row_of_a_fused_pool_fails_its_owner_alone(data):
    kp, vp, q, pt, pos = (data[k] for k in ("kp", "vp", "q", "pt", "pos"))
    mask = po.paged_pool_mask(pt, pos, N, P)
    page = int(pt[1, 0])
    bad = fuse(vp)[0].at[page, 1, D:2 * D].set(jnp.nan)     # head 1 of V
    # (and a NaN in the row's padding lanes reaches nobody)
    bad = bad.at[page, 1, H * D:].set(jnp.nan)
    out = np.asarray(po.paged_pool_attention(q, fuse(kp)[0], bad, mask, 0.3))
    assert np.isnan(out[1, 1]).all()
    assert np.isfinite(np.delete(out, 1, axis=0)).all()
    assert np.isfinite(out[1, [0, 2]]).all()


@pytest.mark.parametrize("form", [po.HeadPoolForm(H, D),
                                  po.HeadPoolForm(2, 128)],
                         ids=["fused", "split"])
def test_whole_pages_by_id_through_the_form(form):
    """Zero, copy-on-write, tier gather and tier write address whole pages
    through the form alone, K/V pools and scale pools alike."""
    rs = np.random.RandomState(3)
    pool = jnp.asarray(rs.randn(*form.pool_shape(L, N, P)), jnp.float32)
    grid = jnp.asarray(rs.rand(*form.scale_shape(L, N)), jnp.float32)
    def take(a, ids):
        return np.take(np.asarray(a), ids, axis=form.page_axis)

    for arr in (pool, grid):
        one = form.pages(arr, 4)                      # page axis cut out
        np.testing.assert_array_equal(one, take(arr, 4))
        some = form.pages(arr, jnp.asarray([4, 7]))   # ... or W in place
        np.testing.assert_array_equal(some, take(arr, [4, 7]))
        cow = form.at_pages(arr, 2).set(form.pages(arr, 4))
        np.testing.assert_array_equal(take(cow, 2), one)
        chunk = jnp.stack([form.pages(arr, 4), form.pages(arr, 7)])
        back = form.at_pages(jnp.zeros_like(arr), jnp.asarray([1, 3])).set(
            form.from_chunk(chunk))
        np.testing.assert_array_equal(take(back, [1, 3]), some)
        # the freed pages and the scratch page, nothing else: a row of
        # [5, 2, scratch, scratch] leaves pages 1, 3, 4, 6.. as they were
        z = form.zero_pages(arr, jnp.asarray([5, 2, 0, 0]), 0)
        rest = [n for n in range(N) if n not in (5, 2, 0)]
        assert not take(z, [5, 2, 0]).any()
        np.testing.assert_array_equal(take(z, rest), take(arr, rest))
        # a full row names no scratch entry: exactly its pages
        z = form.zero_pages(arr, jnp.asarray([5, 2, 7, 1]), 0)
        assert take(z, 0).any() and not take(z, [5, 2, 7, 1]).any()


# -- the engine over both forms ----------------------------------------------

NEW = 6


@pytest.fixture(scope="module", params=["fused", "split"])
def net(request):
    """`fused`: GPTConfig.tiny, 4 heads of 16. `split`: 2 heads of 128, a
    width of the paged kernel. (Seed 0: int8 pages then flip none of these
    prompts' near-ties; over seeds 0-3 they flip 0-4 tokens of 30.)"""
    paddle.seed(0)
    cfg = (GPTConfig.tiny(dropout=0.0) if request.param == "fused" else
           GPTConfig(vocab_size=256, hidden_size=256, num_heads=2,
                     num_layers=2, intermediate_size=256,
                     max_position_embeddings=64, dropout=0.0))
    model = GPTForCausalLM(cfg)
    model.eval()
    model.form = request.param
    return model


def prompts_for(net, n=3, size=9, shared=0):
    rs = np.random.RandomState(7)
    head = rs.randint(0, net.gpt.config.vocab_size, shared)
    return [np.concatenate([head, rs.randint(
        0, net.gpt.config.vocab_size, size - shared)]).astype(np.int32)
        for _ in range(n)]


def greedy(net, prompt):
    return np.asarray(net.generate(paddle.to_tensor(prompt[None]),
                                   max_new_tokens=NEW).numpy())[0]


def serve(net, prompts, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_pages", 32)
    with serving.GenerationEngine(
            net, page_size=4, prefill_buckets=(16,), max_new_tokens=NEW,
            request_timeout_ms=0, name=f"forms_{net.form}", **kw) as eng:
        outs = [eng.generate(p, max_new_tokens=NEW) for p in prompts]
        return outs, eng.stats()


@pytest.mark.parametrize("option", [
    {},
    {"prefix_cache": True},
    {"spec_k": 2},
    {"prefix_cache": True, "kv_tier": True, "kv_tier_host_bytes": 1 << 20,
     "kv_tier_chunk_pages": 2, "prefix_cache_max_pages": 2},
    {"tp": 2},
    {"tp": 2, "prefix_cache": True, "spec_k": 2},
], ids=["plain", "prefix", "verify", "tier", "tp2", "tp2-prefix-verify"])
def test_greedy_tokens_equal_generate_over_either_form(net, option):
    """float32 pages: token for token `GPTModel.generate`, with the prefix
    cache (two prompts share two full pages: tail prefill, and an exact
    repeat forces a copy-on-write split), speculative verify, the host
    tier and a tp=2 mesh."""
    prompts = prompts_for(net, n=3, size=11, shared=8)
    prompts.append(prompts[0][:8])        # exactly two full pages, twice
    prompts.append(prompts[0][:8])
    outs, st = serve(net, prompts, **option)
    want = "[L,N,P,H*D]" if net.form == "fused" else "[L,H,N,P,D]"
    assert st["pages"]["pool_form"] == want
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, greedy(net, p))
    if option.get("prefix_cache"):
        assert st["kv"]["prefix"]["hits"] >= 1
    if option.get("tp"):
        assert st["pages"]["shard_hbm_bytes"] * 2 == st["pages"]["hbm_bytes"]


@pytest.mark.parametrize("option", [
    {"kv_cache_dtype": "bfloat16"},
    {"kv_cache_dtype": "int8"},
    {"kv_cache_dtype": "int8", "prefix_cache": True},
    {"kv_cache_dtype": "int8", "spec_k": 2},
    {"kv_cache_dtype": "int8", "tp": 2},
], ids=["bf16", "int8", "int8-prefix", "int8-verify", "int8-tp2"])
def test_narrower_pages_agree_with_generate_over_either_form(net, option):
    """bfloat16 and int8 pages round what they store, so tokens agree with
    `GPTModel.generate` at 0.9 or better, never bit for bit (another
    compiled program); the engine's own repeats are identical."""
    prompts = prompts_for(net, n=3, size=11, shared=8)
    prompts.append(prompts[0][:8])
    prompts.append(prompts[0][:8])
    outs, st = serve(net, prompts, **option)
    again, _ = serve(net, prompts, **option)
    same = total = 0
    for p, o, o2 in zip(prompts, outs, again):
        np.testing.assert_array_equal(o, o2)
        same += int(np.sum(o[len(p):] == greedy(net, p)[len(p):]))
        total += NEW
    assert same / total >= 0.9, (same, total)
    assert st["pages"]["quantized"] == (option["kv_cache_dtype"] == "int8")
    assert st["pages"]["pages_in_use"] == st["pages"]["cached_pages"]


def test_stats_report_each_pools_shape_layout_and_bytes(net):
    """`stats()["pools"]`: per pool the logical shape, the layout the
    device holds it in and the one the step program was compiled for,
    device and logical bytes; `STAT_kv_cache_hbm_bytes` counts the device
    bytes. The pools lie in their default layout, whatever the compiler
    `preferred` (`_build_programs`)."""
    from paddle_tpu.framework import monitor
    g0 = monitor.stat_get("STAT_kv_cache_hbm_bytes")
    with serving.GenerationEngine(
            net, page_size=4, num_pages=32, max_slots=2,
            prefill_buckets=(16,), kv_cache_dtype="int8",
            name=f"forms_stats_{net.form}") as eng:
        st = eng.stats()
        pools = st["pools"]
        assert len(pools) == 4          # K, V and their scale pools
        form = eng._cache.form
        cfg = net.gpt.config
        assert pools[0]["shape"] == list(form.pool_shape(
            cfg.num_layers, 32, 4)) == list(eng._kp.shape)
        assert pools[2]["shape"] == list(form.scale_shape(
            cfg.num_layers, 32))
        assert [p["dtype"] for p in pools] == ["int8", "int8", "float32",
                                               "float32"]
        for p, a in zip(pools, eng._pools()):
            assert p["layout"] == p["compiled_for"] == "default"
            assert p["device_bytes"] == a.on_device_size_in_bytes()
        # a fused row is whole lane tiles, of which the heads fill a part
        used = form.used / form.row if form.fused else 1
        assert pools[0]["logical_bytes"] == eng._kp.nbytes * used
        assert pools[2]["logical_bytes"] == eng._ks.nbytes
        assert st["pages"]["pools"] == [
            {k: v for k, v in p.items()
             if k not in ("compiled_for", "preferred")}
            for p in pools]
        total = sum(p["device_bytes"] for p in pools)
        assert st["pages"]["hbm_bytes"] == total
        assert monitor.stat_get("STAT_kv_cache_hbm_bytes") - g0 == total
        # the pools the engine holds lie as its programs were compiled to
        # take them, before and after they have run
        eng.generate(np.arange(5, dtype=np.int32), max_new_tokens=3)
        assert [a.format for a in eng._pools()] == list(eng._pool_formats)


def test_a_choice_that_is_not_the_pools_own_layout_is_reported_not_taken():
    """The contract where the compiler, asked, would rather have a pool
    otherwise than it lies: the CPU's picks for the int8 scale pools under
    the verify program a layout that is not their default. The pools
    cannot be moved to it (an executable that the compile cache hands back
    returns default layouts), so the step program is compiled once more
    held to the layout the pools have, every other program pins that,
    `stats()["pools"]` says what was `preferred`, and the tokens are the
    plain int8 engine's."""
    paddle.seed(1)
    model = GPTForCausalLM(GPTConfig.tiny(dropout=0.0))
    model.eval()
    kw = dict(max_slots=2, page_size=4, num_pages=64, prefill_buckets=(16,),
              kv_cache_dtype="int8")
    prompt = np.arange(3, 11, dtype=np.int32)
    with serving.GenerationEngine(model, name="lay_ref", **kw) as ref:
        want = ref.generate(prompt, max_new_tokens=6)
    with serving.GenerationEngine(model, spec_k=3, prefix_cache=True,
                                  name="lay_auto", **kw) as eng:
        pools = eng.stats()["pools"]
        assert eng.stats()["compiles"] == {
            "prefill[b=16]": 1, "prefill_tail[b=16]": 1,
            "verify[k=3]": 1, "cow_copy": 1}
        outs = [eng.generate(prompt, max_new_tokens=6)
                for _ in range(3)]           # a hit, then a CoW split
        assert [a.format for a in eng._pools()] == list(eng._pool_formats)
    for o in outs:
        np.testing.assert_array_equal(o, want)
    assert all(p["layout"] == p["compiled_for"] == "default" for p in pools)
    # what the compiler was free to choose it chose: not all default
    assert {p["preferred"] for p in pools} != {"default"}, pools
