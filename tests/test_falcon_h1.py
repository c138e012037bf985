"""Falcon-H1 (`models/falcon_h1.py`), its state-space ops (`ops/ssm_ops.py`)
and the hybrid decode family (`serving/hybrid_family.py`) on the CPU at small
sizes, seeded weights, LOGITS against the plain reference
(`benchmark/reference/falcon-h1-34b.py`: float32, a positional scan).

The test configuration is NOT `FalconH1Config.tiny()` as it stands: with the
published muP multipliers at a hidden size of 64 the state contributes about
1e-5 of the mixer's output (activations of 0.01, `D x` dominates) and a wrong
state would pass any tolerance. `cfg()` below sets the mixer's multipliers
near 1 and the weights' spread to 0.1, so that activations are of order 1,
and `heavy_state` moves the mixer's scalars to slow decays, large steps and
a small `D`, so that the state is most of `y` (under the convention's
scalars `D x` is nine tenths of it); the other multipliers keep distinct odd
values, so a multiplier left out or applied twice fails test (a).
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM
from paddle_tpu.ops import paged_ops, ssm_ops
from paddle_tpu.serving import hybrid_family
from paddle_tpu.serving.hybrid_family import hybrid_decode
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import plant_fault  # noqa: E402

MULT = dict(embedding_multiplier=1.7, lm_head_multiplier=0.6,
            attention_in_multiplier=0.9, attention_out_multiplier=0.8,
            key_multiplier=0.7, ssm_in_multiplier=1.0,
            ssm_out_multiplier=1.1,
            ssm_multipliers=(1.0, 1.0, 0.9, 1.1, 1.0),
            mlp_multipliers=(0.8, 1.2))
# float32 on both sides, "highest" products: what is left is the order of
# the sums (a chunked scan against a positional one, paged attention against
# dense). Logits have a std of about 0.5 here; 2e-4 of it is some thirty
# times the largest difference seen (6e-6) and two hundred times under what
# a state held in bfloat16 gives (test (b)'s control reads 0.05-0.2)
TOL = 2e-4


def cfg(**kw):
    base = dict(MULT, initializer_range=0.1, mamba_chunk_size=128,
                max_position_embeddings=1024)
    base.update(kw)
    return FalconH1Config.tiny(**base)


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "falcon_h1_reference", os.path.join(
            ROOT, "benchmark", "reference", "falcon-h1-34b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def heavy_state(model):
    """Decays of 0.6-0.98 a step, dt of 0.2-1, D of 0.1: what a sequence
    carries in its state decides its logits."""
    rng = np.random.default_rng(36)
    for blk in model.model.layers:
        sc = blk.mamba.scalars
        H = sc.A_log.shape[0]
        dt = rng.uniform(0.2, 1.0, H)
        sc.A_log.set_value(np.log(rng.uniform(0.05, 0.5, H)))
        sc.dt_bias.set_value(dt + np.log(-np.expm1(-dt)))
        sc.D.set_value(np.full((H,), 0.1))
    return model


@pytest.fixture(scope="module")
def net():
    paddle.seed(36)
    model = heavy_state(FalconH1ForCausalLM(cfg()))
    model.eval()
    return model


def ref_kw(c):
    return dict(MULT, mamba_n_groups=c.mamba_n_groups,
                rope_theta=c.rope_theta, rms_norm_eps=c.rms_norm_eps)


def rel(a, b):
    """Largest difference in units of the reference's logits' std."""
    return float(np.abs(np.asarray(a, np.float64) - np.asarray(b)).max()
                 / np.asarray(b).std())


# -- (a) the model's forward --------------------------------------------------


def test_forward_is_the_references_full_pass(net, ref):
    c = net.config
    ids = np.random.RandomState(0).randint(0, c.vocab_size, (2, 150))
    got = np.asarray(net(paddle.to_tensor(ids.astype(np.int32)))._value)
    want = np.asarray(ref.forward(ref.weights(net.state_dict()), ids,
                                  c.num_heads, **ref_kw(c)))
    assert want.std() > 0.3             # the multipliers leave logits to see
    assert rel(got, want) < TOL


def test_every_multiplier_reaches_the_logits(ref):
    """Each of the twelve is applied, once: moving one moves the logits of
    the model and of the reference alike."""
    ids = np.random.RandomState(1).randint(0, 384, (1, 24))

    def both(kw):
        paddle.seed(36)
        model = heavy_state(FalconH1ForCausalLM(cfg(**kw)))
        model.eval()
        c = model.config
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
        want = np.asarray(ref.forward(ref.weights(model.state_dict()), ids,
                                      c.num_heads, **dict(ref_kw(c), **kw)))
        assert rel(got, want) < TOL, kw
        return want

    base, moved = both({}), []
    for key, val in MULT.items():
        for j in range(len(val) if isinstance(val, tuple) else 1):
            moved.append(both({key: (
                tuple(v * (1.3 if i == j else 1.0)
                      for i, v in enumerate(val))
                if isinstance(val, tuple) else val * 1.3)}))
    assert len(moved) == 14              # 7 scalars, 5 of the mixer, 2 MLP
    assert all(rel(m, base) > 1e-3 for m in moved)


# -- (b) prefill, then decode through pages and slot state -------------------

PAGE, STEPS, BUCKETS = 16, 5, (128, 256, 512)


def engine_for(net, slots=3, **kw):
    opts = dict(max_slots=slots, page_size=PAGE, num_pages=slots * 32 + 1,
                pages_per_seq=32, prefill_buckets=BUCKETS,
                max_new_tokens=STEPS, warmup=False)
    opts.update(kw)
    return serving.GenerationEngine(net, **opts)


def paged_logits(net, lengths, slots, seed=5, state_dtype=None, steps=STEPS):
    """Each prompt prefilled into its slot by the engine's own program, then
    `steps` decode steps on fixed tokens for all of them at once: (the
    prompts with their tokens, logits [n, steps + 1, V])."""
    c = net.config
    rs = np.random.RandomState(seed)
    eng = engine_for(net, slots=max(slots) + 1, name=f"hyb{seed}")
    try:
        M = max(slots) + 1
        pt = np.zeros((M, 32), np.int32)
        first, seqs = {}, {}
        for i, (n, slot) in enumerate(zip(lengths, slots)):
            pt[slot] = eng._cache.alloc(i, n + steps)
            b = eng._bucket_for(n)
            ids = np.zeros((1, b), np.int32)
            ids[0, :n] = seqs[slot] = rs.randint(0, c.vocab_size, n)
            out = eng._prefill_jit(eng._W, *eng._pools(), pt[slot], ids,
                                   np.int32(n), np.int32(slot))
            eng._set_pools(out[:-1])
            first[slot] = np.asarray(out[-1])
        pools = eng._pools()
        active = np.zeros((M,), bool)
        active[list(slots)] = True
        pos0 = np.zeros((M,), np.int32)
        pos0[list(slots)] = lengths
        step = jax.jit(lambda W, pools, tok, pos: hybrid_decode(
            W, pools, jnp.asarray(pt), tok, pos, jnp.asarray(active), c,
            PAGE))
        outs = []
        for k in range(steps):
            tok = np.zeros((M,), np.int32)      # a token a REQUEST
            tok[list(slots)] = rs.randint(0, c.vocab_size, len(slots))
            if state_dtype is not None:
                kp, vp, sp, cp = pools
                pools = (kp, vp, sp.astype(state_dtype).astype(sp.dtype), cp)
            lg, pools, n_slots, rows = step(eng._W, pools, tok, pos0 + k)
            assert int(n_slots) == len(slots)
            assert int(rows) == sum(lengths) + len(slots) * (k + 1)
            outs.append(np.asarray(lg))
            for s in slots:
                seqs[s] = np.append(seqs[s], tok[s])
        logits = np.stack([np.concatenate(
            [first[s][None], np.stack([o[s] for o in outs])]) for s in slots])
        return [seqs[s] for s in slots], logits
    finally:
        eng.shutdown(drain=False)


def reference_logits(net, ref, seqs, lengths, steps=STEPS):
    c = net.config
    W = ref.weights(net.state_dict())
    return np.stack([np.asarray(ref.logits_at(
        W, seq, np.arange(n - 1, n + steps), c.num_heads, **ref_kw(c)))
        for seq, n in zip(seqs, lengths)])


# 1: a lone token; 127 / 128 / 129: either side of a chunk of the scan and
# of a bucket; 300: two chunks and a part in a bucket of 512 (212 padded
# positions whose dt must be 0, the window taken at 300)
LENGTHS = (1, 127, 128, 129, 300)


@pytest.mark.parametrize("length", LENGTHS)
def test_prefill_then_decode_is_the_references_full_pass(net, ref, length):
    seqs, got = paged_logits(net, [length], [1])
    want = reference_logits(net, ref, seqs, [length])
    assert np.isfinite(got).all()
    assert rel(got, want) < TOL


def test_a_bfloat16_state_fails_the_tolerance(net, ref):
    """The control: the same steps with the state pool rounded to bfloat16
    before every step must fail test (b)'s tolerance, by a wide margin."""
    seqs, got = paged_logits(net, [300], [1], state_dtype=jnp.bfloat16)
    want = reference_logits(net, ref, seqs, [300])
    assert rel(got[:, 1:], want[:, 1:]) > 20 * TOL


# -- (c) the chunked scan -----------------------------------------------------


def scan_inputs(S, H=4, P=8, G=2, N=16, seed=0):
    rng = np.random.default_rng(seed)
    f = jnp.float32
    x = jnp.asarray(rng.standard_normal((S, H, P)), f)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((S, H)), f)) * 0.1
    A = -jnp.exp(jnp.asarray(rng.uniform(0, 2.7, (H,)), f))
    B = jnp.asarray(rng.standard_normal((S, G, N)), f)
    C = jnp.asarray(rng.standard_normal((S, G, N)), f)
    return x, dt, A, B, C


@pytest.mark.parametrize("S, chunk", [(384, 128), (64, 128), (256, 32)])
def test_chunked_scan_is_the_positional_scan(S, chunk):
    x, dt, A, B, C = scan_inputs(S)
    y, s = ssm_ops.ssd_chunked_scan(x, dt, A, B, C, chunk=chunk)
    yr, sr = ssm_ops.ssm_scan_reference(x, dt, A, B, C)
    # float32 sums in another order: 1e-5 of outputs of order 1
    np.testing.assert_allclose(y, yr, atol=5e-5)
    np.testing.assert_allclose(s, sr, atol=5e-6)


def test_chunked_scan_carries_a_state_in_and_masks_by_dt():
    x, dt, A, B, C = scan_inputs(384, seed=1)
    y, s = ssm_ops.ssd_chunked_scan(x, dt, A, B, C)
    y1, s1 = ssm_ops.ssd_chunked_scan(x[:128], dt[:128], A, B[:128], C[:128])
    y2, s2 = ssm_ops.ssd_chunked_scan(x[128:], dt[128:], A, B[128:], C[128:],
                                      init_state=s1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), y, atol=2e-5)
    np.testing.assert_allclose(s2, s, atol=5e-6)
    # dt = 0 past position 300: the state is the state AT 300, exactly
    masked = dt.at[300:].set(0)
    _, sm = ssm_ops.ssd_chunked_scan(x, masked, A, B, C)
    _, s300 = ssm_ops.ssm_scan_reference(x[:300], dt[:300], A, B[:300],
                                         C[:300])
    np.testing.assert_allclose(sm, s300, atol=5e-6)


def test_chunked_scan_refuses_a_length_its_chunk_does_not_divide():
    x, dt, A, B, C = scan_inputs(200)
    with pytest.raises(ValueError, match="does not divide"):
        ssm_ops.ssd_chunked_scan(x, dt, A, B, C, chunk=128)


def test_the_convolution_window_is_taken_at_length():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((10, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((6,)), jnp.float32)
    out, win = ssm_ops.causal_conv_window(x, w, b, length=7)
    out7, win7 = ssm_ops.causal_conv_window(x[:7], w, b)
    np.testing.assert_array_equal(out[:7], out7)
    np.testing.assert_array_equal(win, x[3:7])
    np.testing.assert_array_equal(win7, x[3:7])
    # one more position from the carried window: the full pass's row 7
    o8, win8 = ssm_ops.causal_conv_window_step(win[:, None], x[7][None], w, b)
    np.testing.assert_allclose(o8[0], out[7], atol=1e-6)
    np.testing.assert_array_equal(win8[:, 0], x[4:8])
    # a sequence shorter than the window: zero rows in front
    _, win1 = ssm_ops.causal_conv_window(x, w, b, length=1)
    np.testing.assert_array_equal(win1[:3], 0)
    np.testing.assert_array_equal(win1[3], x[0])


# -- (d) the decode kernel ----------------------------------------------------


@pytest.fixture()
def interpreted():
    paddle.set_flags({"FLAGS_flash_attention_interpret": True})
    yield
    paddle.set_flags({"FLAGS_flash_attention_interpret": False})


def test_the_decode_kernel_is_its_jax_numpy_form(interpreted):
    rng = np.random.default_rng(3)
    L, M, H, P, N, G = 2, 3, 16, 128, 128, 2
    f = jnp.float32
    pool = jnp.asarray(rng.standard_normal((L, M, H, P, N)), f)
    decay = jnp.asarray(rng.uniform(0.5, 1, (M, H)), f)
    dtx = jnp.asarray(rng.standard_normal((M, H, P)), f)
    B = jnp.asarray(rng.standard_normal((M, G, N)), f)
    C = jnp.asarray(rng.standard_normal((M, G, N)), f)
    # a dead slot: decay 1, input 0
    decay, dtx = decay.at[2].set(1.0), dtx.at[2].set(0.0)
    assert ssm_ops.ssm_decode_path(pool.shape, pool.dtype, G) == "kernel"
    want_pool, want_y = ssm_ops.ssm_decode_update_reference(
        pool, 1, decay, dtx, B, C)
    got_pool, got_y = ssm_ops.ssm_decode_update(pool, 1, decay, dtx, B, C)
    # float32 products and sums in another order
    np.testing.assert_allclose(got_pool, want_pool, atol=2e-6)
    np.testing.assert_allclose(got_y, want_y, atol=5e-5)
    np.testing.assert_array_equal(got_pool[0], pool[0])     # the other layer
    np.testing.assert_array_equal(got_pool[1, 2], pool[1, 2])  # the dead slot


def test_the_decode_path_is_a_rule_of_shape_and_backend(interpreted):
    f = jnp.float32
    assert ssm_ops.ssm_decode_path((6, 96, 32, 128, 256), f, 2) == "kernel"
    # a state that is not whole (8, 128) tiles, a pool that is not float32,
    # heads that are not whole blocks, a block that would span two groups
    for shape, dtype, groups in [((2, 3, 8, 8, 16), f, 2),
                                 ((2, 3, 16, 128, 128), jnp.bfloat16, 2),
                                 ((2, 3, 12, 128, 128), f, 1),
                                 ((2, 3, 16, 128, 128), f, 4)]:
        assert ssm_ops.ssm_decode_path(shape, dtype, groups) == "reference"
    paddle.set_flags({"FLAGS_flash_attention_interpret": False})
    assert ssm_ops.ssm_decode_path((6, 96, 32, 128, 256), f, 2) == \
        "reference"                     # the CPU backend, no interpreter


# -- (e) state follows the slot ----------------------------------------------


def test_two_requests_that_swap_slots_give_the_same_logits(net):
    seqs_a, a = paged_logits(net, [40, 129], [0, 2], seed=9)
    seqs_b, b = paged_logits(net, [40, 129], [2, 0], seed=9)
    for x, y in zip(seqs_a, seqs_b):
        np.testing.assert_array_equal(x, y)
    # the same programs over other rows of the pools: rounding of another
    # batch order in the step's matmuls, nothing more
    assert rel(a, b) < TOL


# -- (f) through the engine ---------------------------------------------------


def test_continuous_batching_gives_each_requests_own_tokens(net):
    c = net.config
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, c.vocab_size, n).astype(np.int32)
               for n in (5, 140, 17, 3, 300, 64)]
    new = [6, 3, 8, 8, 4, 7]
    eng = engine_for(net, slots=2, name="hyb_batch", max_new_tokens=8,
                     warmup=True)
    try:
        alone = []
        for p, n in zip(prompts, new):     # each served alone
            alone.append(eng.submit(p, max_new_tokens=n).result(120))
        futs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, new)]     # six over two slots
        mixed = [f.result(120) for f in futs]
        st = eng.stats()
    finally:
        eng.shutdown(drain=False)
    for a, m, p, n in zip(alone, mixed, prompts, new):
        assert len(m) == len(p) + n
        np.testing.assert_array_equal(a, m)
    # a freed slot was taken again, and nothing compiled after warm-up
    assert st["prefills"] == 12
    assert st["compiles"] == {"decode[m=2]": 1, "prefill[b=128]": 1,
                              "prefill[b=256]": 1, "prefill[b=512]": 1}
    assert st["decode_attention"] == "reference"
    assert st["ssm_decode_path"] == "reference"
    kinds = [(p["kind"], len(p["shape"])) for p in st["pools"]]
    assert kinds == [("pages", 4), ("pages", 4), ("slots", 5), ("slots", 4)]
    assert st["pools"][2]["dtype"] == "float32"


def test_the_engines_tokens_are_the_references_argmax(net, ref):
    c = net.config
    rs = np.random.RandomState(12)
    prompts = [rs.randint(0, c.vocab_size, n).astype(np.int32)
               for n in (9, 130)]
    eng = engine_for(net, slots=2, name="hyb_ref", warmup=False)
    try:
        outs = [eng.submit(p, max_new_tokens=STEPS).result(120)
                for p in prompts]
        recs = eng._step_log.snapshot()
    finally:
        eng.shutdown(drain=False)
    W = ref.weights(net.state_dict())
    short = ref.token_shortfalls(W, outs, [len(p) for p in prompts],
                                 c.num_heads, **ref_kw(c))
    assert float(np.concatenate(short).max()) == 0.0
    # the records: real prompt tokens of the prefills, the device's counters
    assert sum(r.prefill_tokens for r in recs) == 9 + 130
    steps = [r for r in recs if r.decode_ms > 0]
    assert steps and all(r.state_slots == 1 for r in steps)   # one at a time
    assert max(r.kv_rows for r in steps) == 130 + STEPS - 1


@pytest.mark.parametrize("option, name", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(spec_k=2), "spec_k"),
    (dict(prefix_cache=True, kv_tier=True), "prefix_cache"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(tp=2), "tp > 1"),
    (dict(prefill_buckets=(128, 200)), "multiple of the scan's chunk"),
])
def test_an_option_the_family_does_not_build_is_refused_by_name(net, option,
                                                                name):
    with pytest.raises(InvalidArgumentError, match=name) as e:
        engine_for(net, name="hyb_refused", **option)
    assert "hybrid family" in str(e.value)


def test_a_supervised_restart_rebuilds_a_requests_state(net):
    """A step that dies takes the pools with it; the supervisor's rebuilt
    engine replays the request through prefill, which writes its slot's
    state anew: the tokens are those of an engine that never died."""
    from paddle_tpu.serving import failpoints
    c = net.config
    prompt = np.random.RandomState(13).randint(
        0, c.vocab_size, 140).astype(np.int32)
    opts = dict(max_slots=2, page_size=PAGE, num_pages=65, pages_per_seq=32,
                prefill_buckets=BUCKETS, max_new_tokens=8)
    eng = serving.GenerationEngine(net, name="hyb_sound", **opts)
    try:
        want = eng.submit(prompt, max_new_tokens=8).result(120)
    finally:
        eng.shutdown(drain=False)
    failpoints.reset()
    sup = serving.EngineSupervisor(net, name="hyb_sup", **opts)
    try:
        paddle.set_flags({"FLAGS_failpoints": "decode_step_raise@3"})
        got = sup.submit(prompt, max_new_tokens=8).result(120)
        assert sup.stats()["supervisor"]["restarts"] >= 1
    finally:
        paddle.set_flags({"FLAGS_failpoints": ""})
        failpoints.reset()
        sup.shutdown(drain=False)
    np.testing.assert_array_equal(got, want)


# -- (g) grouped-query pools --------------------------------------------------


def test_the_cache_holds_the_kv_heads_of_grouped_queries():
    from paddle_tpu.ops.paged_ops import HeadPoolForm
    cache = PagedKVCache(6, 20, 128, 16, 9, 4, dtype="bfloat16",
                         num_kv_heads=4,
                         slot_pools=[((6, 3, 32, 128, 256), "float32"),
                                     ((6, 4, 3, 5120), "bfloat16")])
    assert [p.shape for p in cache.pools] == [
        (6, 4, 9, 16, 128), (6, 4, 9, 16, 128), (6, 3, 32, 128, 256),
        (6, 4, 3, 5120)]
    assert (cache.num_heads, cache.num_kv_heads) == (20, 4)
    assert not cache.form.fused and cache.form.heads == 4
    assert cache.form.pool_shape(6, 9, 16) == \
        HeadPoolForm(4, 128).pool_shape(6, 9, 16)
    info = cache.stats()["pools"]
    assert [i["kind"] for i in info] == ["pages", "pages", "slots", "slots"]
    # the gauge counts all four; a page's share of a host tier only K and V
    assert cache.hbm_bytes() == sum(int(p.nbytes) for p in cache.pools)
    assert cache.page_host_bytes() == 2 * 6 * 4 * 16 * 128 * 2
    with pytest.raises(InvalidArgumentError, match="multiple"):
        PagedKVCache(2, 20, 128, 16, 9, 4, num_kv_heads=3)


def test_gpt2s_pools_are_what_they_were():
    cache = PagedKVCache(48, 25, 64, 16, 9, 4)
    assert [p.shape for p in cache.pools] == [(48, 9, 16, 1664)] * 2
    assert cache.num_kv_heads == cache.num_heads == 25
    assert [i["kind"] for i in cache.stats()["pools"]] == ["pages", "pages"]
    wide = PagedKVCache(2, 8, 128, 16, 9, 4)
    assert [p.shape for p in wide.pools] == [(2, 8, 9, 16, 128)] * 2


def test_paged_attention_reads_grouped_queries_through_the_gather():
    """Query head i reads K/V head i // (H / Hkv), split pools and fused."""
    from paddle_tpu.ops import paged_ops
    rng = np.random.default_rng(4)
    B, H, Hkv, N, P, PP = 2, 6, 2, 7, 4, 3
    pt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pos = jnp.asarray([9, 5], jnp.int32)
    for D in (128, 16):
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((Hkv, N, P, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((Hkv, N, P, D)), jnp.float32)
        want = paged_ops.cached_attention(
            q, jnp.repeat(paged_ops.paged_gather(k, pt), 3, 1),
            jnp.repeat(paged_ops.paged_gather(v, pt), 3, 1), pos, 0.3)
        got = paged_ops.paged_attention(q, k, v, pt, pos, 0.3)
        np.testing.assert_allclose(got, want, atol=1e-6)
        if D == 16:     # the same heads side by side in a fused row
            def row(x):
                return jnp.pad(jnp.moveaxis(x, 0, 2).reshape(N, P, Hkv * D),
                               ((0, 0), (0, 0), (0, 128 - Hkv * D)))
            fused = paged_ops.paged_attention(q, row(k), row(v), pt, pos,
                                              0.3, kv_heads=Hkv)
            np.testing.assert_allclose(fused, want, atol=1e-6)


# -- the plants of tools/plant_fault.py are live ------------------------------

HYBRID_PLANTS = ("bf16_state", "neighbour_state", "unmasked_pad",
                 "no_window", "float8_window", "wrong_page", "wrong_table",
                 "wrong_group")


@pytest.fixture()
def restored(monkeypatch):
    """The plants assign module attributes; put the originals back."""
    from paddle_tpu.models import falcon_h1
    monkeypatch.setattr(ssm_ops, "ssm_decode_update",
                        ssm_ops.ssm_decode_update)
    monkeypatch.setattr(hybrid_family, "store_state",
                        hybrid_family.store_state)
    monkeypatch.setattr(hybrid_family, "store_window",
                        hybrid_family.store_window)
    monkeypatch.setattr(falcon_h1, "fh1_prefill", falcon_h1.fh1_prefill)
    monkeypatch.setattr(ssm_ops, "causal_conv_window_step",
                        ssm_ops.causal_conv_window_step)
    monkeypatch.setattr(paged_ops, "paged_write", paged_ops.paged_write)
    monkeypatch.setattr(paged_ops, "paged_attention",
                        paged_ops.paged_attention)
    monkeypatch.setattr(paged_ops, "paged_latent_attention",
                        paged_ops.paged_latent_attention)


@pytest.mark.parametrize("fault", HYBRID_PLANTS)
def test_a_hybrid_plant_moves_the_logits(net, restored, fault):
    _, sound = paged_logits(net, [40, 129], [0, 1], seed=21)
    plant_fault.PLANTS[fault]()
    _, planted = paged_logits(net, [40, 129], [0, 1], seed=21)
    assert np.isfinite(planted).all()
    assert rel(planted[:, 1:], sound[:, 1:]) > 20 * TOL, fault
