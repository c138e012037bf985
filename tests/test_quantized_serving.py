"""Quantized serving end to end (ISSUE 9).

Weights: `jit.save` exports int8/packed-int4 + per-output-channel scales
as leading runtime arguments of the StableHLO artifact (quant manifest
in .pdmeta); `Predictor` feeds them device-resident in integer form and
the dequant happens inside the compiled call. KV: `PagedKVCache` int8
page mode — parallel per-(layer, head, page) scale pools,
quantize-on-append / dequantize-on-read, zero-on-free covering the
scale pools.

Numerics contracts tested here:
- engine-vs-Predictor **bit identity within one compiled shape** holds
  under int8 weights (the standard serving contract — co-riders and
  zero padding never bleed in);
- `GenerationEngine` int8-KV vs fp32-KV greedy parity is **token
  level**: the two run DIFFERENT compiled programs (quantize/dequant
  ops), so float bit-identity is out of scope per the XLA batch-shape
  rule, and int8 round-off may flip a near-tie argmax — asserted as a
  high agreement fraction plus an exact first token (prefill logits
  never read quantized pages).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import inference, serving
from paddle_tpu.framework import monitor
from paddle_tpu.framework.errors import FatalError
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.quantization import quantize_weights
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.static.input_spec import InputSpec


class _Mlp(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 32)
        self.fc2 = nn.Linear(32, 4)

    def forward(self, x):
        return self.fc2(paddle.tanh(self.fc1(x)))


def _x(rows, seed=0):
    return np.random.RandomState(seed).standard_normal(
        (rows, 8)).astype("float32")


@pytest.fixture(params=[8, 4], ids=["int8", "int4"])
def qartifact(request, tmp_path):
    paddle.seed(0)
    net = _Mlp()
    quantize_weights(net, bits=request.param)
    prefix = str(tmp_path / f"qmlp{request.param}")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([None, 8], "float32")])
    return net, prefix


# ---------------------------------------------------------------------------
# weights: Predictor + InferenceEngine over quantized artifacts
# ---------------------------------------------------------------------------

def test_predictor_detects_manifest_and_keeps_integer_weights(qartifact):
    net, prefix = qartifact
    g0 = monitor.stat_get("STAT_quant_weights_loaded")
    pred = inference.create_predictor(inference.Config(prefix))
    # the user-facing signature excludes the artifact's weight args
    assert pred.input_signature() == [
        ("input_0", (None, 8), np.dtype("float32"))]
    info = pred.quant_info()
    assert info["weight_tensors"] == 2
    assert info["resident_bytes"] > 0
    # device-resident INTEGER form — never an fp32 copy
    assert {str(a.dtype) for a in pred._qargs} == {"int8", "float32"}
    assert monitor.stat_get("STAT_quant_weights_loaded") - g0 == 2
    assert monitor.stat_get("STAT_quant_weight_hbm_bytes") > 0
    x = _x(3, seed=1)
    np.testing.assert_allclose(pred.run([x])[0],
                               net(paddle.to_tensor(x)).numpy(),
                               rtol=1e-5, atol=1e-5)
    # symbolic batch still serves any batch size
    assert pred.run([_x(13)])[0].shape == (13, 4)


def test_hbm_gauges_track_live_residency(tmp_path):
    """STAT_quant_weight_hbm_bytes / STAT_kv_cache_hbm_bytes are real
    gauges: replicas/pools ADD on construction and SUBTRACT when
    collected, so a multi-engine process (or a restart loop) exports
    actual residency, not a monotone high-water mark or the last-built
    pool."""
    import gc
    gc.collect()  # flush earlier tests' dead replicas/pools first
    paddle.seed(4)
    net = quantize_weights(_Mlp())
    prefix = str(tmp_path / "g")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([None, 8], "float32")])
    b0 = monitor.stat_get("STAT_quant_weight_hbm_bytes")
    pred = inference.create_predictor(inference.Config(prefix))
    per = pred.quant_info()["resident_bytes"]
    assert monitor.stat_get("STAT_quant_weight_hbm_bytes") == b0 + per
    pred2 = inference.create_predictor(inference.Config(prefix))
    assert monitor.stat_get("STAT_quant_weight_hbm_bytes") == \
        b0 + 2 * per
    del pred2
    gc.collect()
    assert monitor.stat_get("STAT_quant_weight_hbm_bytes") == b0 + per

    k0 = monitor.stat_get("STAT_kv_cache_hbm_bytes")
    c1 = PagedKVCache(2, 2, 8, 4, 16, 2)
    c2 = PagedKVCache(2, 2, 8, 4, 16, 2, dtype="int8")
    assert monitor.stat_get("STAT_kv_cache_hbm_bytes") == \
        k0 + c1.hbm_bytes() + c2.hbm_bytes()
    gone = c1.hbm_bytes()
    keep = c2.hbm_bytes()
    del c1
    gc.collect()
    assert monitor.stat_get("STAT_kv_cache_hbm_bytes") == k0 + keep


def test_unquantized_artifact_has_no_manifest(tmp_path):
    paddle.seed(0)
    prefix = str(tmp_path / "fp")
    paddle.jit.save(_Mlp(), prefix,
                    input_spec=[InputSpec([None, 8], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))
    assert pred.quant_info() is None and pred._qargs == []


def test_engine_vs_predictor_bit_identity_int8_weights(tmp_path):
    """The PR 2 in-bucket contract re-verified under int8 weights: a
    request's rows are bit-identical whether zero-padded or surrounded
    by co-riders, and identical to Predictor.run on the hand-padded
    batch through the same bucket executable."""
    paddle.seed(1)
    net = quantize_weights(_Mlp())
    prefix = str(tmp_path / "q8")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([None, 8], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))
    eng = serving.InferenceEngine(pred, batch_buckets=(1, 4, 16),
                                  max_batch_size=16,
                                  max_batch_delay_ms=30.0,
                                  name="quant_bit_identity")
    try:
        xs = [_x(r, seed=r) for r in (1, 2, 3)]  # 6 rows -> bucket 16
        futs = [eng.submit(x) for x in xs]
        res = [f.result(timeout=60) for f in futs]
        padded = np.concatenate(xs + [np.zeros((10, 8), "float32")])
        oracle = pred.run([padded])[0]
        off = 0
        for x, r in zip(xs, res):
            np.testing.assert_array_equal(r[0], oracle[off:off + len(x)])
            off += len(x)
        alone = eng.submit(np.concatenate(xs)).result(timeout=60)
        np.testing.assert_array_equal(alone[0], oracle[:6])
    finally:
        eng.shutdown()


def test_quantized_engine_compile_ledger_exact(tmp_path):
    """Warmup compiles exactly once per (device, bucket) for a quantized
    artifact and serving traffic adds ZERO live compiles — the PR 3
    ledger contract is quantization-blind."""
    paddle.seed(2)
    net = quantize_weights(_Mlp())
    prefix = str(tmp_path / "q8")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([None, 8], "float32")])
    c0 = monitor.stat_get("STAT_predictor_compiles")
    eng = serving.InferenceEngine(inference.Config(prefix), devices=1,
                                  batch_buckets=(1, 4),
                                  max_batch_size=4,
                                  max_batch_delay_ms=1.0,
                                  name="quant_ledger")
    try:
        warm = monitor.stat_get("STAT_predictor_compiles") - c0
        assert warm == 2  # one lane x two buckets
        futs = [eng.submit(_x(1, seed=i)) for i in range(12)]
        for f in futs:
            f.result(timeout=60)
        assert monitor.stat_get("STAT_predictor_compiles") - c0 == warm
        s = eng.stats()
        assert s["quantized_weights"]["weight_tensors"] == 2
        assert all(c == 1 for lane in s["lanes"]
                   for c in lane["bucket_compiles"].values())
    finally:
        eng.shutdown()


def test_unsliceable_output_verdict_under_quantized_artifact(tmp_path):
    """A quantized model whose output lacks a leading batch dim still
    gets the unsliceable verdict: requests run unpadded and co-riders
    are never co-mingled (PR 2 hardening, re-verified with int8
    weights)."""

    class Agg(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 4)

        def forward(self, x):
            return paddle.mean(self.fc(x))  # scalar: batch-aggregate

    paddle.seed(3)
    net = quantize_weights(Agg())
    prefix = str(tmp_path / "agg")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([None, 8], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))
    eng = serving.InferenceEngine(pred, batch_buckets=(1, 4),
                                  max_batch_size=4,
                                  max_batch_delay_ms=20.0,
                                  name="quant_unsliceable")
    try:
        xs = [_x(1, seed=i) for i in range(3)]
        futs = [eng.submit(x) for x in xs]
        res = [f.result(timeout=60) for f in futs]
        for x, r in zip(xs, res):
            np.testing.assert_array_equal(r[0], pred.run([x])[0])
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# KV cache: int8 page mode
# ---------------------------------------------------------------------------

def test_kv_cache_int8_scale_pools_and_budget_arithmetic():
    c = PagedKVCache(2, 3, 8, 4, 16, 4, dtype="int8")
    assert c.quantized and str(c.k_pages.dtype) == "int8"
    # 8-wide heads take the fused form: 3 x 8 values in a row of one
    # whole lane tile, a scale per (layer, page, head)
    assert c.form.fused and c.k_pages.shape == (2, 16, 4, 128)
    assert c.k_scales.shape == (2, 16, 3)
    assert c.v_scales.shape == (2, 16, 3)
    assert c.hbm_bytes() == (2 * 2 * 16 * 4 * 128        # int8 pools
                             + 2 * 2 * 3 * 16 * 4)       # fp32 scales
    dims = dict(num_layers=2, num_heads=3, head_dim=8, page_size=4)
    per_fp = PagedKVCache.page_hbm_bytes(dtype="float32", **dims)
    per_q = PagedKVCache.page_hbm_bytes(dtype="int8", **dims)
    # ~4x pages per byte (scale pool overhead eats a sliver)
    assert 3.5 < per_fp / per_q <= 4.0
    budget = 64 * per_fp
    assert PagedKVCache.pages_for_budget(budget, dtype="float32",
                                         **dims) == 64
    assert PagedKVCache.pages_for_budget(budget, dtype="int8",
                                         **dims) >= int(3.5 * 64)
    # fp32 mode: no scale pools, no byte overhead
    f = PagedKVCache(2, 3, 8, 4, 16, 4)
    assert not f.quantized and f.k_scales is None


def test_can_admit_capacity_multiplies_at_equal_bytes():
    """Same HBM budget, ~4x the pages, ~4x the admitted sequences —
    the can_admit arithmetic IS the capacity multiplier."""
    dims = dict(num_layers=2, num_heads=2, head_dim=8, page_size=4)
    budget = PagedKVCache.page_hbm_bytes(dtype="float32", **dims) * 9
    n_fp = PagedKVCache.pages_for_budget(budget, dtype="float32", **dims)
    n_q = PagedKVCache.pages_for_budget(budget, dtype="int8", **dims)
    fp = PagedKVCache(page_size=4, num_pages=n_fp, pages_per_seq=2,
                      num_layers=2, num_heads=2, head_dim=8)
    q = PagedKVCache(page_size=4, num_pages=n_q, pages_per_seq=2,
                     num_layers=2, num_heads=2, head_dim=8, dtype="int8")

    def capacity(cache):
        n = 0
        while cache.can_admit(8):   # 2 pages per request
            cache.alloc(n, 8)
            n += 1
        return n

    cap_fp, cap_q = capacity(fp), capacity(q)
    assert cap_fp == 4              # (9 - trash) // 2
    assert cap_q >= 1.9 * cap_fp


def test_live_peak_multiplies_at_equal_pool_bytes(gpt_model):
    """The same multiplier read off live engines: at an equal pool-byte
    budget sized so fp32 pages hold a quarter of the slots, the int8
    engine's peak of co-resident sequences (the step ring's `live`) is
    >= 1.9x the fp32 engine's. Admission could regress (admitted then
    starved) without moving `can_admit`'s arithmetic."""
    from paddle_tpu.profiler import step_log
    cfg = gpt_model.gpt.config
    dims = dict(num_layers=cfg.num_layers, num_heads=cfg.num_heads,
                head_dim=cfg.hidden_size // cfg.num_heads, page_size=4)
    slots, prompt, new = 8, 12, 8
    per_req = -(-(prompt + new) // 4)
    fp_pages = (slots // 4) * per_req + 1
    budget = fp_pages * PagedKVCache.page_hbm_bytes(dtype="float32", **dims)
    q_pages = PagedKVCache.pages_for_budget(budget, dtype="int8", **dims)
    prompts = _gen_prompts(n=16, S=prompt)

    def peak(kv, pages):
        name = f"qpeak_{kv}"
        with serving.GenerationEngine(
                gpt_model, max_slots=slots, page_size=4, num_pages=pages,
                prefill_buckets=(16,), max_new_tokens=new,
                kv_cache_dtype=kv, request_timeout_ms=0, name=name) as eng:
            for f in [eng.submit(p, max_new_tokens=new) for p in prompts]:
                f.result(timeout=300)
            records = step_log.steps_payload()["engines"][name]["records"]
            assert eng.stats()["pages"]["pages_in_use"] == 0
        return max(r["live"] for r in records)

    peak_fp, peak_q = peak("float32", fp_pages), peak("int8", q_pages)
    assert peak_fp == slots // 4
    assert peak_q >= 1.9 * peak_fp


def test_paged_write_quantized_parity_and_requant_on_grow():
    """Op-level parity: quantized prefill + decode appends dequantize to
    the fp32-written values within int8 round-off, including a decode
    append whose abs-max FORCES the page's existing content onto a
    wider quantization grid."""
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_ops import (
        cached_attention, page_rows_for_positions, paged_attention,
        paged_gather, paged_gather_quantized, paged_write,
        paged_write_quantized)

    rng = np.random.RandomState(0)
    L, H, N, P, D = 2, 3, 8, 4, 5
    pq = jnp.zeros((L, H, N, P, D), "int8")
    sc = jnp.zeros((L, H, N), "float32")
    pf = jnp.zeros((L, H, N, P, D), "float32")
    pt_row = np.array([1, 2, 0, 0], np.int32)
    pos = np.arange(7)
    pids, offs = page_rows_for_positions(jnp.asarray(pt_row),
                                         jnp.asarray(pos), P)
    vals = rng.standard_normal((L, H, 7, D)).astype("float32")
    pq, sc = paged_write_quantized(pq, sc, None, pids, offs,
                                   jnp.asarray(vals))
    pf = paged_write(pf, None, pids, offs, jnp.asarray(vals))
    # decode append with 3x the magnitude: page 2's grid must widen and
    # its existing tokens requantize onto it
    v = rng.standard_normal((1, H, D)).astype("float32") * 3.0
    p1, o1 = page_rows_for_positions(jnp.asarray(pt_row)[None, :],
                                     jnp.asarray([7]), P)
    for layer in range(L):
        pq, sc = paged_write_quantized(pq, sc, layer, p1, o1,
                                       jnp.asarray(v))
        pf = paged_write(pf, layer, p1, o1, jnp.asarray(v))
    pt = jnp.asarray(pt_row)[None, :]
    for layer in range(L):
        dq = np.asarray(paged_gather_quantized(pq[layer], sc[layer], pt))
        fp = np.asarray(paged_gather(pf[layer], pt))
        rel = np.abs(dq[:, :, :8] - fp[:, :, :8]).max() \
            / np.abs(fp[:, :, :8]).max()
        assert rel < 0.03, rel
    # attention over the quantized pool matches the fp32 oracle
    q = jnp.asarray(rng.standard_normal((1, H, D)).astype("float32"))
    posb = jnp.asarray([7], jnp.int32)
    out_q = np.asarray(paged_attention(q, pq[0], pq[0], pt, posb, 0.4,
                                       sc[0], sc[0]))
    out_f = np.asarray(cached_attention(q, paged_gather(pf[0], pt),
                                        paged_gather(pf[0], pt),
                                        posb, 0.4))
    assert np.abs(out_q - out_f).max() < 0.05 * np.abs(out_f).max() + 0.02


# ---------------------------------------------------------------------------
# generation engine: int8 KV pages
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt_model():
    paddle.seed(0)
    net = GPTForCausalLM(GPTConfig.tiny())
    net.eval()
    return net


def _gen_prompts(n=6, S=12):
    rng = np.random.RandomState(3)
    return [rng.randint(0, 512, size=(S,)).astype("int64")
            for _ in range(n)]


def _run_engine(net, kv, prompts, max_new=8, **kw):
    eng = serving.GenerationEngine(
        net, max_slots=4, page_size=4, num_pages=64,
        prefill_buckets=(16,), max_new_tokens=max_new,
        kv_cache_dtype=kv, request_timeout_ms=0,
        name=f"qgen_{kv}", **kw)
    try:
        futs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    finally:
        eng.shutdown()
    return outs, stats


def test_generation_engine_int8_kv_token_parity(gpt_model):
    """Greedy decode over int8 KV pages agrees with fp32 pages at TOKEN
    level: exact first token (prefill logits never read the quantized
    cache) and a high overall agreement fraction (int8 round-off may
    flip a near-tie argmax; cross-program comparisons are never float
    bit-identity — the XLA batch-shape rule)."""
    prompts = _gen_prompts()
    outs_f, s_f = _run_engine(gpt_model, "float32", prompts)
    outs_q, s_q = _run_engine(gpt_model, "int8", prompts)
    assert s_q["pages"]["dtype"] == "int8"
    assert s_q["pages"]["quantized"] and not s_f["pages"]["quantized"]
    S = len(prompts[0])
    for a, b in zip(outs_f, outs_q):
        assert a[S] == b[S]         # first generated token exact
    # GENERATED tokens only: prompt tokens trivially match and would
    # dilute the agreement fraction
    agree = np.mean([np.mean(a[S:] == b[S:])
                     for a, b in zip(outs_f, outs_q)])
    assert agree >= 0.9, f"token agreement {agree} below contract"
    # exactly-once ledgers in BOTH modes + no leaked pages
    for s in (s_f, s_q):
        assert s["compiles"]["decode[m=4]"] == 1
        assert s["compiles"]["prefill[b=16]"] == 1
        assert s["pages"]["pages_in_use"] == 0


def test_prefill_pad_tail_never_touches_real_page_scales(gpt_model):
    """Bucket-pad prefill positions write to the scratch page: a 12-token
    prompt in a b=16 bucket must leave the page holding offsets 12..15
    untouched — its scale stays 0 until decode actually appends there.
    (The int8 grid only ever widens, so pad-token K/V baked into a real
    page's scale would permanently cost real tokens precision.)"""
    seen = []

    def hook(eng):
        req = eng._slots[0]
        if req is not None and not seen:
            pages = eng._cache.owned(req.rid)
            ks = np.asarray(eng._ks)
            # prompt 12, page_size 4: pages[0:3] hold real tokens,
            # pages[3:] are decode-reserve — untouched by prefill
            form = eng._cache.form
            seen.append((form.pages(ks, pages[:3]),
                         form.pages(ks, pages[3:])))

    eng = serving.GenerationEngine(
        gpt_model, max_slots=2, page_size=4, num_pages=32,
        prefill_buckets=(16,), max_new_tokens=8,
        kv_cache_dtype="int8", request_timeout_ms=0, name="qgen_padtail")
    try:
        eng._pre_step_hook = hook
        eng.generate(_gen_prompts(n=1)[0], max_new_tokens=8)
    finally:
        eng.shutdown()
    assert seen, "hook never observed the live sequence"
    real, reserve = seen[0]
    assert np.all(real > 0.0), "real prompt pages must carry scales"
    assert np.all(reserve == 0.0), \
        "pad-tail prefill writes leaked into a real page's scale"


def test_int8_kv_engine_bit_stable_across_repeats(gpt_model):
    """One engine config, one compiled decode shape: int8-KV results are
    bit-stable across engine instances (same programs, same inputs)."""
    prompts = _gen_prompts(n=3)
    a, _ = _run_engine(gpt_model, "int8", prompts)
    b, _ = _run_engine(gpt_model, "int8", prompts)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_int8_kv_poison_isolated_and_scale_pool_scrubbed(gpt_model):
    """Zero-on-free hygiene covers the SCALE pool: a poisoned sequence
    (NaN pages + garbage scales) fails alone, its neighbor decodes
    exactly, and the freed pages' scales are reset to 0 so the next
    owner starts from a clean quantization grid."""
    prompts = _gen_prompts(n=2)
    ref, _ = _run_engine(gpt_model, "int8", [prompts[0]], max_new=12)
    p0 = monitor.stat_get("STAT_gen_poisoned")
    fired, poisoned_pages = [], []

    def hook(eng):
        req = eng._slots[1] if len(eng._slots) > 1 else None
        if not fired and req is not None and len(req.toks) >= 2:
            pages = eng._cache.owned(req.rid)
            if pages:
                form = eng._cache.form
                eng._kp = form.at_pages(eng._kp, pages).set(127)
                eng._ks = form.at_pages(eng._ks, pages).set(np.nan)
                poisoned_pages.extend(pages)
                fired.append(req.rid)

    eng = serving.GenerationEngine(
        gpt_model, max_slots=4, page_size=4, num_pages=64,
        prefill_buckets=(16,), max_new_tokens=12,
        kv_cache_dtype="int8", request_timeout_ms=0, name="qgen_poison")
    try:
        eng._pre_step_hook = hook
        fa = eng.submit(prompts[0], max_new_tokens=12)
        fb = eng.submit(prompts[1], max_new_tokens=12)
        with pytest.raises(FatalError):
            fb.result(timeout=300)
        out_a = fa.result(timeout=300)
        eng._pre_step_hook = None
        # the victim's pages AND scales were zeroed on free
        ks = np.asarray(eng._ks)
        kp = np.asarray(eng._kp)
        form = eng._cache.form
        assert np.all(form.pages(ks, poisoned_pages) == 0.0)
        assert np.all(form.pages(kp, poisoned_pages) == 0)
        # a follow-up request reusing those pages decodes cleanly
        out_c = eng.generate(prompts[0], max_new_tokens=12)
        np.testing.assert_array_equal(out_c, ref[0])
        assert eng.stats()["pages"]["pages_in_use"] == 0
    finally:
        eng.shutdown()
    assert fired, "hook never found the co-resident sequence"
    np.testing.assert_array_equal(out_a, ref[0][:len(out_a)])
    assert monitor.stat_get("STAT_gen_poisoned") > p0


# ---------------------------------------------------------------------------
# quantized weights through the generation engine
# ---------------------------------------------------------------------------

def test_generation_engine_int8_weights(gpt_model):
    """quantize_weights'd GPT serves through the engine: decode-weight
    pytree carries (int8, scale) leaves, greedy output token-agrees with
    the fp32 model, and generate() on the quantized model matches the
    engine exactly (same int8 weights, token level)."""
    prompts = _gen_prompts(n=4)
    ref, _ = _run_engine(gpt_model, "auto", prompts)
    paddle.seed(0)
    qnet = quantize_weights(GPTForCausalLM(GPTConfig.tiny()).eval())
    W = qnet.decode_weights()
    leaf = W["blocks"][0][2]
    assert isinstance(leaf, tuple) and str(
        np.asarray(leaf[0]).dtype) == "int8"
    outs, stats = _run_engine(qnet, "auto", prompts)
    assert stats["compiles"]["decode[m=4]"] == 1
    S = len(prompts[0])
    agree = np.mean([np.mean(a[S:] == b[S:]) for a, b in zip(ref, outs)])
    assert agree >= 0.9
    # engine vs the quantized model's own generate: token-level greedy
    gen = qnet.generate(paddle.to_tensor(prompts[0][None]),
                        max_new_tokens=8).numpy()[0]
    np.testing.assert_array_equal(outs[0], gen[:len(outs[0])])


def test_int4_weights_decode_as_int8(gpt_model):
    paddle.seed(0)
    qnet = quantize_weights(GPTForCausalLM(GPTConfig.tiny()).eval(),
                            bits=4)
    q, s = qnet.decode_weights()["blocks"][0][2]
    assert str(np.asarray(q).dtype) == "int8"
    assert q.shape[-1] == s.shape[-1]       # unpacked to full channels
    out = _run_engine(qnet, "auto", _gen_prompts(n=2))[0]
    assert all(len(o) == 20 for o in out)   # 12 prompt + 8 new
