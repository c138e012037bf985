"""Mesh-slice lanes (ISSUE 19): a tensor-parallel GenerationEngine
replica must be OUTPUT-IDENTICAL to the single-chip lane.

The engine's programs rebuild under shard_map over a 'tp' mesh axis —
attention/MLP projections and the paged K/V pools (plus the int8 scale
grids) head-sharded, page tables and logits replicated, one psum per
block at the row-parallel projections. None of that may be observable
from outside: greedy AND sampled tokens must match tp=1 exactly on the
CPU virtual-device mesh (conftest forces 8 host devices), across fp32
and int8 KV, through a prefix-cache hit's tail prefill and through a
speculative verify step. Compile discipline carries over unchanged —
the warmed ledger is exactly-once and no live request traces.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework import monitor
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.kv_cache import PagedKVCache


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = GPTConfig.tiny(dropout=0.0)   # 4 heads: tp in {1, 2, 4}
    net = GPTForCausalLM(cfg)
    net.eval()
    return net


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("request_timeout_ms", 0)
    return serving.GenerationEngine(model, **kw)


def _prompts(n=3, S=7, seed=0, vocab=512):
    return [np.random.RandomState(seed + i).randint(
        0, vocab, size=(S,)).astype("int64") for i in range(n)]


def _run(model, tp, prompts, sample=False, **kw):
    with _engine(model, tp=tp, name=f"tpid{tp}{'s' if sample else ''}",
                 **kw) as eng:
        outs = [eng.generate(p, max_new_tokens=6, do_sample=sample,
                             temperature=0.8 if sample else 1.0)
                for p in prompts]
        return outs, eng.stats()


# -- token identity ---------------------------------------------------------

@pytest.mark.parametrize("tp", [2, 4])
def test_tp_greedy_token_identity_fp32(model, tp):
    prompts = _prompts()
    ref, s1 = _run(model, 1, prompts)
    got, sN = _run(model, tp, prompts)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    # same warmed exactly-once ledger on both lanes — the sharded pack
    # minted no extra programs and no live request traced
    assert sN["compiles"] == s1["compiles"]
    assert all(v == 1 for v in sN["compiles"].values())
    assert sN["tp"] == tp and s1["tp"] == 1


def test_tp_sampled_token_identity(model):
    """Sampling shares the engine PRNG stream: the replicated key and
    the (psum-identical) logits must draw the same tokens per shard —
    and the same tokens as the single-chip lane."""
    prompts = _prompts(seed=3)
    ref, _ = _run(model, 1, prompts, sample=True)
    got, _ = _run(model, 2, prompts, sample=True)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)


def test_tp_greedy_token_identity_int8_kv(model):
    """int8 page mode: the scale grids shard along heads with the
    pools; quantize-on-append and dequant-on-gather are per-head math,
    so sharded quantization is bit-identical to the single chip's."""
    prompts = _prompts(seed=5)
    ref, _ = _run(model, 1, prompts, kv_cache_dtype="int8")
    got, s = _run(model, 2, prompts, kv_cache_dtype="int8")
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    assert s["pages"]["quantized"] and s["pages"]["tp"] == 2


def test_tp_prefix_hit_token_identity(model):
    """A prefix-cache hit rides the tail-prefill program — under tp its
    all-layers gather walks head-sharded pools. Same prompt twice: the
    second admission must hit the cached chain AND produce identical
    tokens to the tp=1 lane's identical hit."""
    rng = np.random.RandomState(9)
    prefix = rng.randint(0, 512, size=(8,)).astype("int64")
    tails = [rng.randint(0, 512, size=(3,)).astype("int64")
             for _ in range(2)]
    prompts = [np.concatenate([prefix, t]) for t in tails]

    def run(tp):
        with _engine(model, tp=tp, prefix_cache=True,
                     prefill_buckets=(4, 16),
                     name=f"tppfx{tp}") as eng:
            outs = [eng.generate(p, max_new_tokens=6) for p in prompts]
            return outs, eng.stats()

    ref, s1 = run(1)
    got, sN = run(2)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    # the hit actually happened on the sharded lane (shared pages +
    # tail program), and nothing traced outside warmup
    assert sN["kv"]["prefix"]["hits"] >= 1
    assert all(v == 1 for v in sN["compiles"].values())


def test_tp_spec_verify_token_identity(model):
    """Speculative decoding replaces the decode program with ONE
    verify[k] program — under tp that whole block (draft scoring,
    acceptance scan, scratch-routed rollback writes) runs sharded and
    must stay token-identical to the tp=1 speculative lane AND the
    plain greedy lane."""
    prompts = [np.array([7, 8, 9, 7, 8, 9, 7], np.int64),
               np.array([5, 5, 5, 5, 5, 5, 5], np.int64)]
    plain, _ = _run(model, 1, prompts)
    ref, s1 = _run(model, 1, prompts, spec_k=2)
    got, sN = _run(model, 2, prompts, spec_k=2)
    for a, b, c in zip(got, ref, plain):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    assert sN["compiles"]["verify[k=2]"] == 1
    assert not any(k.startswith("decode") for k in sN["compiles"])
    assert sN["compiles"] == s1["compiles"]


def test_tp_tier_demote_promote_token_identity(model):
    """Host-tier round trip under tp (ISSUE 18 seam): the demotion
    gather's sharded out_specs reassemble every head shard into ONE
    full host page, and the chunked promotion upload splits the staged
    full blocks back across the slice — token identity with the tp=1
    tier lane proves the reassembly is lossless both ways."""
    rng = np.random.RandomState(31)
    prompts = [np.concatenate([rng.randint(0, 512, size=(8,)),
                               rng.randint(0, 512, size=(3,))])
               .astype("int64") for _ in range(8)]

    def run(tp):
        with _engine(model, tp=tp, num_pages=12, prefill_buckets=(16,),
                     max_new_tokens=4, prefix_cache=True, kv_tier=True,
                     kv_tier_host_bytes=64 << 20, kv_tier_chunk_pages=2,
                     name=f"tptier{tp}") as eng:
            flood = [eng.generate(p, max_new_tokens=4) for p in prompts]
            again = eng.generate(prompts[0], max_new_tokens=4)
            return flood + [again], eng.stats()

    ref, s1 = run(1)
    got, sN = run(2)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    # the sharded lane really demoted AND promoted through the tier
    assert sN["kv"]["prefix"]["demotions"] >= 2
    assert sN["kv"]["prefix"]["promotions"] >= 2
    assert sN["compiles"]["tier_gather"] == 1


# -- capacity / gauges ------------------------------------------------------

def test_tp_shard_bytes_and_gauge(model):
    # an earlier test's tp cache gives its share back when it is collected
    # (weakref.finalize): have that happen before the gauge is read, not
    # between the two readings
    import gc
    gc.collect()
    base = monitor.stat_get("STAT_tp_kv_shard_bytes") or 0
    with _engine(model, tp=2, name="tpgauge") as eng:
        s = eng.stats()["pages"]
        assert s["tp"] == 2
        assert s["shard_hbm_bytes"] * 2 == s["hbm_bytes"]
        # the live per-shard gauge carries exactly this cache's share
        assert (monitor.stat_get("STAT_tp_kv_shard_bytes") - base
                == s["shard_hbm_bytes"])
        pr = eng.pressure()
        assert pr["tp"] == 2
        assert pr["kv_shard_bytes"] == s["shard_hbm_bytes"]


def test_tp_page_arithmetic_per_shard():
    """page_hbm_bytes/pages_for_budget size against ONE chip of the
    slice: the same per-chip budget admits tp× the pages — the
    serve-larger-models unlock, and the admission arithmetic stays in
    tp-invariant page units (the page axis is full on every shard)."""
    # (64-wide heads: a shard's two heads fill a 128-lane tile exactly;
    # narrower toy heads would pad each shard's row to a whole tile)
    kw = dict(num_layers=2, num_heads=4, head_dim=64, page_size=4)
    full = PagedKVCache.page_hbm_bytes(**kw)
    half = PagedKVCache.page_hbm_bytes(**kw, tp=2)
    assert half * 2 == full
    n1 = PagedKVCache.pages_for_budget(1 << 20, **kw)
    n2 = PagedKVCache.pages_for_budget(1 << 20, **kw, tp=2)
    assert n2 == 2 * n1
    q = PagedKVCache.page_hbm_bytes(**kw, dtype="int8", tp=2)
    assert q * 2 == PagedKVCache.page_hbm_bytes(**kw, dtype="int8")
    with pytest.raises(InvalidArgumentError):
        PagedKVCache.page_hbm_bytes(**kw, tp=3)   # 4 heads % 3 != 0


def test_tp_validation(model):
    with pytest.raises(InvalidArgumentError):
        _engine(model, tp=3, name="tpbad")        # 4 heads % 3 != 0
    with pytest.raises(InvalidArgumentError):
        serving.GenerationConfig(tp=0)
