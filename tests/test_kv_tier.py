"""Tiered KV cache: host-RAM demotion tier under the prefix cache
(ISSUE 18).

The load-bearing anchors:

- **Cross-tier token identity** — a chain that was demoted to host RAM
  and promoted back decodes exactly like a never-evicted one, in fp32
  AND int8 (raw page bytes + fp32 scale rows round-trip bit-identical;
  the PR 9 scale-grid poisoning class, now across tiers).
- **No leak under faults** — both failpoints
  (`kv_tier.promote_upload`, `kv_tier.demote_gather`) leave ZERO
  leaked pages on either tier: an abandoned promotion zeroes its
  partially-written targets and falls back to cold prefill (correct
  tokens, exactly one KV_PROMOTE_ABANDON audit record); a failed
  demote gather degrades to the plain PR 12 eviction.
- **Budget discipline** — the tier's own byte budget LRU-evicts
  (demote-of-demoted = final eviction, KV_TIER_EVICT), refuses entries
  that alone exceed it, and never evicts a protected in-flight
  promotion run.
- **Observability** — stats()/step-ring/pressure all carry the tier
  fields, and tools/engine_report.py summarizes them.
"""
from contextlib import contextmanager

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework import monitor
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import failpoints
from paddle_tpu.serving.kv_tier import HostEntry, HostTier


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = GPTConfig.tiny(dropout=0.0)
    net = GPTForCausalLM(cfg)
    net.eval()
    return net


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    yield
    paddle.set_flags({"FLAGS_failpoints": ""})
    failpoints.reset()


@contextmanager
def flags(**kw):
    old = paddle.get_flags(list(kw))
    paddle.set_flags(kw)
    try:
        yield
    finally:
        paddle.set_flags(old)


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 12)          # 11 usable: floods evict
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("max_new_tokens", 4)
    kw.setdefault("request_timeout_ms", 0)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("kv_tier", True)
    kw.setdefault("kv_tier_host_bytes", 64 << 20)
    kw.setdefault("kv_tier_chunk_pages", 2)
    return serving.GenerationEngine(model, **kw)


def _prompts(n=8, pfx=8, tail=3, seed=0, vocab=512):
    """n prompts with DISTINCT pfx-token leads (each registers its own
    2-page chain at the 4-token test page size) + tail tokens."""
    rng = np.random.RandomState(seed)
    return [np.concatenate([rng.randint(0, vocab, size=(pfx,)),
                            rng.randint(0, vocab, size=(tail,))])
            .astype("int64") for _ in range(n)]


def _tier_consistent(tier: HostTier) -> bool:
    """Byte ledger reconciles exactly with the stored entries."""
    return tier.host_bytes == sum(e.nbytes
                                  for e in tier._entries.values())


def _pool_reconciles(eng) -> bool:
    """No live sequences: every allocated page is cache-held, one
    reference per cached page."""
    cache = eng._cache
    refs = cache.refcounts()
    cached = set(cache.cached_pages())
    return (cache.owners() == {} and set(refs) == cached
            and sum(refs.values()) == len(cached)
            and cache.pages_in_use == len(cached))


# -- HostTier store (unit) ---------------------------------------------------

def _entry(nbytes=16):
    half = nbytes // 2
    return HostEntry(np.zeros(half, np.int8), np.zeros(half, np.int8))


def test_host_tier_put_get_pop_accounting():
    t = HostTier(max_bytes=64, engine="tier_unit")
    stored, evicted = t.put(b"a", _entry())
    assert stored and evicted == []
    assert t.host_bytes == 16 and len(t) == 1 and b"a" in t
    # re-put under the same digest replaces without double counting
    stored, _ = t.put(b"a", _entry(32))
    assert stored and t.host_bytes == 32 and len(t) == 1
    assert t.get(b"a") is not None and t.get(b"zz") is None
    e = t.pop(b"a")
    assert e is not None and e.nbytes == 32
    assert t.host_bytes == 0 and len(t) == 0
    assert t.pop(b"a") is None              # absent pop is a no-op
    assert t.evictions == 0                 # plain pops aren't evictions
    t.put(b"b", _entry())
    t.pop(b"b", final=True)                 # cascade/abandon discard IS
    assert t.evictions == 1
    s = t.stats()
    assert s["demotions"] == 3 and s["host_bytes"] == 0
    assert _tier_consistent(t)


def test_host_tier_lru_eviction_respects_recency_and_protect():
    t = HostTier(max_bytes=40, engine="tier_lru")
    t.put(b"a", _entry())
    t.put(b"b", _entry())
    stored, evicted = t.put(b"c", _entry())  # 48 > 40: LRU "a" goes
    assert stored and evicted == [b"a"]
    assert t.digests() == [b"b", b"c"] and t.host_bytes == 32
    t.get(b"b")                              # touch: "c" is now LRU
    _, evicted = t.put(b"d", _entry())
    assert evicted == [b"c"]
    # a protected digest survives even as the LRU victim
    _, evicted = t.put(b"e", _entry(), protect=(b"b",))
    assert b"b" not in evicted and b"b" in t
    assert _tier_consistent(t)


def test_host_tier_refuses_entry_alone_over_budget():
    t = HostTier(max_bytes=8, engine="tier_reject")
    stored, evicted = t.put(b"big", _entry(16))
    assert not stored and evicted == []
    assert len(t) == 0 and t.host_bytes == 0
    assert t.rejects == 1 and t.demotions == 0


# -- engine demote/promote round-trip ----------------------------------------

def test_demote_promote_token_identical_fp32(model):
    prompts = _prompts(n=8, seed=31)
    ref = [model.generate(paddle.to_tensor(p[None]),
                          max_new_tokens=4).numpy()[0] for p in prompts]
    with _engine(model, name="tier_fp32") as eng:
        flood = [eng.generate(p, max_new_tokens=4) for p in prompts]
        pfx = eng.stats()["kv"]["prefix"]
        assert pfx["tier_enabled"] and pfx["demotions"] >= 2
        assert pfx["host_nodes"] >= 2 and pfx["host_bytes"] > 0
        # revisit the LRU-evicted (earliest) chain: misses HBM, hits
        # the host tier, promotes through the chunked upload pipeline
        again = eng.generate(prompts[0], max_new_tokens=4)
        s = eng.stats()
        reasons = [ev["reason"] for ev in eng._audit.tail(256)]
        tier = eng._tier.stats()
        # neither tier leaks on the clean path either
        assert _tier_consistent(eng._tier) and _pool_reconciles(eng)
    for o, r in zip(flood, ref):
        np.testing.assert_array_equal(o, r)
    np.testing.assert_array_equal(again, ref[0])
    assert tier["promotions"] >= 2 and tier["hits"] >= 1
    assert "KV_DEMOTE" in reasons and "KV_PROMOTE" in reasons
    assert s["kv"]["prefix"]["promotions"] >= 2
    assert s["kv"]["prefix"]["tier_hit_rate"] > 0
    # promotion rode the warmed tier programs: one compile each, ever
    assert s["compiles"]["tier_gather"] == 1
    assert all(v == 1 for k, v in s["compiles"].items()
               if k.startswith("tier_write"))


def test_promoted_int8_chain_token_identical_to_never_evicted(model):
    """The regression the raw-bytes storage exists for: an int8 chain
    demoted (pages + fp32 scale rows gathered to host) and promoted
    back must decode exactly like the never-evicted original."""
    prompts = _prompts(n=8, seed=37)
    with _engine(model, kv_cache_dtype="int8", name="tier_int8") as eng:
        # never-evicted baseline: cold prefill, then a pure-HBM hit
        base = eng.generate(prompts[0], max_new_tokens=4)
        warm = eng.generate(prompts[0], max_new_tokens=4)
        np.testing.assert_array_equal(base, warm)
        # flood with distinct chains until prompts[0]'s chain demotes
        for p in prompts[1:]:
            eng.generate(p, max_new_tokens=4)
        assert eng.stats()["kv"]["prefix"]["demotions"] >= 2
        promoted = eng.generate(prompts[0], max_new_tokens=4)
        tier = eng._tier.stats()
        reasons = [ev["reason"] for ev in eng._audit.tail(256)]
    np.testing.assert_array_equal(promoted, base)
    assert tier["promotions"] >= 2
    assert "KV_PROMOTE" in reasons


# -- failpoints: no leak on either tier --------------------------------------

def test_promote_upload_failpoint_falls_back_cold_no_leak(model):
    """Abandon mid-upload (after the first 1-page chunk): the written
    target page is zeroed (stale int8 scales would otherwise poison the
    requanting tail prefill), the admission falls back to cold prefill
    with CORRECT tokens, exactly one KV_PROMOTE_ABANDON is audited, and
    neither tier leaks a page."""
    prompts = _prompts(n=8, seed=41)
    with _engine(model, kv_cache_dtype="int8", kv_tier_chunk_pages=1,
                 name="tier_abandon") as eng:
        base = eng.generate(prompts[0], max_new_tokens=4)
        for p in prompts[1:]:
            eng.generate(p, max_new_tokens=4)
        assert eng.stats()["kv"]["prefix"]["demotions"] >= 2
        failpoints.reset()
        with flags(FLAGS_failpoints="kv_tier.promote_upload@2"):
            out = eng.generate(prompts[0], max_new_tokens=4)
        abandons = [ev for ev in eng._audit.tail(256)
                    if ev["reason"] == "KV_PROMOTE_ABANDON"]
        tier = eng._tier
        assert tier.abandons == 1 and tier.promotions == 0
        assert _tier_consistent(tier)
        assert _pool_reconciles(eng)
        # the cold prefill re-registered the chain: a fresh revisit is
        # a plain HBM hit again, still token-identical
        again = eng.generate(prompts[0], max_new_tokens=4)
    np.testing.assert_array_equal(out, base)
    np.testing.assert_array_equal(again, base)
    assert len(abandons) == 1
    assert abandons[0]["pages"] == 2 and abandons[0]["written"] == 1


def test_demote_gather_failpoint_degrades_to_plain_eviction(model):
    """Every demote gather fails: evictions proceed exactly like PR 12
    (content discarded), the tier stays empty, nothing leaks."""
    prompts = _prompts(n=8, seed=43)
    ref = model.generate(paddle.to_tensor(prompts[0][None]),
                         max_new_tokens=4).numpy()[0]
    with _engine(model, name="tier_nogather") as eng:
        with flags(FLAGS_failpoints="kv_tier.demote_gather@every:1"):
            for p in prompts:
                eng.generate(p, max_new_tokens=4)
            out = eng.generate(prompts[0], max_new_tokens=4)
        pfx = eng.stats()["kv"]["prefix"]
        tier = eng._tier
        assert len(tier) == 0 and tier.host_bytes == 0
        assert tier.demotions == 0 and pfx["host_nodes"] == 0
        assert pfx["evictions"] >= 1          # plain LRU evictions ran
        assert _pool_reconciles(eng)
    np.testing.assert_array_equal(out, ref)


# -- config validation -------------------------------------------------------

def test_kv_tier_requires_prefix_cache(model):
    with pytest.raises(InvalidArgumentError):
        _engine(model, prefix_cache=False, name="tier_cfg")


# -- observability plumbing --------------------------------------------------

def test_step_ring_pressure_and_report_carry_tier_fields(model, tmp_path):
    import importlib.util
    import json
    import os
    from paddle_tpu.profiler import step_log

    d0 = monitor.stat_get("STAT_kv_tier_demotions")
    p0 = monitor.stat_get("STAT_kv_tier_promotions")
    prompts = _prompts(n=8, seed=47)
    with _engine(model, name="tier_obs") as eng:
        for p in prompts:
            eng.generate(p, max_new_tokens=4)
        eng.generate(prompts[0], max_new_tokens=4)   # promote
        payload = step_log.steps_payload()
        recs = payload["engines"]["tier_obs"]["records"]
        pressure = eng._compute_pressure()
    assert sum(r["tier_demotions"] for r in recs) >= 2
    assert sum(r["tier_promotions"] for r in recs) >= 2
    assert monitor.stat_get("STAT_kv_tier_demotions") - d0 >= 2
    assert monitor.stat_get("STAT_kv_tier_promotions") - p0 >= 2
    assert pressure["tier"]["hit_rate"] > 0
    assert pressure["tier"]["host_bytes"] >= 0

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    spec = importlib.util.spec_from_file_location(
        "engine_report", os.path.join(tools, "engine_report.py"))
    er = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(er)
    summ = er.summarize(recs)
    assert summ["tier_demotions"] >= 2 and summ["tier_promotions"] >= 2
    path = str(tmp_path / "steps.json")
    with open(path, "w") as f:
        json.dump(payload, f)
    assert er.main([path, "--engine", "tier_obs"]) == 0
