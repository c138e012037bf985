"""Prefix cache with copy-on-write paged KV + streaming delivery
(ISSUE 12).

The load-bearing anchors:

- **Parity** — engine greedy output is token-identical with the prefix
  cache on vs off (fresh AND mid-decode-joined requests): the cached
  pages hold the same K/V the skipped prefill would have produced, and
  the tail-prefill program is anchored to the same masked-softmax
  oracle as the decode step.
- **Refcount hygiene** — zero-on-free defers until refcount 0: freeing
  one sharer never zeroes pages (or int8 scale rows) another sharer or
  the index still reads; after a drain shutdown the refcounts reconcile
  exactly with owners() + the cached set and no page leaks.
- **Truthful admission** — evictable (refcount-0 cached) pages count as
  reclaimable in can_admit/headroom/stats, with the LRU eviction
  performed before alloc.
- **Streaming barrier** — streamed tokens arrive before `resolved` and
  concatenate exactly to the non-streaming result; TTFT deadlines are
  hard, whole-request deadlines soft for streams.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework import monitor
from paddle_tpu.framework.errors import (ExecutionTimeoutError,
                                         InvalidArgumentError)
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.prefix_cache import PrefixCache


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = GPTConfig.tiny(dropout=0.0)
    net = GPTForCausalLM(cfg)
    net.eval()
    return net


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_buckets", (4, 16))
    kw.setdefault("max_new_tokens", 5)
    kw.setdefault("request_timeout_ms", 0)
    kw.setdefault("prefix_cache", True)
    return serving.GenerationEngine(model, **kw)


def _shared_prefix_prompts(n=3, pfx=8, tail=3, seed=0, vocab=512):
    """n prompts sharing one `pfx`-token prefix (a multiple of the
    4-token test page size) with distinct `tail` tokens."""
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, size=(pfx,)).astype("int64")
    return [np.concatenate([prefix,
                            rng.randint(0, vocab, size=(tail,))
                            .astype("int64")]) for _ in range(n)]


# -- allocator refcount layer ----------------------------------------------

def test_refcounted_share_and_deferred_free():
    c = PagedKVCache(num_layers=2, num_heads=2, head_dim=4, page_size=4,
                     num_pages=16, pages_per_seq=4)
    row_a = c.alloc(1, 9)                       # 3 pages, refcount 1 each
    shared = [int(row_a[0]), int(row_a[1])]
    row_b = c.alloc_shared(2, 12, shared)       # maps 2 shared + 1 fresh
    assert list(row_b[:2]) == shared
    assert c.refcounts()[shared[0]] == 2
    # freeing A returns ONLY its private page — the shared ones defer
    freed_a = c.free(1)
    assert len(freed_a) == 1 and set(freed_a).isdisjoint(shared)
    assert c.refcounts()[shared[0]] == 1
    freed_b = c.free(2)                         # last sharer: all return
    assert set(shared) <= set(freed_b) and len(freed_b) == 3
    assert c.pages_in_use == 0 and not c.refcounts()


def test_cache_hold_evictable_accounting_and_cow_split():
    c = PagedKVCache(num_layers=1, num_heads=1, head_dim=4, page_size=4,
                     num_pages=8, pages_per_seq=4)   # 7 usable
    row = c.alloc(1, 8)                              # 2 pages
    held = [int(row[0]), int(row[1])]
    c.cache_hold(held)                               # index reference
    assert c.evictable_pages == 0                    # seq 1 still shares
    assert c.free(1) == []                           # nothing hits 0
    assert c.evictable_pages == 2
    # cached-but-evictable counts as admission capacity (ISSUE 12)
    assert c.reclaimable_pages == 7 and c.can_admit(16)
    assert not c.can_admit(28)               # page-table width still binds
    assert c.headroom([8]) == {8: 3}                 # 7 // 2
    s = c.stats()
    assert s["cached_pages"] == 2 and s["evictable_pages"] == 2
    assert s["reclaimable_pages"] == 7
    # CoW split: a sharer swaps a shared page for a private copy
    row2 = c.alloc_shared(2, 8, held)
    new = c.cow_split(2, held[1])
    assert new not in held and c.owned(2) == [held[0], new]
    assert c.refcounts()[held[1]] == 1               # index only now
    with pytest.raises(InvalidArgumentError):
        c.cow_split(2, new)                          # not shared
    released = c.cache_release(held)
    assert released == [held[1]]                     # held[0]: seq 2 shares
    assert c.free(2) == sorted([held[0], new]) or \
        set(c.free(2) or [held[0], new]) == {held[0], new}


def test_prefix_index_lookup_register_evict():
    c = PagedKVCache(num_layers=1, num_heads=1, head_dim=4, page_size=4,
                     num_pages=16, pages_per_seq=4)
    idx = PrefixCache(c, "t")
    prompt = np.arange(10, dtype=np.int64)           # 2 full pages + 2
    digests, hit = idx.lookup(prompt)
    assert len(digests) == 2 and hit == []
    row = c.alloc(1, 10)
    idx.register(digests, row)
    assert len(idx) == 2 and c.cached_pages()
    # same leading tokens, longer prompt: both pages hit; a diverging
    # second page hits only the first (the chain digest commits to
    # every token before it)
    _, hit2 = idx.lookup(np.arange(16, dtype=np.int64))
    assert hit2 == [int(row[0]), int(row[1])]
    diverged = np.concatenate([np.arange(4), np.arange(40, 44)])
    _, hit3 = idx.lookup(diverged.astype(np.int64))
    assert hit3 == [int(row[0])]
    c.free(1)
    # leaf-first LRU eviction returns the freed pages for zeroing
    freed = idx.evict(2)
    assert sorted(freed) == sorted([int(row[0]), int(row[1])])
    assert len(idx) == 0 and idx.evictions == 2
    _, hit4 = idx.lookup(prompt)
    assert hit4 == []


# -- engine parity on vs off ------------------------------------------------

def test_greedy_token_identical_cache_on_vs_off(model):
    prompts = _shared_prefix_prompts(n=3)
    ref = [model.generate(paddle.to_tensor(p[None]),
                          max_new_tokens=5).numpy()[0] for p in prompts]
    h0 = monitor.stat_get("STAT_prefix_hits")
    with _engine(model, prefix_cache=False, name="pfx_off") as eng:
        off = [eng.generate(p, max_new_tokens=5) for p in prompts]
    with _engine(model, prefix_cache=True, name="pfx_on") as eng:
        on = [eng.generate(p, max_new_tokens=5) for p in prompts]
        s = eng.stats()
    for a, b, r in zip(on, off, ref):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, r)
    # requests 2 and 3 rode the cached 8-token prefix (2 pages)
    assert monitor.stat_get("STAT_prefix_hits") - h0 >= 2
    assert s["kv"]["prefix"]["hits"] >= 2
    assert s["kv"]["prefix"]["hit_tokens"] >= 16
    # hits rode the warmed tail program: every ledger entry exactly once
    assert all(v == 1 for v in s["compiles"].values())
    assert "prefill_tail[b=4]" in s["compiles"]


def test_mid_decode_join_prefix_hit_parity(model):
    prompts = _shared_prefix_prompts(n=2, seed=3)
    ref_a = model.generate(paddle.to_tensor(prompts[0][None]),
                           max_new_tokens=40).numpy()[0]
    ref_b = model.generate(paddle.to_tensor(prompts[1][None]),
                           max_new_tokens=5).numpy()[0]
    with _engine(model, name="pfx_join") as eng:
        fa = eng.submit(prompts[0], max_new_tokens=40)
        deadline = time.time() + 60
        while eng.stats()["steps"] < 3:
            assert time.time() < deadline, "engine never started stepping"
            time.sleep(0.002)
        fb = eng.submit(prompts[1], max_new_tokens=5)  # joins mid-decode
        out_b = fb.result(timeout=120)
        out_a = fa.result(timeout=120)
        s = eng.stats()
    np.testing.assert_array_equal(out_a, ref_a)
    np.testing.assert_array_equal(out_b, ref_b)
    assert s["kv"]["prefix"]["hits"] >= 1
    assert all(v == 1 for v in s["compiles"].values())


def test_full_prompt_match_cow_split(model):
    p8 = _shared_prefix_prompts(n=1, pfx=8, tail=0)[0]
    assert p8.size == 8                      # exactly 2 full pages
    ref = model.generate(paddle.to_tensor(p8[None]),
                         max_new_tokens=4).numpy()[0]
    c0 = monitor.stat_get("STAT_cow_splits")
    with _engine(model, name="pfx_cow") as eng:
        a = eng.generate(p8, max_new_tokens=4)   # miss: registers chain
        b = eng.generate(p8, max_new_tokens=4)   # full match: CoW split
        s = eng.stats()
        reasons = [e["reason"] for e in eng._audit.tail(64)]
    np.testing.assert_array_equal(a, ref)
    np.testing.assert_array_equal(b, ref)
    assert monitor.stat_get("STAT_cow_splits") - c0 >= 1
    assert "ADMIT_PREFIX_HIT" in reasons and "COW_SPLIT" in reasons
    assert s["compiles"]["cow_copy"] == 1


# -- int8 CoW + free isolation (satellite) ---------------------------------

def test_int8_cow_clones_scales_and_free_never_zeroes_sharer(model):
    """int8 CoW contract: the split clones the per-(layer, head, page)
    scale row, and freeing one sharer never zeroes pages/scales another
    sharer (or the index) still reads — poison-isolation style."""
    p8 = _shared_prefix_prompts(n=1, pfx=8, tail=0, seed=7)[0]
    with _engine(model, kv_cache_dtype="int8", name="pfx_int8") as eng:
        a = eng.generate(p8, max_new_tokens=4)   # registers the chain
        chain = sorted(eng._cache.cached_pages())
        assert len(chain) == 2
        form = eng._cache.form
        scales_before = form.pages(np.asarray(eng._ks), chain).copy()
        assert float(np.abs(scales_before).max()) > 0
        b = eng.generate(p8, max_new_tokens=4)   # CoW split + decode
        # the sharer completed and freed; the cached chain's pages and
        # scale rows must be untouched (zero-on-free deferred)
        scales_after = form.pages(np.asarray(eng._ks), chain)
        np.testing.assert_array_equal(scales_before, scales_after)
        cw = eng.stats()["kv"]["prefix"]
        assert cw["hits"] >= 1
        c = eng.generate(p8, max_new_tokens=4)   # third hit still clean
        pages_live = eng.stats()["pages"]
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(b, c)
    assert monitor.stat_get("STAT_cow_splits") >= 1
    # only the cached chain remains allocated
    assert pages_live["pages_in_use"] == pages_live["cached_pages"] == 2


# -- truthful admission + LRU eviction --------------------------------------

def test_eviction_before_alloc_keeps_admission_truthful(model):
    """A pool whose free list is short but whose cached chains are
    evictable must still admit (headroom counts reclaimable pages), by
    LRU-evicting refcount-0 chains before alloc."""
    pA = _shared_prefix_prompts(n=1, pfx=8, tail=0, seed=5)[0]
    pB = _shared_prefix_prompts(n=1, pfx=8, tail=0, seed=6)[0]
    refA = model.generate(paddle.to_tensor(pA[None]),
                          max_new_tokens=4).numpy()[0]
    refB = model.generate(paddle.to_tensor(pB[None]),
                          max_new_tokens=4).numpy()[0]
    e0 = monitor.stat_get("STAT_prefix_evictions")
    # 4 usable pages; one request needs 3 (8 prompt + 4 new)
    with _engine(model, max_slots=1, num_pages=5, prefill_buckets=(16,),
                 max_new_tokens=4, name="pfx_evict") as eng:
        oA = eng.generate(pA, max_new_tokens=4)   # registers 2 pages
        kv = eng.stats()["kv"]
        assert kv["evictable_pages"] == 2
        # the full pool is reclaimable (2 free + 2 evictable), and the
        # allocator's headroom arithmetic counts the evictable pages:
        # a 12-token shape (3 pages) fits once ONLY if they count
        assert kv["reclaimable_pages"] == 4
        assert eng._cache.headroom([12]) == {12: 1}
        oB = eng.generate(pB, max_new_tokens=4)   # needs eviction first
        reasons = [ev["reason"] for ev in eng._audit.tail(64)]
        oA2 = eng.generate(pA, max_new_tokens=4)  # evicted → miss again
    np.testing.assert_array_equal(oA, refA)
    np.testing.assert_array_equal(oB, refB)
    np.testing.assert_array_equal(oA2, refA)
    assert monitor.stat_get("STAT_prefix_evictions") - e0 >= 1
    assert "EVICT_PREFIX_LRU" in reasons


# -- streaming --------------------------------------------------------------

def test_stream_tokens_concatenate_and_arrive_before_resolved(model):
    prompts = _shared_prefix_prompts(n=2, seed=9)
    with _engine(model, name="pfx_stream") as eng:
        ref = eng.generate(prompts[0], max_new_tokens=5)
        stream = eng.submit_stream(prompts[0], max_new_tokens=5)
        toks = list(stream)                      # per-token delivery
        out = stream.result(timeout=60)
        np.testing.assert_array_equal(out, ref)
        assert toks == list(out[prompts[0].size:])
        # barrier order: once result() returns, the final token was
        # already queued — a fresh stream drains without blocking
        s2 = eng.submit_stream(prompts[1], max_new_tokens=5)
        out2 = s2.result(timeout=60)
        toks2 = list(s2)                         # must not block
        assert toks2 == list(out2[prompts[1].size:])


def test_stream_ttft_deadline_hard_while_blocked(model):
    """TTFT deadline is HARD: a stream that cannot produce its first
    token in time fails with ExecutionTimeoutError even though the
    whole-request deadline is disabled."""
    prompts = _shared_prefix_prompts(n=2, seed=13, tail=3)
    # pool sized for one sequence: the second stream stays queued
    with _engine(model, max_slots=1, num_pages=30, page_size=4,
                 max_new_tokens=100, prefill_buckets=(16,),
                 name="pfx_ttft") as eng:
        fa = eng.submit(prompts[0], max_new_tokens=100)
        # (5 ms: with decode steps launched ahead of the host, ISSUE 34,
        # the tiny model's 100 tokens take some 50 ms on the CPU, and the
        # stream must expire while the first request still holds the slot)
        stream = eng.submit_stream(prompts[1], max_new_tokens=5,
                                   ttft_timeout_ms=5)
        with pytest.raises(ExecutionTimeoutError):
            next(iter(stream))
        with pytest.raises(ExecutionTimeoutError):
            stream.result(timeout=30)
        fa.result(timeout=240)


def test_stream_whole_request_deadline_soft_mid_stream(model):
    """Once tokens flow, the whole-request deadline turns soft: expiry
    stops decoding and resolves with the tokens already delivered."""
    from paddle_tpu.serving import failpoints
    p = _shared_prefix_prompts(n=1, seed=17)[0]
    t0 = monitor.stat_get("STAT_gen_timeouts")
    # every step slowed by 5 ms: the tiny model's 100 tokens cannot beat
    # the 60 ms deadline however fast the loop is (with decode steps
    # launched ahead of the host, ISSUE 34, they otherwise do on the CPU)
    failpoints.reset()
    paddle.set_flags({"FLAGS_failpoints": "slow_step_ms@every:1:5"})
    try:
        with _engine(model, max_new_tokens=100, num_pages=64,
                     name="pfx_soft") as eng:
            stream = eng.submit_stream(p, max_new_tokens=100,
                                       timeout_ms=60)
            toks = list(stream)                  # ends at the deadline
            out = stream.result(timeout=60)
            reasons = [ev["reason"] for ev in eng._audit.tail(64)]
            pages_after = eng.stats()["pages"]["pages_in_use"]
    finally:
        paddle.set_flags({"FLAGS_failpoints": ""})
        failpoints.reset()
    assert 1 <= len(toks) < 100
    assert toks == list(out[p.size:])
    assert monitor.stat_get("STAT_gen_timeouts") > t0
    assert "EXPIRE_DECODE" in reasons
    assert pages_after == eng.stats()["pages"]["cached_pages"]


# -- drain reconciliation (acceptance) --------------------------------------

def test_drain_shutdown_reconciles_refcounts_and_leaks_nothing(model):
    prompts = _shared_prefix_prompts(n=4, seed=21)
    eng = _engine(model, max_slots=3, name="pfx_drain")
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    stream = eng.submit_stream(prompts[0], max_new_tokens=4)
    eng.shutdown(drain=True, timeout_s=120)
    for f in futs:
        assert f.result(timeout=1).shape[0] == prompts[0].size + 4
    assert list(stream) == list(stream.result(timeout=1)[prompts[0].size:])
    cache = eng._cache
    refs = cache.refcounts()
    cached = set(cache.cached_pages())
    # zero leaks: every allocated page is cache-held, owners() is empty,
    # and the refcount sum reconciles exactly (one reference per cached
    # page, none from sequences)
    assert cache.owners() == {}
    assert set(refs) == cached
    assert sum(refs.values()) == len(cached)
    assert cache.pages_in_use == len(cached)
    assert cache.free_pages + cache.pages_in_use == cache.usable_pages
    # and the admission surface reports every cached page reclaimable
    assert cache.evictable_pages == len(cached)


# -- observability plumbing -------------------------------------------------

def test_step_ring_and_reports_carry_prefix_fields(model, tmp_path):
    import importlib.util
    import json
    import os
    from paddle_tpu import profiler
    from paddle_tpu.profiler import step_log

    prompts = _shared_prefix_prompts(n=3, seed=25)
    with _engine(model, name="pfx_obs") as eng:
        for p in prompts:
            eng.generate(p, max_new_tokens=4)
        p8 = prompts[0][:8]
        eng.generate(p8, max_new_tokens=3)   # full match → CoW
        eng.generate(p8, max_new_tokens=3)
        payload = step_log.steps_payload()
        recs = payload["engines"]["pfx_obs"]["records"]
    assert sum(r["prefix_tokens"] for r in recs) > 0
    assert sum(r["cow_splits"] for r in recs) >= 1

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")

    def load(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(tools, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # engine_report summarizes the new per-iteration fields
    er = load("engine_report")
    path = str(tmp_path / "steps.json")
    with open(path, "w") as f:
        json.dump(payload, f)
    summ = er.summarize(recs)
    assert summ["prefix_tokens"] > 0 and summ["cow_splits"] >= 1
    assert er.main([path, "--engine", "pfx_obs"]) == 0

    # latency_report parses the pfx reqspan field per request
    lr = load("latency_report")
    trace = str(tmp_path / "trace.json")
    profiler.export_chrome_tracing(trace)
    gens = [g for g in lr.parse_gen_trace(trace)
            if g["engine"] == "pfx_obs"]
    assert gens and any(g["pfx"] > 0 for g in gens)
    rep = lr.gen_report(gens, top=3)
    assert rep["prefix_hit_tokens"] > 0
    assert rep["prefix_hit_requests"] >= 1
