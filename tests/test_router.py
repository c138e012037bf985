"""The router tier (ISSUE 17): prefix-affinity placement over
supervised replicas.

The load-bearing anchors:

- **One digest implementation** — `chain_digests` is the function the
  engine's `PrefixCache` indexes by AND the function the router hashes
  prompts with; they cannot drift.
- **Affinity is TTFT-visible** — requests sharing a prompt prefix all
  land on the replica that prefilled it first, and that replica's
  prefix cache registers real hits; the cold replica registers none.
- **Pressure, not luck** — with no prefix to match, placement follows
  the least-pressured replica's `pressure()` snapshot (queue depth
  overlaid live, headroom from the step thread's published dict).
- **Drain stops placements** — a replica shedding readiness (SLO
  error-rate burn past FLAGS_slo_max_burn_rate) takes no new requests
  until it recovers; both edges are audited ROUTE_DRAIN.
- **Deaths cost nothing** — a replica killed mid-load resolves every
  future success-or-typed through its own supervisor replay, outputs
  token-identical to a fault-free run, and streams deliver each token
  exactly once across the restart; the router adds zero double-delivery
  surface because it only re-routes placement-time failures.
"""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework.errors import (InvalidArgumentError,
                                         UnavailableError)
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.profiler import exporter, slo
from paddle_tpu.serving import EngineOverloaded, Router, chain_digests
from paddle_tpu.serving import failpoints
from paddle_tpu.serving.prefix_cache import PrefixCache


@pytest.fixture(scope="module")
def model():
    paddle.seed(17)
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


def _router(model, name, **kw):
    kw.setdefault("num_replicas", 2)
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("max_new_tokens", 5)
    kw.setdefault("request_timeout_ms", 0)
    kw.setdefault("prefix_cache", True)
    # ttl 0: every placement refreshes pressure/health — deterministic
    kw.setdefault("pressure_ttl_ms", 0.0)
    return Router(model, name=name, **kw)


def _prompts_shared_prefix(n, prefix_pages=2, page=4, tail=4, seed=3,
                           vocab=200):
    """n prompts sharing `prefix_pages` FULL pages, distinct tails."""
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, size=prefix_pages * page)
    return [np.concatenate([prefix,
                            rng.randint(0, vocab, size=tail)])
            .astype("int64") for _ in range(n)]


def _drained_clean(rep) -> bool:
    """No live sequence on the replica: every allocated page is
    cache-held, one reference each."""
    cache = rep.sup.engine._cache
    refs, cached = cache.refcounts(), set(cache.cached_pages())
    return (cache.owners() == {} and set(refs) == cached
            and sum(refs.values()) == len(cached)
            and cache.pages_in_use == len(cached))


def _reasons(router):
    return [e["reason"]
            for e in router.stats()["router"]["audit_tail"]]


# -- satellite: one digest implementation ------------------------------------

def test_chain_digests_is_the_shared_implementation():
    p = np.arange(13, dtype=np.int64)
    d4 = chain_digests(p, 4)
    assert len(d4) == 3 and all(len(d) == 16 for d in d4)
    # chain property: an extended prompt re-derives the same leading
    # digests — the replica-independence affinity routing rests on
    assert chain_digests(p[:8], 4) == d4[:2]
    # content + boundary sensitivity
    q = p.copy()
    q[1] += 1
    assert chain_digests(q, 4)[0] != d4[0]
    assert chain_digests(p, 8)[0] != d4[0]
    # PrefixCache keys its index through the same function
    assert PrefixCache.digests.__doc__ and (
        "chain_digests" in PrefixCache.digests.__doc__)


def test_prefix_cache_digests_delegate(model):
    r = _router(model, "rtr_digest", num_replicas=1)
    try:
        p = np.arange(12, dtype=np.int64)
        eng = r._replicas[0].sup.engine
        assert eng._prefix.digests(p) == chain_digests(p, 4)
    finally:
        r.shutdown()


# -- satellite: the pressure snapshot ----------------------------------------

def test_pressure_snapshot_shape_and_live_queue_overlay(model):
    r = _router(model, "rtr_pressure", num_replicas=1)
    try:
        sup = r._replicas[0].sup
        p = sup.pressure()
        assert p["queue_depth"] == 0 and p["oldest_age_ms"] == 0.0
        assert p["slots_free"] == 2 and p["live"] == 0
        # headroom covers the same shapes as stats()["kv"]
        assert p["headroom"] == sup.stats()["kv"]["admit_headroom"]
        assert p["free_pages"] > 0 and p["queue_limit"] > 0
        # a full engine shows its queue through pressure() immediately
        # (the overlay is live, not iteration-delayed)
        prompts = _prompts_shared_prefix(5, seed=21)
        futs = [sup.submit(q, max_new_tokens=5) for q in prompts]
        assert sup.pressure()["queue_depth"] >= 1
        for f in futs:
            f.result(timeout=60)
        assert sup.pressure()["queue_depth"] == 0
    finally:
        r.shutdown()


# -- tentpole: affinity steering ---------------------------------------------

def test_affinity_steers_to_warm_replica(model):
    prompts = _prompts_shared_prefix(6, seed=7)
    r = _router(model, "rtr_affinity")
    try:
        r.submit(prompts[0], max_new_tokens=5).result(timeout=60)
        first = [rep for rep in r._replicas if rep.placements == 1][0]
        cold = [rep for rep in r._replicas if rep is not first][0]
        for q in prompts[1:]:
            r.submit(q, max_new_tokens=5).result(timeout=60)
        # every shared-prefix follow-up stuck to the warm replica ...
        assert first.placements == len(prompts)
        assert cold.placements == 0
        # ... and the warmth is real, not just stickiness: the engine's
        # prefix cache served every follow-up's leading pages
        assert first.sup.engine._prefix.hits == len(prompts) - 1
        assert cold.sup.engine._prefix.hits == 0
        reasons = _reasons(r)
        assert reasons.count("ROUTE_AFFINITY") == len(prompts) - 1
        assert r.stats()["router"]["replicas"][first.name][
            "sketch_digests"] >= 2
    finally:
        r.shutdown()


def test_affinity_off_is_round_robin(model):
    prompts = _prompts_shared_prefix(6, seed=8)
    r = _router(model, "rtr_rr", affinity=False)
    try:
        for q in prompts:
            r.submit(q, max_new_tokens=5).result(timeout=60)
        spread = sorted(rep.placements for rep in r._replicas)
        assert spread == [3, 3]
        assert "ROUTE_AFFINITY" not in _reasons(r)
        # replica i lives on local device i — weights and KV pools both
        import jax
        for i, rep in enumerate(r._replicas):
            eng, dev = rep.sup.engine, jax.local_devices()[i]
            assert eng._kp.devices() == {dev}
            assert all(leaf.devices() == {dev}
                       for leaf in jax.tree_util.tree_leaves(eng._W))
    finally:
        r.shutdown()


@pytest.mark.parametrize("affinity", [True, False],
                         ids=["affinity", "round_robin"])
def test_placement_changes_no_token_no_ledger_and_leaks_no_page(
        model, affinity):
    """Placement decides the cache's temperature, never the math: either
    policy serves `generate`'s tokens, routed traffic rides the warmed
    programs (no replica's compile ledger moves), and once drained every
    page still allocated is one the prefix cache holds."""
    prompts = _prompts_shared_prefix(6, seed=9)
    ref = [model.generate(paddle.to_tensor(q[None]),
                          max_new_tokens=5).numpy()[0] for q in prompts]
    r = _router(model, f"rtr_same_{int(affinity)}", affinity=affinity)
    try:
        ledgers = [dict(rep.sup.engine._ledger) for rep in r._replicas]
        outs = [r.submit(q, max_new_tokens=5).result(timeout=60)
                for q in prompts]
        for a, b in zip(outs, ref):
            np.testing.assert_array_equal(a, b)
        assert [dict(rep.sup.engine._ledger)
                for rep in r._replicas] == ledgers
        assert all(v == 1 for led in ledgers for v in led.values())
        assert all(_drained_clean(rep) for rep in r._replicas)
    finally:
        r.shutdown()


# -- tentpole: least-pressure fallback ---------------------------------------

def test_least_pressure_fallback_avoids_loaded_replica(model):
    r = _router(model, "rtr_pressure_lb")
    try:
        r0, r1 = r._replicas
        # load r0 directly (slots full + one queued) so its pressure
        # snapshot reads worse on every axis the fallback scores
        rng = np.random.RandomState(31)
        busy = [r0.sup.submit(
            rng.randint(0, 200, size=6).astype("int64"),
            max_new_tokens=40) for _ in range(3)]
        assert r0.sup.pressure()["queue_depth"] >= 1
        # a prompt with NO full shared page falls through affinity
        out = r.submit(rng.randint(0, 200, size=3).astype("int64"),
                       max_new_tokens=5)
        out.result(timeout=60)
        assert r1.placements == 1 and r0.placements == 0
        assert "ROUTE_LEAST_PRESSURE" in _reasons(r)
        for f in busy:
            f.result(timeout=120)
    finally:
        r.shutdown()


# -- tentpole: drain on SLO burn ---------------------------------------------

def test_drain_on_burn_rate_stops_placements(model):
    prev = paddle.get_flags(["FLAGS_slo_error_rate",
                             "FLAGS_slo_max_burn_rate"])
    slo.reset()
    r = _router(model, "rtr_drain")
    try:
        paddle.set_flags({"FLAGS_slo_error_rate": 0.5,
                          "FLAGS_slo_max_burn_rate": 1.0})
        r0, r1 = r._replicas
        for _ in range(4):
            slo.observe_request(r0.name, ok=False)
        assert not r0.sup.health()["ready"]
        prompts = _prompts_shared_prefix(4, seed=9)
        for q in prompts:
            r.submit(q, max_new_tokens=5).result(timeout=60)
        # burn-rate shed replica took nothing; the drain edge is audited
        assert r0.placements == 0 and r1.placements == 4
        assert "ROUTE_DRAIN" in _reasons(r)
        h = r.health()
        assert h["ready"] and h["placeable"] == 1
        assert not h["replicas"][r0.name]["ready"]
        # recovery: burn clears, the replica re-enters placement
        slo.reset()
        assert r.health()["placeable"] == 2
        drains = [e for e in r.stats()["router"]["audit_tail"]
                  if e["reason"] == "ROUTE_DRAIN"]
        assert {d["drained"] for d in drains} == {True, False}
    finally:
        paddle.set_flags(prev)
        slo.reset()
        r.shutdown()


def test_all_drained_raises_typed(model):
    prev = paddle.get_flags(["FLAGS_slo_error_rate",
                             "FLAGS_slo_max_burn_rate"])
    slo.reset()
    r = _router(model, "rtr_alldrain")
    try:
        paddle.set_flags({"FLAGS_slo_error_rate": 0.5,
                          "FLAGS_slo_max_burn_rate": 1.0})
        for rep in r._replicas:
            for _ in range(4):
                slo.observe_request(rep.name, ok=False)
        with pytest.raises(UnavailableError):
            r.submit(np.arange(6, dtype=np.int64), max_new_tokens=5)
        assert not r.health()["ready"]
    finally:
        paddle.set_flags(prev)
        slo.reset()
        r.shutdown()


# -- tentpole: placement-time re-route ---------------------------------------

def test_reroute_on_placement_failure(model):
    prompts = _prompts_shared_prefix(2, seed=11)
    r = _router(model, "rtr_reroute")
    try:
        # warm the sketch so affinity pins the follow-up to `first`
        r.submit(prompts[0], max_new_tokens=5).result(timeout=60)
        first = [rep for rep in r._replicas if rep.placements == 1][0]
        other = [rep for rep in r._replicas if rep is not first][0]
        real = first.sup.submit

        def overloaded_once(prompt_ids, **kw):
            first.sup.submit = real
            raise EngineOverloaded("queue full (injected)")

        first.sup.submit = overloaded_once
        out = r.submit(prompts[1], max_new_tokens=5).result(timeout=60)
        assert out is not None
        assert other.placements == 1
        assert "ROUTE_REROUTE" in _reasons(r)
    finally:
        r.shutdown()


# -- tentpole: replica death mid-load ----------------------------------------

def test_replica_kill_mid_load_success_or_typed_token_identical(model):
    prompts = _prompts_shared_prefix(8, seed=13)
    ref_r = _router(model, "rtr_kill_ref")
    try:
        ref = [ref_r.submit(q, max_new_tokens=5).result(timeout=60)
               for q in prompts]
    finally:
        ref_r.shutdown()
    prev = paddle.get_flags(["FLAGS_failpoints",
                             "FLAGS_gen_restart_backoff_ms"])
    try:
        paddle.set_flags({"FLAGS_failpoints": "decode_step_raise@6",
                          "FLAGS_gen_restart_backoff_ms": 5.0})
        r = _router(model, "rtr_kill")
        try:
            ledgers = [dict(rep.sup.engine._ledger)
                       for rep in r._replicas]
            futs = [r.submit(q, max_new_tokens=5) for q in prompts]
            outs = [f.result(timeout=120) for f in futs]
            # zero requests lost: everything resolved successfully and
            # greedy decode is placement-independent, so survivors AND
            # replayed requests match the fault-free run exactly
            for a, b in zip(ref, outs):
                assert np.array_equal(a, b)
            restarts = sum(rep.sup.restarts for rep in r._replicas)
            assert restarts == 1
            # the resurrection reused the dead engine's program pack:
            # no replica's compile ledger moved
            assert [dict(rep.sup.engine._ledger)
                    for rep in r._replicas] == ledgers
            # and the replay path leaked no page anywhere in the fleet
            assert all(_drained_clean(rep) for rep in r._replicas)
        finally:
            r.shutdown()
    finally:
        paddle.set_flags(prev)


def test_stream_exactly_once_through_router_across_replay(model):
    prompts = _prompts_shared_prefix(3, seed=14, tail=3)
    ref_r = _router(model, "rtr_stream_ref", max_new_tokens=8)
    try:
        ref = [ref_r.submit(q, max_new_tokens=8).result(timeout=60)
               for q in prompts]
    finally:
        ref_r.shutdown()
    prev = paddle.get_flags(["FLAGS_failpoints",
                             "FLAGS_gen_restart_backoff_ms"])
    try:
        paddle.set_flags({"FLAGS_failpoints": "decode_step_raise@4",
                          "FLAGS_gen_restart_backoff_ms": 5.0})
        r = _router(model, "rtr_stream", max_new_tokens=8)
        try:
            streams = [r.submit_stream(q, max_new_tokens=8)
                       for q in prompts]
            collected = [[] for _ in streams]

            def drain(i):
                for tok in streams[i]:
                    collected[i].append(tok)

            ts = [threading.Thread(target=drain, args=(i,), daemon=True)
                  for i in range(len(streams))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(120)
            assert sum(rep.sup.restarts for rep in r._replicas) == 1
            for i, st in enumerate(streams):
                out = st.result(timeout=60)
                # exactly-once through the router: the streamed tokens
                # concatenate EXACTLY to the generated part across the
                # replica's restart — no duplicate, no gap
                assert collected[i] == out[len(prompts[i]):].tolist()
                assert np.array_equal(out, ref[i])
        finally:
            r.shutdown()
    finally:
        paddle.set_flags(prev)


# -- observability + lifecycle -----------------------------------------------

def test_router_registers_with_exporter_and_readyz(model):
    r = _router(model, "rtr_export")
    try:
        ready = exporter.readiness_payload()
        assert ready["engines"]["rtr_export"]["ready"]
        assert ready["engines"]["rtr_export-r0"]["ready"]
        stats = exporter.stats_payload()
        rs = stats["engines"]["rtr_export"]["router"]
        assert rs["placements_total"] == 0
        assert set(rs["replicas"]) == {"rtr_export-r0", "rtr_export-r1"}
        r.submit(np.arange(6, dtype=np.int64),
                 max_new_tokens=5).result(timeout=60)
        # health polls AND placements both feed the pressure timeline
        tl = r.pressure_timeline()
        assert tl and set(tl[-1]["replicas"]) == set(rs["replicas"])
    finally:
        r.shutdown()
    assert "rtr_export" not in exporter.readiness_payload()["engines"]


def test_router_constructor_validation(model):
    with pytest.raises(InvalidArgumentError):
        Router(model, num_replicas=0)
    with pytest.raises(InvalidArgumentError):
        Router(replicas=[])
    r = _router(model, "rtr_valid", num_replicas=1)
    try:
        with pytest.raises(InvalidArgumentError):
            Router(model, replicas=[r._replicas[0].sup])
        with pytest.raises(UnavailableError):
            r.shutdown()
            r.submit(np.arange(6, dtype=np.int64))
    finally:
        r.shutdown()  # idempotent
