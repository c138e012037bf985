"""Scheduler X-ray (ISSUE 11): per-step engine timeline, KV-pool
introspection, decision audit log, SLO burn rates.

Load-bearing anchors:

- **Exact reconciliation** — the step ring's per-iteration
  admitted/completed/expired/poisoned sums must equal the
  STAT_gen_completions / STAT_gen_timeouts / STAT_gen_poisoned deltas:
  the timeline is the counters' ledger, not an approximation.
- **Bounded + gated** — the ring is capacity-bounded and FLAGS-gated;
  flag off means zero records AND zero histogram observations (the
  bench A/B's contract).
- **Postmortem completeness** — a forced engine death's flight dump
  carries the final step-ring records and the audit tail with reason
  codes, so "why did this request wait/die" reads off the artifact.
- **SLO folding** — an injected slow-prefill load flips the TTFT
  objective to violated and recovers once the windows age out; burn
  past FLAGS_slo_max_burn_rate sheds readiness BEFORE the budget is
  gone.
"""
import importlib.util
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework import monitor
from paddle_tpu.framework.errors import InvalidArgumentError, \
    UnavailableError
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.profiler import audit, exporter, slo, step_log
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = GPTConfig.tiny(dropout=0.0)
    net = GPTForCausalLM(cfg)
    net.eval()
    return net


def _prompts(n=2, S=7, seed=0, vocab=512):
    return np.random.RandomState(seed).randint(
        0, vocab, size=(n, S)).astype("int64")


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("max_new_tokens", 5)
    kw.setdefault("request_timeout_ms", 0)
    return serving.GenerationEngine(model, **kw)


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read().decode())
        except ValueError:
            return e.code, {}


@pytest.fixture
def flightdir(tmp_path):
    prev = paddle.get_flags(["FLAGS_flight_recorder_dir",
                             "FLAGS_flight_recorder"])
    paddle.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path),
                      "FLAGS_flight_recorder": True})
    yield tmp_path
    paddle.set_flags(prev)


def _wait_for_dump(tmp_path, reason, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        hits = [p for p in tmp_path.iterdir() if reason in p.name]
        if hits:
            time.sleep(0.1)  # let the writer finish
            return hits[-1]
        time.sleep(0.02)
    raise AssertionError(f"no {reason} dump appeared in {tmp_path}")


# -- tentpole 1: the per-step ring ------------------------------------------

def test_step_ring_reconciles_and_serves_steps_endpoint(model):
    """One engine run with completions + a deadline expiry: /steps
    records reconcile EXACTLY with the outcome counters, the audit log
    carries the matching reason codes, /trace grows scheduler counter
    tracks, and stats()['kv'] exposes the ownership/headroom surface."""
    c0 = monitor.stat_get("STAT_gen_completions")
    t0 = monitor.stat_get("STAT_gen_timeouts")
    p0 = monitor.stat_get("STAT_gen_poisoned")
    h0 = monitor.histogram("engine_step_ms").count
    a0 = monitor.histogram("gen_queue_age_ms").count
    srv = exporter.MetricsServer(0)
    ids = _prompts(n=5, seed=3)
    mid_kv = {}

    def hook(eng):
        if not mid_kv and eng._num_active():
            mid_kv.update(eng.stats()["kv"])

    try:
        with _engine(model, name="xray_recon") as eng:
            eng._pre_step_hook = hook
            futs = [eng.submit(p, max_new_tokens=4) for p in ids[:4]]
            # expires (queued or mid-decode — either reconciles the
            # same way) long before 100 tokens decode on any host
            doomed = eng.submit(ids[4], max_new_tokens=100,
                                timeout_ms=20)
            for f in futs:
                assert f.result(timeout=120).shape == (11,)
            with pytest.raises(Exception):
                doomed.result(timeout=120)
            # a forced overload rejection audits too (config is a
            # per-engine copy, so this hack stays local)
            eng._cfg.max_queue_depth = 0
            with pytest.raises(serving.EngineOverloaded):
                eng.submit(ids[0], max_new_tokens=2)

            status, steps = _get(srv.url + "/steps")
            assert status == 200 and steps["enabled"]
            e = steps["engines"]["xray_recon"]
            recs = e["records"]
            assert recs, "no step records"
            status, trace = _get(srv.url + "/trace")
            s = eng.stats()
    finally:
        srv.close()

    # exact reconciliation: the ring's decision sums ARE the counters
    assert sum(r["completed"] for r in recs) == \
        monitor.stat_get("STAT_gen_completions") - c0 == 4
    assert sum(r["expired"] for r in recs) == \
        monitor.stat_get("STAT_gen_timeouts") - t0 == 1
    assert sum(r["poisoned"] for r in recs) == \
        monitor.stat_get("STAT_gen_poisoned") - p0 == 0
    assert sum(r["admitted"] for r in recs) == \
        sum(r["freed"] for r in recs)
    # record shape: every documented field present, pages drain to zero
    for f in ("it", "step", "live", "queue_depth", "oldest_age_ms",
              "pages_in_use", "free_pages", "prefill_ms", "decode_ms"):
        assert f in recs[0], f
    assert recs[-1]["pages_in_use"] == 0
    assert any(r["prefill_ms"] > 0 for r in recs)
    assert any(r["decode_ms"] > 0 for r in recs)
    # the two step histograms observed
    assert monitor.histogram("engine_step_ms").count > h0
    assert monitor.histogram("gen_queue_age_ms").count > a0
    # audit reasons: scheduler decisions with their codes, all from the
    # registered vocabulary
    reasons = [ev["reason"] for ev in e["audit"]]
    assert set(reasons) <= audit.REASONS
    assert "ADMIT" in reasons and "COMPLETE_MAX_NEW" in reasons
    assert "REJECT_QUEUE_FULL" in reasons
    assert any(r.startswith("EXPIRE") for r in reasons)
    # 5 requests through 2 slots: someone waited on a busy batch
    assert "DEFER_SLOTS" in reasons
    # chrome trace: scheduler counter tracks merged in
    counters = [ev for ev in trace["traceEvents"]
                if ev.get("ph") == "C"
                and ev.get("name") == "xray_recon scheduler"]
    assert counters and "live_slots" in counters[0]["args"]
    assert "pages_in_use" in counters[0]["args"]
    # engine stats carried the introspection surface mid-flight
    assert mid_kv and mid_kv["owners"], "hook never saw live owners"
    own = mid_kv["owners"][0]
    assert own["slot"] is not None and own["pages"]
    assert mid_kv["free_low_water"] < mid_kv["usable_pages"]
    # representative shape: bucket 8 + max_new 5 = 13 tokens
    assert "13" in mid_kv["admit_headroom"]
    assert s["kv"]["pages_in_use"] == 0
    assert s["step_log"]["enabled"] and s["step_log"]["recorded"] > 0


def test_step_ring_bounded(model):
    prev = paddle.get_flags(["FLAGS_gen_step_log_size"])
    paddle.set_flags({"FLAGS_gen_step_log_size": 8})
    try:
        with _engine(model, name="xray_bounded") as eng:
            for p in _prompts(n=3, seed=5):
                eng.generate(p, max_new_tokens=6)
            log = eng._step_log
            assert log.cap == 8
            assert log.recorded > 8
            recs = log.tail(100)
            assert len(recs) == 8
            its = [r["it"] for r in recs]
            assert its == sorted(its) and its[-1] == log.recorded
    finally:
        paddle.set_flags(prev)


def test_step_ring_flag_off_records_nothing(model):
    prev = paddle.get_flags(["FLAGS_gen_step_log"])
    paddle.set_flags({"FLAGS_gen_step_log": False})
    h0 = monitor.histogram("engine_step_ms").count
    a0 = monitor.histogram("gen_queue_age_ms").count
    try:
        with _engine(model, name="xray_off") as eng:
            for p in _prompts(n=2, seed=7):
                eng.generate(p, max_new_tokens=4)
            s = eng.stats()
        assert s["step_log"]["enabled"] is False
        assert s["step_log"]["recorded"] == 0
        # no ring → no step histograms, no /steps registration
        assert monitor.histogram("engine_step_ms").count == h0
        assert monitor.histogram("gen_queue_age_ms").count == a0
        assert "xray_off" not in step_log.steps_payload()["engines"]
        # the audit log is NOT gated by the ring flag
        assert s["step_log"]["audit_events"] > 0
    finally:
        paddle.set_flags(prev)


def test_abort_shutdown_flushes_final_record(model):
    """shutdown(drain=False) evictions must reach the ring: the final
    iteration's aborted/freed counts are flushed on the abort exit, so
    the sums still reconcile with the EVICT_SHUTDOWN audit events."""
    eng = _engine(model, name="xray_abort")
    futs = [eng.submit(p, max_new_tokens=100)
            for p in _prompts(n=2, seed=23)]
    # let admissions happen — and no longer: with decode steps launched
    # ahead of the host (ISSUE 34) the tiny model's 100 tokens take some
    # 50 ms on the CPU, so a fixed sleep would find them finished
    deadline = time.monotonic() + 30
    while eng._num_active() < 2 and time.monotonic() < deadline:
        time.sleep(0.0005)
    eng.shutdown(drain=False, timeout_s=120)
    for f in futs:
        with pytest.raises(UnavailableError):
            f.result(timeout=5)
    recs = eng._step_log.tail(10000)
    evicted = [e for e in eng._audit.tail(256)
               if e["reason"] == "EVICT_SHUTDOWN"]
    assert evicted, "no live sequence was evicted by the abort"
    assert sum(r["aborted"] for r in recs) == len(evicted)
    assert sum(r["freed"] for r in recs) == \
        sum(r["admitted"] for r in recs)
    # shutdown unregistered both logs: /steps no longer lists the
    # engine, audit tails by name come back empty
    assert "xray_abort" not in step_log.steps_payload()["engines"]
    assert audit.tail_for("xray_abort") == []


def _streamed(streams):
    """Every stream read to its end on a thread of its own; returns the
    lists (filled as tokens arrive) and the threads."""
    got = [[] for _ in streams]

    def read(i):
        try:
            for tok in streams[i]:
                got[i].append(tok)
        except Exception as e:  # noqa: BLE001 — the outcome, kept
            got[i].append(e)

    threads = [threading.Thread(target=read, args=(i,), daemon=True)
               for i in range(len(streams))]
    for t in threads:
        t.start()
    return got, threads


def test_drain_shutdown_with_a_step_in_flight_delivers_everything(model):
    """shutdown(drain=True) called while a decode step is launched and
    unread (ISSUE 34): every submitter gets its one outcome, the streamed
    tokens are the result's, and the ring's sums reconcile — a step
    launched ahead is read, never lost, before the loop exits."""
    c0 = monitor.stat_get("STAT_gen_completions")
    k0 = monitor.stat_get("STAT_gen_tokens")
    ids = _prompts(n=4, seed=31)
    eng = _engine(model, name="xray_drain_ahead", max_new_tokens=40)
    streams = [eng.submit_stream(p, max_new_tokens=30) for p in ids]
    got, threads = _streamed(streams)
    deadline = time.monotonic() + 60
    while eng._flight is None and time.monotonic() < deadline:
        time.sleep(0.0005)
    assert eng._flight is not None, "no step was ever in flight"
    eng.shutdown(drain=True, timeout_s=120)
    for t in threads:
        t.join(60)
    recs = eng._step_log.tail(10000)
    look = eng.stats()["lookahead"]
    for p, s, g in zip(ids, streams, got):
        out = s.result(timeout=5)
        assert out.shape == (7 + 30,)
        assert g == out[7:].tolist()        # each token once, in order
    assert eng._flight is None
    assert look["ahead"] > 0 and look["dropped_tokens"] == 0
    assert sum(r["completed"] for r in recs) == \
        monitor.stat_get("STAT_gen_completions") - c0 == 4
    assert sum(r["tokens"] for r in recs) == \
        monitor.stat_get("STAT_gen_tokens") - k0 == 4 * 30
    assert sum(r["admitted"] for r in recs) == \
        sum(r["freed"] for r in recs) == 4
    assert sum(r["ahead"] for r in recs) == look["ahead"]
    assert recs[-1]["pages_in_use"] == 0


# -- tentpole 2: KV-pool introspection --------------------------------------

def test_kv_introspection_unit():
    c = PagedKVCache(num_layers=2, num_heads=2, head_dim=4, page_size=4,
                     num_pages=9, pages_per_seq=3)
    assert c.headroom([4, 8, 12, 13]) == {4: 8, 8: 4, 12: 2, 13: 0}
    row1 = c.alloc(1, 9)                      # 3 pages
    c.alloc(2, 4)                             # 1 page
    own = c.owners()
    assert sorted(own) == [1, 2]
    assert own[1] == list(row1[:3]) and len(own[2]) == 1
    assert c.headroom([8])[8] == 2            # 4 free // 2
    st = c.stats()
    assert st["free_low_water"] == 4 and st["free_high_water"] == 8
    c.free(1)
    c.free(2)
    st = c.stats()
    assert st["free_low_water"] == 4          # watermark sticks
    assert st["free_high_water"] == 8
    assert c.headroom([12])[12] == 2
    # mutating the returned map must not corrupt the allocator
    c.owners().clear()
    assert c.alloc(3, 4).shape == (3,)


# -- tentpole 3: the decision audit log -------------------------------------

def test_audit_jsonl_sink_and_defer_pages(model, tmp_path):
    sink = tmp_path / "audit.jsonl"
    prev = paddle.get_flags(["FLAGS_gen_audit_log"])
    paddle.set_flags({"FLAGS_gen_audit_log": str(sink)})
    try:
        # 7 usable pages, 3 pages per request: the third concurrent
        # request must defer on pages (slots are free: max_slots=3)
        with _engine(model, max_slots=3, num_pages=8,
                     name="xray_audit") as eng:
            futs = [eng.submit(p, max_new_tokens=5)
                    for p in _prompts(n=3, seed=9)]
            for f in futs:
                assert f.result(timeout=120).shape == (12,)
            tail = eng._audit.tail(256)
    finally:
        paddle.set_flags(prev)
    reasons = [ev["reason"] for ev in tail]
    assert reasons.count("ADMIT") == 3
    assert "DEFER_PAGES" in reasons
    assert reasons.count("COMPLETE_MAX_NEW") == 3
    # the JSONL sink mirrors the ring, line for line
    lines = [json.loads(ln) for ln in
             sink.read_text().strip().splitlines()]
    assert [ev["reason"] for ev in lines] == reasons
    assert all(ev["engine"] == "xray_audit" for ev in lines)
    # closed vocabulary: an unknown code is an immediate error
    with pytest.raises(InvalidArgumentError):
        audit.AuditLog("xray_vocab").audit("NOT_A_CODE")


def test_flight_dump_has_step_and_audit_tails(model, flightdir):
    """Satellite: a forced engine death's dump shows the scheduler
    state that led to the failure — final step-ring records AND the
    audit tail with reason codes."""
    boom = RuntimeError("injected step-loop failure")

    def hook(eng):
        if eng._steps_total >= 2:
            raise boom

    eng = _engine(model, name="xray_death")
    eng._pre_step_hook = hook
    fut = eng.submit(_prompts()[0], max_new_tokens=50)
    with pytest.raises(UnavailableError):
        fut.result(timeout=120)
    path = _wait_for_dump(flightdir, "gen_engine_death")
    dump = json.loads(path.read_text())
    extra = dump["extra"]
    recs = extra["step_log_tail"]
    assert recs, "dump carries no step-ring tail"
    assert recs[-1]["live"] == 1 and recs[-1]["step"] >= 2
    assert sum(r["admitted"] for r in recs) == 1
    reasons = [ev["reason"] for ev in extra["audit_tail"]]
    assert "ADMIT" in reasons and "ENGINE_DIED" in reasons
    assert set(reasons) <= audit.REASONS
    eng.shutdown(drain=False, timeout_s=30)


# -- tentpole 4: SLO burn rates ---------------------------------------------

def test_slo_burn_flips_and_recovers_then_sheds_readiness(model):
    """Injected slow prefill violates a TTFT objective (fast+slow
    window burn >= 1, /slo + gauges agree), recovery follows once the
    windows age out; then an error-rate burn past
    FLAGS_slo_max_burn_rate flips health()/readyz to not-ready."""
    prev = paddle.get_flags([
        "FLAGS_slo_ttft_p99_ms", "FLAGS_slo_windows_s",
        "FLAGS_slo_error_rate", "FLAGS_slo_max_burn_rate"])
    slo.reset()
    srv = exporter.MetricsServer(0)
    eng = _engine(model, name="xray_slo")
    try:
        paddle.set_flags({"FLAGS_slo_ttft_p99_ms": 200.0,
                          "FLAGS_slo_windows_s": "1,2"})
        orig = eng._prefill_jit

        def slow_prefill(*a, **kw):
            time.sleep(0.4)     # >> the 200ms objective
            return orig(*a, **kw)

        eng._prefill_jit = slow_prefill
        for p in _prompts(n=3, seed=15):
            eng.generate(p, max_new_tokens=3)
        ev = slo.evaluate("xray_slo")["xray_slo"]["ttft"]
        assert ev["violated"]
        assert ev["windows"][0]["burn_rate"] >= 1.0
        assert ev["windows"][0]["violations"] == 3
        status, body = _get(srv.url + "/slo")
        assert status == 200 and body["enabled"]
        assert body["engines"]["xray_slo"]["ttft"]["violated"]
        # the burn-rate gauge rides /metrics as a gauge
        with urllib.request.urlopen(srv.url + "/metrics",
                                    timeout=10) as r:
            text = r.read().decode()
        assert ("# TYPE paddle_tpu_stat_slo_ttft_burn_bp_w1 gauge"
                in text)
        # recovery: restore fast prefill, let both windows age out
        eng._prefill_jit = orig
        time.sleep(2.2)
        for p in _prompts(n=3, seed=16):
            eng.generate(p, max_new_tokens=3)
        ev = slo.evaluate("xray_slo")["xray_slo"]["ttft"]
        assert not ev["violated"], ev
        assert ev["windows"][0]["violations"] == 0

        # readiness shedding: error-rate burn over the threshold
        assert eng.health()["ready"]
        paddle.set_flags({"FLAGS_slo_error_rate": 0.5,
                          "FLAGS_slo_max_burn_rate": 1.0})
        for _ in range(4):
            slo.observe_request("xray_slo", ok=False)
        h = eng.health()
        assert not h["ready"] and "slo error_rate" in h["reason"]
        payload = exporter.readiness_payload()
        assert payload["engines"]["xray_slo"]["ready"] is False
        slo.reset()
        assert eng.health()["ready"]
    finally:
        eng._pre_step_hook = None
        paddle.set_flags(prev)
        slo.reset()
        eng.shutdown()
        srv.close()


# -- satellite: scrapes racing engine teardown ------------------------------

def test_scrapes_race_engine_death_and_shutdown(model):
    """Concurrent /stats + /metrics + /steps scrapes must never 500
    while an engine dies mid-scrape or shuts down/unregisters."""
    srv = exporter.MetricsServer(0)
    stop = threading.Event()
    failures = []

    def scraper(path):
        while not stop.is_set():
            try:
                with urllib.request.urlopen(srv.url + path,
                                            timeout=10) as r:
                    body = r.read()
                    if r.status != 200:
                        failures.append((path, r.status))
                    elif path != "/metrics":
                        json.loads(body)
            except urllib.error.HTTPError as e:
                failures.append((path, e.code))
            except Exception as e:  # noqa: BLE001
                failures.append((path, repr(e)))

    threads = [threading.Thread(target=scraper, args=(p,), daemon=True)
               for p in ("/stats", "/metrics", "/steps")
               for _ in range(2)]
    for t in threads:
        t.start()
    try:
        # arm 1: death mid-scrape
        def hook(eng):
            if eng._steps_total >= 1:
                raise RuntimeError("die under scrape")

        eng1 = _engine(model, name="xray_race_die")
        eng1._pre_step_hook = hook
        with pytest.raises(UnavailableError):
            eng1.submit(_prompts()[0], max_new_tokens=20)\
                .result(timeout=120)
        # arm 2: clean shutdown + unregister mid-scrape
        eng2 = _engine(model, name="xray_race_drain")
        f = eng2.submit(_prompts()[1], max_new_tokens=10)
        eng2.shutdown(drain=True, timeout_s=120)
        assert f.result(timeout=5).shape == (17,)
        time.sleep(0.3)  # several scrape rounds against the torn state
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        eng1.shutdown(drain=False, timeout_s=30)
        srv.close()
    assert not failures, failures[:5]


# -- satellite: monitor as the single gauge registry ------------------------

def test_gauge_registry_is_single_source():
    name_ud = "STAT_xray_test_updown"
    name_lv = "STAT_xray_test_level"
    monitor.register_gauge(name_ud, updown=True)
    monitor.stat_add(name_ud, 3)
    monitor.stat_set(name_lv, 7)
    assert monitor.gauge_kind(name_ud) == "updown"
    assert monitor.gauge_kind(name_lv) == "level"
    # the engines' queue depths registered themselves at import
    assert monitor.gauge_kind("STAT_gen_queue_depth") == "updown"
    assert monitor.gauge_kind("STAT_serving_queue_depth") == "updown"
    assert monitor.gauge_kind("STAT_train_steps") is None
    # exporter renders straight from the registry
    text = exporter.render_prometheus()
    assert f"# TYPE paddle_tpu_{name_ud.lower()} gauge" in text
    assert f"# TYPE paddle_tpu_{name_lv.lower()} gauge" in text
    assert "# TYPE paddle_tpu_stat_gen_queue_depth gauge" in text
    # relay: updown RELAYS (deltas sum correctly), level is skipped
    delta = monitor.drain_deltas()
    assert delta and delta["stats"].get(name_ud) == 3
    assert name_lv not in delta["stats"]
    assert monitor.stat_get(name_lv) == 7  # level untouched by drain


# -- satellite: the engine_report tool --------------------------------------

def _engine_report():
    spec = importlib.util.spec_from_file_location(
        "engine_report", os.path.join(ROOT, "tools", "engine_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_engine_report_renders_steps_and_dump(model, tmp_path, capsys):
    with _engine(model, name="xray_report") as eng:
        for p in _prompts(n=2, seed=19):
            eng.generate(p, max_new_tokens=4)
        payload = step_log.steps_payload()
    steps_path = tmp_path / "steps.json"
    steps_path.write_text(json.dumps(payload))
    mod = _engine_report()
    assert mod.main([str(steps_path), "--engine", "xray_report"]) == 0
    out = capsys.readouterr().out
    assert "engine xray_report" in out
    assert "ADMIT" in out and "COMPLETE_MAX_NEW" in out
    assert "decision audit" in out
    # --json round trip with reconciled summary
    assert mod.main([str(steps_path), "--engine", "xray_report",
                     "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)["xray_report"]
    assert rep["summary"]["completed"] == 2
    assert rep["summary"]["admitted"] == rep["summary"]["freed"] == 2
    # flight-dump input shape (what _die writes) renders too
    dump_path = tmp_path / "flightrec-dump.json"
    dump_path.write_text(json.dumps({
        "reason": "gen_engine_death",
        "extra": {"engine": "xray_report",
                  "step_log_tail": payload["engines"]["xray_report"]
                  ["records"][-4:],
                  "audit_tail": payload["engines"]["xray_report"]
                  ["audit"][-4:]}}))
    assert mod.main([str(dump_path)]) == 0
    out = capsys.readouterr().out
    assert "from flight dump: gen_engine_death" in out
    # unknown engine errors out instead of reporting nothing
    assert mod.main([str(steps_path), "--engine", "nope"]) == 1
