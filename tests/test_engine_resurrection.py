"""Engine resurrection (ISSUE 15): deterministic failpoints, supervised
restart with request replay, degraded modes, per-lane restart.

The load-bearing anchors:

- **Exactly-once across restarts** — with the supervisor on and an
  injected decode/prefill fault, every in-flight and queued request
  either completes with greedy output token-identical to a fault-free
  run, or fails with a typed error within its retry budget; a stream
  delivers each token exactly once (no duplicate, no gap) across the
  restart.
- **Zero new traces** — the rebuilt engine reuses the dead one's
  program pack; the shared compile ledger must not move across a
  restart (warmup re-runs from jit cache).
- **Zero leaked pages** — every fault path frees its pages; after a
  drain shutdown the pool owns nothing.
- **Breaker/degraded verdicts are observable** — /readyz-shaped
  health() carries the breaker reason, audit carries the new ISSUE 15
  reason codes, the step ring carries the incarnation.
"""
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework import monitor
from paddle_tpu.framework.errors import (FatalError, InvalidArgumentError,
                                         ResourceExhaustedError,
                                         UnavailableError)
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.profiler import step_log
from paddle_tpu.serving import failpoints
from paddle_tpu.serving.failpoints import InjectedFault
from paddle_tpu.serving.restart import CrashBreaker, RestartBackoff


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
    names = {k: v for k, v in kw.items()}
    old = paddle.get_flags(list(names))
    paddle.set_flags(names)
    try:
        yield
    finally:
        paddle.set_flags(old)


def _prompts(n=4, S=7, seed=0, vocab=256):
    return np.random.RandomState(seed).randint(
        0, vocab, size=(n, S)).astype("int64")


def _sup(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("max_new_tokens", 5)
    kw.setdefault("request_timeout_ms", 0)
    kw.setdefault("name", "resurrect")
    return serving.EngineSupervisor(model, **kw)


def _eng(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("max_new_tokens", 5)
    kw.setdefault("request_timeout_ms", 0)
    return serving.GenerationEngine(model, **kw)


# -- failpoints registry -----------------------------------------------------

def test_failpoints_unset_is_noop_and_counts_nothing():
    assert failpoints.fire("decode_step_raise") is None
    failpoints.maybe_raise("decode_step_raise")  # no spec → no raise
    assert failpoints.snapshot()["hits"] == {}


def test_failpoints_nth_hit_is_one_shot():
    with flags(FLAGS_failpoints="decode_step_raise@3"):
        hits = [failpoints.fire("decode_step_raise") for _ in range(6)]
    assert [h is not None for h in hits] == [False, False, True,
                                            False, False, False]
    snap = failpoints.snapshot()
    assert snap["hits"]["decode_step_raise"] == 6
    assert snap["fired"]["decode_step_raise"] == 1


def test_failpoints_every_k_and_arg():
    with flags(FLAGS_failpoints="slow_step_ms@every:2:40"):
        vals = [failpoints.fire("slow_step_ms") for _ in range(5)]
    assert [v is not None for v in vals] == [False, True, False, True,
                                            False]
    assert all(v == 40.0 for v in vals if v is not None)
    # other sites stay silent under a spec that doesn't name them
    with flags(FLAGS_failpoints="slow_step_ms@every:2:40"):
        assert failpoints.fire("prefill_raise") is None


def test_failpoints_maybe_raise_and_reset():
    with flags(FLAGS_failpoints="prefill_raise@1"):
        with pytest.raises(InjectedFault):
            failpoints.maybe_raise("prefill_raise")
        failpoints.maybe_raise("prefill_raise")  # one-shot spent
        failpoints.reset()
        with pytest.raises(InjectedFault):  # reset → fresh schedule
            failpoints.maybe_raise("prefill_raise")


def test_failpoints_bad_spec_raises():
    with flags(FLAGS_failpoints="no_such_site@1"):
        with pytest.raises(InvalidArgumentError):
            failpoints.fire("decode_step_raise")
    failpoints.reset()
    with flags(FLAGS_failpoints="decode_step_raise"):
        with pytest.raises(InvalidArgumentError):
            failpoints.fire("decode_step_raise")


# -- restart primitives ------------------------------------------------------

def test_restart_backoff_schedule_and_reset():
    b = RestartBackoff(10.0)
    assert [b.next_delay_ms() for _ in range(4)] == [10.0, 20.0, 40.0,
                                                    80.0]
    b.reset()
    assert b.next_delay_ms() == 10.0
    # cap at 32x base
    for _ in range(20):
        d = b.next_delay_ms()
    assert d == 320.0


def test_crash_breaker_opens_and_latches():
    br = CrashBreaker(threshold=3, window_s=60.0)
    assert not br.record(now=0.0)
    assert not br.record(now=1.0)
    assert br.record(now=2.0)       # third death in window → open
    assert br.is_open
    assert br.record(now=500.0)     # latched: stays open forever
    st = br.state()
    assert st["open"] and st["threshold"] == 3
    br.reset()
    assert not br.is_open


def test_crash_breaker_window_expiry():
    br = CrashBreaker(threshold=2, window_s=5.0)
    assert not br.record(now=0.0)
    assert not br.record(now=10.0)  # first event aged out of the window
    assert br.record(now=11.0)


def test_backoff_note_death_quiet_window():
    b = RestartBackoff(10.0)
    assert not b.note_death(30.0, now=0.0)   # first death: not quiet
    assert b.next_delay_ms() == 10.0
    assert not b.note_death(30.0, now=5.0)   # consecutive: escalates
    assert b.next_delay_ms() == 20.0
    # a gap beyond the quiet window resets the escalation
    assert b.note_death(30.0, now=100.0)
    assert b.next_delay_ms() == 10.0


def test_crash_breaker_trip_latches():
    br = CrashBreaker(threshold=100, window_s=60.0)
    br.trip()
    assert br.is_open
    assert br.record()  # open stays the verdict for later records


# -- supervised restart + replay --------------------------------------------

def test_decode_fault_restart_token_identical(model):
    prompts = _prompts(4)
    with _eng(model, name="resurrect_ref") as eng:
        ref = [eng.submit(p, max_new_tokens=5).result() for p in prompts]
    with flags(FLAGS_failpoints="decode_step_raise@3",
               FLAGS_gen_restart_backoff_ms=5.0):
        sup = _sup(model)
        led0 = dict(sup.engine._ledger)
        futs = [sup.submit(p, max_new_tokens=5) for p in prompts]
        outs = [f.result(timeout=60) for f in futs]
        # every request completed token-identical to the fault-free run
        for a, b in zip(ref, outs):
            assert np.array_equal(a, b)
        assert sup.restarts == 1
        assert sup.incarnation == 1
        assert sup.replayed >= 1
        # zero new in-process traces: the rebuilt engine re-warmed from
        # the shared program pack's jit caches
        assert dict(sup.engine._ledger) == led0
        # the step ring spans both generations
        payload = step_log.steps_payload()
        incs = {r["incarnation"]
                for r in payload["engines"]["resurrect"]["records"]}
        assert incs == {0, 1}
        # audit trail carries the restart + replays next to the death
        reasons = [e["reason"]
                   for e in payload["engines"]["resurrect"]["audit"]]
        assert "ENGINE_RESTART" in reasons
        assert "REPLAY_ADMIT" in reasons
        assert "ENGINE_DIED" not in reasons  # supervised: nothing stranded
        h = sup.health()
        assert h["ready"] and h["incarnation"] == 1 and h["restarts"] == 1
        s = sup.stats()
        assert s["supervisor"]["restarts"] == 1
        assert s["supervisor"]["last_recovery_ms"] is not None
        assert s["pages"]["pages_in_use"] == 0
        sup.shutdown()


def test_stream_exactly_once_across_restart(model):
    prompts = _prompts(3, seed=5)
    with _eng(model, name="resurrect_sref",
              prefill_buckets=(8, 16)) as eng:
        ref = [eng.submit(p, max_new_tokens=8).result() for p in prompts]
    with flags(FLAGS_failpoints="decode_step_raise@4",
               FLAGS_gen_restart_backoff_ms=5.0):
        sup = _sup(model, name="resurrect_s", prefill_buckets=(8, 16),
                   max_new_tokens=8)
        streams = [sup.submit_stream(p, max_new_tokens=8)
                   for p in prompts]
        collected = [[] for _ in streams]

        def drain(i):
            for tok in streams[i]:
                collected[i].append(tok)

        ts = [threading.Thread(target=drain, args=(i,), daemon=True)
              for i in range(len(streams))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert sup.restarts == 1
        for i, st in enumerate(streams):
            out = st.result(timeout=30)
            # exactly-once: the streamed tokens concatenate EXACTLY to
            # the generated part — a duplicate or a gap across the
            # restart boundary breaks this equality
            assert collected[i] == out[len(prompts[i]):].tolist()
            assert np.array_equal(out, ref[i])
        sup.shutdown()


def test_death_with_a_step_in_flight_replays_each_token_once(model):
    """The engine dies (a prefill fault: not a step failpoint, so decode
    steps are launched ahead all along) while a decode step is launched
    and unread (ISSUE 34). That step is dropped, not read: nothing of it
    was staged or counted, so the manifest holds exactly what was
    delivered, the replay derives the dropped token again, and every
    stream still delivers each token once; every submitter gets one
    outcome and the ring's sums reconcile over both incarnations."""
    prompts = _prompts(3, seed=13)
    # the third waits for a slot: it is admitted when the short second
    # ends, and its prefill (the third) kills the engine under the long
    # first one's step in flight
    lengths = [100, 8, 8]
    with _eng(model, name="resurrect_fref", max_new_tokens=100) as eng:
        ref = [eng.submit(p, max_new_tokens=n).result()
               for p, n in zip(prompts, lengths)]
    k0 = monitor.stat_get("STAT_gen_tokens")
    c0 = monitor.stat_get("STAT_gen_completions")
    at_death = []
    with flags(FLAGS_failpoints="prefill_raise@3",
               FLAGS_gen_restart_backoff_ms=5.0):
        sup = _sup(model, name="resurrect_f", max_new_tokens=100)
        first = sup.engine
        real_die = first._die

        def die(e):     # what the dying engine held when it died
            at_death.append(first._flight is not None)
            return real_die(e)
        first._die = die
        streams = [sup.submit_stream(p, max_new_tokens=n)
                   for p, n in zip(prompts, lengths)]
        collected = [[] for _ in prompts]

        def drain(i):
            for tok in streams[i]:
                collected[i].append(tok)

        ts = [threading.Thread(target=drain, args=(i,), daemon=True)
              for i in range(len(streams))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert sup.restarts == 1
        assert at_death == [True], "the death did not find a step in flight"
        for i, st in enumerate(streams):
            out = st.result(timeout=30)
            assert collected[i] == out[len(prompts[i]):].tolist()
            assert np.array_equal(out, ref[i])
        recs = step_log.steps_payload()["engines"]["resurrect_f"]["records"]
        assert {r["incarnation"] for r in recs} == {0, 1}
        assert sum(r["completed"] for r in recs) == \
            monitor.stat_get("STAT_gen_completions") - c0 == 3
        assert sum(r["tokens"] for r in recs) == \
            monitor.stat_get("STAT_gen_tokens") - k0
        assert sup.stats()["pages"]["pages_in_use"] == 0
        sup.shutdown()


def test_death_with_a_prefill_unread_replays_each_token_once(model):
    """Three requests admitted in one iteration: the first two prefills are
    launched with their first tokens on the device and left unread (the
    decode step that would be launched behind them never is), and the third
    prefill kills the engine. The unread prefills are dropped, not read:
    nothing of them was staged, counted or streamed, so every request
    replays from its prompt on the rebuilt engine and every stream delivers
    each token once; the ring's sums reconcile over both incarnations."""
    prompts = _prompts(3, seed=17)
    with _eng(model, name="resurrect_pref", max_slots=3,
              max_new_tokens=9) as eng:
        ref = [eng.submit(p, max_new_tokens=9).result() for p in prompts]
    k0 = monitor.stat_get("STAT_gen_tokens")
    c0 = monitor.stat_get("STAT_gen_completions")
    at_death = []
    with flags(FLAGS_failpoints="prefill_raise@3",
               FLAGS_gen_restart_backoff_ms=5.0):
        sup = _sup(model, name="resurrect_p", max_slots=3, max_new_tokens=9)
        first = sup.engine
        real_die = first._die

        def die(e):     # what the dying engine held when it died
            at_death.append(([p.req.rid for p in first._unread],
                             [len(r.toks) for r in first._slots
                              if r is not None]))
            return real_die(e)
        first._die = die
        with first._cv:     # all three admitted by one `_admit`
            streams = [sup.submit_stream(p, max_new_tokens=9)
                       for p in prompts]
        collected = [[int(t) for t in st] for st in streams]
        assert sup.restarts == 1
        (unread, generated), = at_death
        assert len(unread) == 2 and generated == [0, 0, 0]
        for i, st in enumerate(streams):
            out = st.result(timeout=30)
            assert collected[i] == out[len(prompts[i]):].tolist()
            np.testing.assert_array_equal(out, ref[i])
        recs = step_log.steps_payload()["engines"]["resurrect_p"]["records"]
        assert {r["incarnation"] for r in recs} == {0, 1}
        assert sum(r["completed"] for r in recs) == \
            monitor.stat_get("STAT_gen_completions") - c0 == 3
        assert sum(r["tokens"] for r in recs) == \
            monitor.stat_get("STAT_gen_tokens") - k0 == 3 * 9
        assert sup.stats()["pages"]["pages_in_use"] == 0
        sup.shutdown()


def test_prefill_fault_restart(model):
    prompts = _prompts(2, seed=9)
    with _eng(model, name="resurrect_pref") as eng:
        ref = [eng.submit(p, max_new_tokens=5).result() for p in prompts]
    with flags(FLAGS_failpoints="prefill_raise@1",
               FLAGS_gen_restart_backoff_ms=5.0):
        sup = _sup(model, name="resurrect_p")
        outs = [sup.submit(p, max_new_tokens=5).result(timeout=60)
                for p in prompts]
        for a, b in zip(ref, outs):
            assert np.array_equal(a, b)
        assert sup.restarts == 1
        assert sup.stats()["pages"]["pages_in_use"] == 0
        sup.shutdown()


def test_retry_exhausted_and_breaker_open(model):
    # every step dies: the request burns its whole retry budget, then
    # the crash storm opens the breaker
    with flags(FLAGS_failpoints="decode_step_raise@every:1",
               FLAGS_gen_restart_backoff_ms=1.0):
        sup = _sup(model, name="resurrect_b", retry_limit=1,
                   breaker_threshold=3, breaker_window_s=60.0)
        fut = sup.submit(_prompts(1)[0], max_new_tokens=5)
        # death 1 → replay (retries=1) → death 2 → budget spent: typed
        with pytest.raises(UnavailableError):
            fut.result(timeout=60)
        # a third request drives death 3 → the breaker opens
        with pytest.raises(UnavailableError):
            sup.submit(_prompts(1)[0], max_new_tokens=5).result(
                timeout=60)
        deadline = time.time() + 30
        while not sup._breaker.is_open and time.time() < deadline:
            time.sleep(0.05)
        h = sup.health()
        assert not h["ready"] and h["breaker_open"]
        assert "breaker open" in h["reason"]
        with pytest.raises(UnavailableError):
            sup.submit(_prompts(1)[0], max_new_tokens=5)
        s = sup.stats()["supervisor"]
        assert s["breaker"]["open"]
        assert s["retry_exhausted"] >= 1
        sup.shutdown()


def test_die_resolution_race_dedupes_by_rid(model):
    """A request whose outcome is already STAGED when the engine dies
    must observe that outcome, never the death error too (the _die
    resolution race): the staged result wins, the stream ends cleanly."""
    eng = _eng(model, name="resurrect_race")
    eng.shutdown()  # step loop parked; white-box staging below
    from paddle_tpu.serving.generation import TokenStream, _GenRequest
    from concurrent.futures import Future
    stream = TokenStream(Future())
    req = _GenRequest(np.arange(4, dtype=np.int32), 3, None, False, 1.0,
                      stream.future, None, 0.0, None, stream=stream)
    eng._slots[0] = req  # still slot-resident, as mid-iteration
    done = np.arange(7, dtype=np.int32)
    eng._resolve_req_later(req, result=done)
    eng._die(RuntimeError("mid-iteration death"))
    # the future carries the staged RESULT, not the death error
    assert np.array_equal(req.future.result(timeout=5), done)
    # the stream ends cleanly (END sentinel), no error ever queued
    assert list(stream) == []
    eng._slots[0] = None


def test_replay_entry_delivered_keeps_residual_skip():
    """A from-scratch stream replay interrupted by a SECOND death must
    not re-deliver the tokens the first incarnation already streamed:
    `delivered` = generated here + suppressions still owed, and the
    continuation skip covers any delivered-beyond-generated residue."""
    from concurrent.futures import Future
    from paddle_tpu.serving.generation import (ReplayEntry, TokenStream,
                                               _GenRequest)
    stream = TokenStream(Future())
    req = _GenRequest(np.arange(4, dtype=np.int32), 8, None, False, 1.0,
                      stream.future, None, 0.0, None, stream=stream)
    req.toks = [5, 6]       # re-derived so far (both were suppressed)
    req.skip_stream = 3     # suppressions still owed from delivered=5
    entry = ReplayEntry(req, queued=False)
    assert entry.delivered == 5
    # continuation replay: 2 generated tokens ride in the prompt, so 3
    # of the 5 delivered tokens still need suppressing
    assert max(0, entry.delivered - len(entry.toks)) == 3
    # a non-stream never suppresses
    req2 = _GenRequest(np.arange(4, dtype=np.int32), 8, None, False,
                       1.0, Future(), None, 0.0, None)
    req2.toks = [5, 6]
    assert ReplayEntry(req2, queued=False).delivered == 0


# -- degraded modes ----------------------------------------------------------

def test_poison_storm_flips_spec_off(model):
    prompt = _prompts(1, seed=3)[0]
    with _eng(model, name="resurrect_dref") as eng:
        ref = eng.submit(prompt, max_new_tokens=5).result()
    with flags(FLAGS_gen_poison_degrade_k=2,
               FLAGS_gen_degraded_window_s=60.0):
        eng = _eng(model, name="resurrect_d", spec_k=2)
        led0 = dict(eng._ledger)
        # with the degrade armed, BOTH programs were warmed
        assert any(k.startswith("verify[") for k in led0)
        assert any(k.startswith("decode[") for k in led0)
        with flags(FLAGS_failpoints="decode_poison_nan@every:1"):
            for _ in range(2):
                with pytest.raises(FatalError):
                    eng.submit(prompt, max_new_tokens=5).result(
                        timeout=30)
        assert eng.stats()["degraded"]["spec_off"]
        # the flip is audited and the engine keeps serving — through
        # the PRE-WARMED decode program, with zero new compiles
        out = eng.submit(prompt, max_new_tokens=5).result(timeout=30)
        assert np.array_equal(out, ref)
        assert dict(eng._ledger) == led0
        payload = step_log.steps_payload()
        reasons = [e["reason"]
                   for e in payload["engines"]["resurrect_d"]["audit"]]
        assert "DEGRADED_SPEC_OFF" in reasons
        eng.shutdown()


def test_degraded_spec_off_survives_restart(model):
    prompt = _prompts(1, seed=4)[0]
    with flags(FLAGS_gen_poison_degrade_k=1,
               FLAGS_gen_degraded_window_s=60.0,
               FLAGS_gen_restart_backoff_ms=1.0):
        sup = _sup(model, name="resurrect_ds", spec_k=2)
        with flags(FLAGS_failpoints="decode_poison_nan@1"):
            with pytest.raises(FatalError):
                sup.submit(prompt, max_new_tokens=5).result(timeout=30)
        assert sup.stats()["degraded"]["spec_off"]
        with flags(FLAGS_failpoints="decode_step_raise@1"):
            failpoints.reset()
            out = sup.submit(prompt, max_new_tokens=5).result(timeout=60)
        assert sup.restarts == 1
        # the manifest carried the verdict: the rebuilt engine starts
        # degraded instead of re-learning the storm
        assert sup.stats()["degraded"]["spec_off"]
        assert out is not None
        sup.shutdown()


def test_exhaust_clamp_fails_fast_then_clears(model):
    with flags(FLAGS_gen_exhaust_clamp_k=5,
               FLAGS_gen_degraded_window_s=60.0,
               FLAGS_failpoints="slow_step_ms@every:1:25"):
        # pool sized so request A's worst case takes EVERY usable page
        eng = _eng(model, name="resurrect_c", max_slots=3,
                   num_pages=13, max_new_tokens=40)
        pA = _prompts(1, seed=1)[0]
        futA = eng.submit(pA, max_new_tokens=40)
        # B and C defer on pages → 2 exhaustion events → clamp
        futB = eng.submit(pA, max_new_tokens=5)
        futC = eng.submit(pA, max_new_tokens=5)
        deadline = time.time() + 20
        while not eng._admit_clamped and time.time() < deadline:
            time.sleep(0.02)
        assert eng._admit_clamped
        # clamped: an uncoverable submit fails FAST with a typed error
        with pytest.raises(ResourceExhaustedError):
            eng.submit(pA, max_new_tokens=5)
        assert eng.stats()["degraded"]["admit_clamped"]
        # A finishes → pages free → B admits → clamp clears
        futA.result(timeout=90)
        futB.result(timeout=90)
        futC.result(timeout=90)
        deadline = time.time() + 10
        while eng._admit_clamped and time.time() < deadline:
            time.sleep(0.02)
        assert not eng._admit_clamped
        paddle.set_flags({"FLAGS_failpoints": ""})
        futD = eng.submit(pA, max_new_tokens=5)
        assert futD.result(timeout=30) is not None
        payload = step_log.steps_payload()
        reasons = [e["reason"]
                   for e in payload["engines"]["resurrect_c"]["audit"]]
        assert "DEGRADED_ADMIT_CLAMP" in reasons
        eng.shutdown()


# -- per-lane restart (InferenceEngine) --------------------------------------

class _LaneKiller(BaseException):
    pass


def test_lane_restart_restores_capacity():
    calls = {"n": 0}

    def flaky(arrays):
        calls["n"] += 1
        if calls["n"] == 2:
            raise _LaneKiller("transient")
        return [np.asarray(arrays[0]) * 2.0]

    with flags(FLAGS_serving_lane_restarts=2,
               FLAGS_gen_restart_backoff_ms=5.0):
        eng = serving.InferenceEngine(
            [flaky], name="lane_restart", max_batch_size=4,
            max_batch_delay_ms=0.5, batch_buckets=(4,),
            request_timeout_ms=0, warmup=False)
        x = np.ones((1, 3), np.float32)
        assert eng.run([x])[0][0, 0] == 2.0
        with pytest.raises(UnavailableError):
            eng.run([x])  # rides the dying lane
        # the lane slot is rebuilt in place: capacity restored, the
        # engine keeps serving through the SAME lane index
        out = eng.run([x], timeout_ms=10000)
        assert out[0][0, 0] == 2.0
        lane = eng.stats()["lanes"][0]
        assert lane["alive"] and lane["restarts"] == 1
        assert eng.health()["ready"]
        eng.shutdown()


def test_lane_restart_budget_exhausts_to_permanent_death():
    def always_dies(arrays):
        raise _LaneKiller("permanent")

    with flags(FLAGS_serving_lane_restarts=1,
               FLAGS_gen_restart_backoff_ms=1.0):
        eng = serving.InferenceEngine(
            [always_dies], name="lane_exhaust", max_batch_size=4,
            max_batch_delay_ms=0.5, batch_buckets=(4,),
            request_timeout_ms=0, warmup=False)
        x = np.ones((1, 3), np.float32)
        with pytest.raises(UnavailableError):
            eng.run([x])
        # the restarted lane dies again; budget spent → permanently out
        with pytest.raises(UnavailableError):
            eng.run([x], timeout_ms=10000)
        deadline = time.time() + 10
        while time.time() < deadline:
            lanes = eng.stats()["lanes"]
            if not any(l["alive"] for l in lanes):
                break
            time.sleep(0.02)
        assert not any(l["alive"] for l in eng.stats()["lanes"])
        eng.shutdown()


def test_lane_restarts_default_off_keeps_legacy_death():
    def dies_once(arrays):
        raise _LaneKiller("boom")

    eng = serving.InferenceEngine(
        [dies_once], name="lane_legacy", max_batch_size=4,
        max_batch_delay_ms=0.5, batch_buckets=(4,),
        request_timeout_ms=0, warmup=False)
    x = np.ones((1, 3), np.float32)
    with pytest.raises(UnavailableError):
        eng.run([x])
    assert not eng.stats()["lanes"][0]["alive"]
    assert eng.stats()["lanes"][0]["restarts"] == 0
    eng.shutdown()


# -- report plumbing ---------------------------------------------------------

def test_reports_carry_incarnation():
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import engine_report
    import latency_report
    recs = [{"it": 1, "incarnation": 0, "decode_ms": 1.0, "tokens": 2},
            {"it": 2, "incarnation": 1, "decode_ms": 1.0, "tokens": 2}]
    summ = engine_report.summarize(recs)
    assert summ["incarnations"] == [0, 1]
    assert summ["restarts_in_window"] == 1
    # pre-ISSUE-15 records read incarnation 0 by default
    assert engine_report.summarize(
        [{"it": 1}])["restarts_in_window"] == 0
    evs = [{"name": "reqspan:7:g:slot0:n=5:ttft=1.0,tpot=2.0,e=9.0,"
                    "pfx=0,acc=0,inc=1", "ts": 1.0},
           {"name": "reqspan:8:g:slot1:n=3:ttft=1.0,tpot=2.0,e=4.0",
            "ts": 2.0}]
    gens = latency_report.parse_gen_trace(None, events=evs)
    assert [g["inc"] for g in gens] == [1, 0]
    rep = latency_report.gen_report(gens)
    assert rep["post_restart_requests"] == 1
    assert rep["incarnations"] == [0, 1]


# -- chaos soak --------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("spec_k", [0, 2])
def test_chaos_soak(model, spec_k):
    """Seeded random failpoint schedule over >=100 mixed requests
    (stream/non-stream, prefix-hit/miss, spec on/off via the param):
    every future resolves (success, or typed error within the retry
    budget), zero leaked pages after drain, and every survivor's greedy
    output is token-identical to a fault-free run."""
    rng = np.random.RandomState(1234 + spec_k)
    N = 104
    shared = rng.randint(0, 256, size=(8,)).astype("int64")
    prompts = []
    for i in range(N):
        tail_len = int(rng.randint(2, 5))
        tail = rng.randint(0, 256, size=(tail_len,)).astype("int64")
        if rng.rand() < 0.6:  # prefix-hit traffic
            prompts.append(np.concatenate([shared, tail]))
        else:  # prefix-miss traffic
            prompts.append(rng.randint(
                0, 256, size=(6 + tail_len,)).astype("int64"))
    cfg = dict(max_slots=4, page_size=4, num_pages=128,
               prefill_buckets=(16,), max_new_tokens=6,
               request_timeout_ms=0, max_queue_depth=2 * N,
               prefix_cache=True, spec_k=spec_k)

    # fault-free reference
    ref = {}
    with serving.GenerationEngine(model, name=f"soak_ref{spec_k}",
                                  **cfg) as eng:
        for i, p in enumerate(prompts):
            key = p.tobytes()
            if key not in ref:
                ref[key] = eng.submit(p, max_new_tokens=6).result()

    with flags(FLAGS_gen_restart_backoff_ms=2.0):
        sup = serving.EngineSupervisor(
            model, name=f"soak{spec_k}", retry_limit=4,
            breaker_threshold=10 ** 6, breaker_window_s=60.0, **cfg)
        handles = [None] * N      # (kind, handle)
        collected = [[] for _ in range(N)]
        stream_errs = [None] * N
        drains = []

        def drain(i, stream):
            try:
                for tok in stream:
                    collected[i].append(tok)
            except Exception as e:  # noqa: BLE001 — typed errors asserted below
                stream_errs[i] = e

        schedule = ["", "decode_step_raise@every:29",
                    "decode_poison_nan@every:37", "",
                    "decode_step_raise@every:23",
                    "slow_step_ms@every:11:5", ""]
        for w, lo in enumerate(range(0, N, 13)):
            paddle.set_flags(
                {"FLAGS_failpoints": schedule[w % len(schedule)]})
            for i in range(lo, min(lo + 13, N)):
                if i % 2 == 0:
                    st = sup.submit_stream(prompts[i], max_new_tokens=6)
                    handles[i] = ("stream", st)
                    t = threading.Thread(target=drain, args=(i, st),
                                         daemon=True)
                    t.start()
                    drains.append(t)
                else:
                    handles[i] = ("future",
                                  sup.submit(prompts[i],
                                             max_new_tokens=6))
            time.sleep(0.02 * (1 + rng.randint(3)))
        paddle.set_flags({"FLAGS_failpoints": ""})

        outs = [None] * N
        ok = failed = 0
        for i, (kind, h) in enumerate(handles):
            fut = h.future if kind == "stream" else h
            try:
                outs[i] = fut.result(timeout=180)
                ok += 1
            except (UnavailableError, FatalError):
                failed += 1  # typed, within budget — acceptable
        for t in drains:  # every stream has ended or errored by now
            t.join(30)
        for i, (kind, h) in enumerate(handles):
            if outs[i] is None:
                continue
            # survivor: token-identical to the fault-free run
            assert np.array_equal(outs[i], ref[prompts[i].tobytes()]), i
            if kind == "stream":
                # exactly-once: streamed tokens == generated part
                assert collected[i] == \
                    outs[i][len(prompts[i]):].tolist(), i
        assert ok + failed == N
        assert ok > 0
        # drain shutdown: nothing may own pages but the prefix index
        eng = sup.engine
        sup.shutdown(drain=True)
        assert eng._cache.owners() == {}
        assert (eng._cache.pages_in_use
                == len(eng._cache.cached_pages()))
