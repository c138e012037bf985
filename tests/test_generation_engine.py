"""serving.GenerationEngine: continuous batching over a paged KV cache.

The load-bearing anchors:

- **Greedy parity** — the engine's paged decode and `GPTModel.generate`'s
  contiguous cache share one math (`models.gpt.gpt_prefill`/
  `gpt_decode_step`); greedy outputs must agree at token level for the
  same prompts (the decode programs are different compiled shapes, so
  float bits may differ — argmax tokens must not; within ONE engine the
  [max_slots] decode program is a single compiled shape and repeat runs
  are bit-stable).
- **Compile discipline** — exactly one decode-step compile per engine
  and one prefill per prompt bucket, ledger-verified, with sequences
  joining and leaving mid-decode.
- **Page hygiene** — EOS/deadline/poison all free the sequence's pages
  the same step, zeroed before reuse.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework import monitor
from paddle_tpu.framework.errors import (ExecutionTimeoutError, FatalError,
                                         InvalidArgumentError,
                                         ResourceExhaustedError,
                                         UnavailableError)
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.profiler import exporter, flight_recorder
from paddle_tpu.serving.kv_cache import PagedKVCache


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


# -- allocator unit layer ---------------------------------------------------

def test_paged_allocator_basics():
    c = PagedKVCache(num_layers=2, num_heads=2, head_dim=4, page_size=4,
                     num_pages=8, pages_per_seq=3)
    assert c.usable_pages == 7           # page 0 reserved scratch
    assert c.pages_needed(1) == 1 and c.pages_needed(4) == 1
    assert c.pages_needed(5) == 2
    assert c.fits(12) and not c.fits(13)  # pages_per_seq bound
    row = c.alloc(1, 9)                   # 3 pages
    assert row.shape == (3,) and (row[:3] > 0).all()
    assert c.pages_in_use == 3 and c.can_admit(9)
    c.alloc(2, 9)
    c.alloc(3, 4)
    assert c.pages_in_use == 7 and not c.can_admit(1)
    assert monitor.stat_get("STAT_kv_pages_inuse") == 7
    with pytest.raises(ResourceExhaustedError):
        c.alloc(4, 1)
    with pytest.raises(InvalidArgumentError):
        c.alloc(1, 1)                     # double alloc same seq
    freed = c.free(2)
    assert len(freed) == 3 and c.can_admit(9)
    assert c.free(2) == []                # idempotent double free
    assert monitor.stat_get("STAT_kv_pages_inuse") == 4
    with pytest.raises(InvalidArgumentError):
        c.alloc(9, 13)                    # wider than the page table


# -- parity / numerics ------------------------------------------------------

def test_greedy_parity_with_generate(model):
    ids = _prompts()
    ref = model.generate(paddle.to_tensor(ids), max_new_tokens=5).numpy()
    with _engine(model) as eng:
        outs = [f.result(timeout=120)
                for f in [eng.submit(p, max_new_tokens=5) for p in ids]]
        s = eng.stats()
    for out, r in zip(outs, ref):
        np.testing.assert_array_equal(out, r)
    assert s["compiles"] == {"prefill[b=8]": 1, "decode[m=2]": 1}
    assert s["pages"]["pages_in_use"] == 0


def test_repeat_runs_bit_stable_one_engine(model):
    """Within ONE engine config the decode program is a single compiled
    shape: repeated submissions of the same prompt are bit-stable, and
    co-riders never perturb a sequence's tokens (row independence)."""
    ids = _prompts(n=3, seed=5)
    with _engine(model, max_slots=3) as eng:
        solo = eng.submit(ids[0], max_new_tokens=6).result(timeout=120)
        futs = [eng.submit(p, max_new_tokens=6) for p in ids]
        crowd = [f.result(timeout=120) for f in futs]
    np.testing.assert_array_equal(solo, crowd[0])


def test_sampling_is_engine_deterministic(model):
    ids = _prompts(seed=3)[0]
    def run():
        with _engine(model, seed=42) as eng:
            return eng.generate(ids, max_new_tokens=6, do_sample=True,
                                temperature=0.9)
    a, b = run(), run()
    np.testing.assert_array_equal(a, b)
    assert a.shape == (ids.size + 6,)


# -- scheduler dynamics -----------------------------------------------------

def test_mid_decode_join_without_recompile(model):
    ids = _prompts()
    ref_a = model.generate(paddle.to_tensor(ids[0:1]),
                           max_new_tokens=40).numpy()[0]
    ref_b = model.generate(paddle.to_tensor(ids[1:2]),
                           max_new_tokens=5).numpy()[0]
    with _engine(model, num_pages=64) as eng:
        fa = eng.submit(ids[0], max_new_tokens=40)
        # wait until A is genuinely mid-decode, then join B
        deadline = time.time() + 60
        while eng.stats()["steps"] < 3:
            assert time.time() < deadline, "engine never started stepping"
            time.sleep(0.002)
        joined_at = eng.stats()["steps"]
        fb = eng.submit(ids[1], max_new_tokens=5)
        out_b = fb.result(timeout=120)
        out_a = fa.result(timeout=120)
        s = eng.stats()
    assert joined_at >= 3                      # B really joined mid-decode
    np.testing.assert_array_equal(out_a, ref_a)
    np.testing.assert_array_equal(out_b, ref_b)
    # the join compiled NOTHING new: one decode step, one prefill bucket
    assert s["compiles"] == {"prefill[b=8]": 1, "decode[m=2]": 1}


def test_eos_frees_pages_same_step(model):
    ids = _prompts()
    ref = model.generate(paddle.to_tensor(ids[0:1]),
                         max_new_tokens=5).numpy()[0]
    S = ids.shape[1]
    gen = ref[S:]
    eos = int(gen[2])  # a token generated mid-stream
    stop = int(np.where(gen == eos)[0][0])  # first occurrence wins
    assert stop < len(gen) - 1, "eos must cut the stream short"
    with _engine(model) as eng:
        out = eng.generate(ids[0], max_new_tokens=5, eos_token_id=eos)
        pages_after = eng.stats()["pages"]["pages_in_use"]
    np.testing.assert_array_equal(out, ref[:S + stop + 1])  # EOS included
    assert pages_after == 0


def test_exhaustion_defers_admission_then_serves(model):
    """Admission control: a request whose worst-case pages are not free
    stays QUEUED (head-of-line) and is admitted as soon as a finishing
    sequence frees pages — never failed, never starving a running
    sequence mid-decode."""
    ids = _prompts()
    blocked0 = monitor.stat_get("STAT_gen_admit_blocked")
    dumps0 = len([d for d in flight_recorder.dump_records()
                  if d["reason"] == "gen_allocator_exhausted"])
    # pool sized for exactly one sequence: ceil((7+5)/4) = 3 pages + trash
    with _engine(model, num_pages=4) as eng:
        fa = eng.submit(ids[0], max_new_tokens=5)
        fb = eng.submit(ids[1], max_new_tokens=5)
        out_a = fa.result(timeout=120)
        out_b = fb.result(timeout=120)
    assert out_a.shape == out_b.shape == (12,)
    assert monitor.stat_get("STAT_gen_admit_blocked") > blocked0
    assert len([d for d in flight_recorder.dump_records()
                if d["reason"] == "gen_allocator_exhausted"]) > dumps0


def test_queued_deadline_expires_behind_blocked_head(model):
    """A request queued BEHIND a page-blocked head must still get its
    deadline error on time — head-of-line blocking defers admission,
    never expiry."""
    ids = _prompts(n=3, seed=31)
    # pool fits one 107-token sequence (27 pages of 29 usable) at a time
    with _engine(model, num_pages=30, page_size=4,
                 max_new_tokens=100) as eng:
        fa = eng.submit(ids[0], max_new_tokens=100)   # occupies the pool
        fh = eng.submit(ids[1], max_new_tokens=100)   # blocked head
        fb = eng.submit(ids[2], max_new_tokens=5, timeout_ms=50)
        with pytest.raises(ExecutionTimeoutError):
            fb.result(timeout=30)   # must NOT wait for A to finish
        fa.result(timeout=240)
        fh.result(timeout=240)


def test_request_that_can_never_fit_fails_fast(model):
    with _engine(model, num_pages=4) as eng:
        with pytest.raises(ResourceExhaustedError):
            eng.submit(_prompts()[0], max_new_tokens=20)  # > pool
        with pytest.raises(InvalidArgumentError):
            eng.submit(np.arange(20), max_new_tokens=2)   # > bucket
        with pytest.raises(InvalidArgumentError):
            eng.submit(np.zeros((0,), np.int64))
        with pytest.raises(InvalidArgumentError):
            eng.submit(_prompts()[0], max_new_tokens=0)
        with pytest.raises(InvalidArgumentError):
            eng.submit(np.zeros((2, 3), np.int64))


def test_deadline_expiry_mid_decode_cancels_only_that_future(model):
    ids = _prompts()
    t0 = monitor.stat_get("STAT_gen_timeouts")
    e0 = monitor.stat_get("STAT_gen_evictions")
    # (each step slowed by 5 ms: at the tiny model's ~0.5 ms a token B's
    # 100 tokens could otherwise finish inside its 60 ms)
    from paddle_tpu.serving import failpoints
    paddle.set_flags({"FLAGS_failpoints": "slow_step_ms@every:1:5"})
    try:
        with _engine(model, num_pages=64) as eng:
            fa = eng.submit(ids[0], max_new_tokens=40)      # no deadline
            fb = eng.submit(ids[1], max_new_tokens=100, timeout_ms=60)
            with pytest.raises(ExecutionTimeoutError):
                fb.result(timeout=120)
            out_a = fa.result(timeout=120)                  # unaffected
            pages_after = eng.stats()["pages"]["pages_in_use"]
    finally:
        paddle.set_flags({"FLAGS_failpoints": ""})
        failpoints.reset()
    assert out_a.shape == (47,)
    assert pages_after == 0                 # the cancel freed B's pages
    assert monitor.stat_get("STAT_gen_timeouts") > t0
    assert monitor.stat_get("STAT_gen_evictions") > e0


def test_poisoned_sequence_fails_alone_and_pages_scrub(model):
    """Poison isolation: NaN K/V in one sequence's pages fails ONLY that
    sequence (non-finite-logit flag), and because freed pages are zeroed
    the next owner of the same physical pages decodes cleanly."""
    ids = _prompts()
    ref_a = model.generate(paddle.to_tensor(ids[0:1]),
                           max_new_tokens=12).numpy()[0]
    ref_c = model.generate(paddle.to_tensor(ids[0:1]),
                           max_new_tokens=17).numpy()[0]
    p0 = monitor.stat_get("STAT_gen_poisoned")
    fired = []

    def hook(eng):
        req = eng._slots[1] if len(eng._slots) > 1 else None
        if not fired and req is not None and len(req.toks) >= 2:
            pages = eng._cache.owned(req.rid)
            if pages:
                eng._kp = eng._cache.form.at_pages(eng._kp, pages).set(np.nan)
                fired.append(req.rid)

    with _engine(model, num_pages=64) as eng:
        eng._pre_step_hook = hook
        fa = eng.submit(ids[0], max_new_tokens=12)
        # B lands in slot 1 (A holds slot 0) and gets poisoned
        fb = eng.submit(ids[1], max_new_tokens=12)
        with pytest.raises(FatalError):
            fb.result(timeout=120)
        out_a = fa.result(timeout=120)
        eng._pre_step_hook = None
        # the poisoned pages were zeroed on free: a wider request that
        # reuses them (6 pages > A's 5, so it reaches into B's freed
        # pages under the LIFO free list) must decode exactly the
        # clean-run tokens
        out_c = eng.generate(ids[0], max_new_tokens=17)
        pages_after = eng.stats()["pages"]["pages_in_use"]
    assert fired, "test hook never found the co-resident sequence"
    np.testing.assert_array_equal(out_a, ref_a)
    np.testing.assert_array_equal(out_c, ref_c)
    assert pages_after == 0
    assert monitor.stat_get("STAT_gen_poisoned") > p0


def test_poisoned_v_pages_fail_their_sequence_alone(model):
    """The V side of the same contract. On the pool-dense path (64 pages =
    2 slots x 32 entries) every row's probabilities multiply every page, and
    0.0 * NaN is NaN: the co-resident sequence must still decode its
    clean-run tokens, and the owner must still fail."""
    ids = _prompts()
    ref_a = model.generate(paddle.to_tensor(ids[0:1]),
                           max_new_tokens=12).numpy()[0]
    fired = []

    def hook(eng):
        req = eng._slots[1] if len(eng._slots) > 1 else None
        if not fired and req is not None and len(req.toks) >= 2:
            pages = eng._cache.owned(req.rid)
            if pages:
                eng._vp = eng._cache.form.at_pages(eng._vp, pages).set(np.nan)
                fired.append(req.rid)

    with _engine(model, num_pages=64) as eng:
        assert eng.stats()["decode_attention"] == "pool"
        eng._pre_step_hook = hook
        fa = eng.submit(ids[0], max_new_tokens=12)
        fb = eng.submit(ids[1], max_new_tokens=12)
        with pytest.raises(FatalError):
            fb.result(timeout=120)
        out_a = fa.result(timeout=120)
        eng._pre_step_hook = None
        pages_after = eng.stats()["pages"]["pages_in_use"]
    assert fired, "test hook never found the co-resident sequence"
    np.testing.assert_array_equal(out_a, ref_a)
    assert pages_after == 0


# -- lifecycle / backpressure / observability -------------------------------

def test_backpressure_rejects_at_queue_depth(model):
    with _engine(model, max_queue_depth=0) as eng:
        with pytest.raises(serving.EngineOverloaded):
            eng.submit(_prompts()[0], max_new_tokens=2)
        assert monitor.stat_get("STAT_gen_rejected") >= 1


def test_shutdown_drain_finishes_queued_work(model):
    ids = _prompts(n=4, seed=9)
    eng = _engine(model, num_pages=64)
    futs = [eng.submit(p, max_new_tokens=4) for p in ids]
    eng.shutdown(drain=True, timeout_s=120)
    for f in futs:
        assert f.result(timeout=1).shape == (11,)
    with pytest.raises(UnavailableError):
        eng.submit(ids[0])


def test_shutdown_no_drain_fails_fast(model):
    # five long requests: two decode for ~100 steps, three stay queued —
    # both classes must fail fast on drain=False, nothing may hang
    # (each step slowed by 5 ms: the tiny model decodes ~0.5 ms a token,
    # so 100 steps could otherwise finish before the shutdown)
    from paddle_tpu.serving import failpoints
    ids = _prompts(n=5, seed=21)
    eng = _engine(model, num_pages=64, name="gen_nodrain")
    paddle.set_flags({"FLAGS_failpoints": "slow_step_ms@every:1:5"})
    try:
        futs = [eng.submit(p, max_new_tokens=100) for p in ids]
        time.sleep(0.05)  # let the first admissions happen
        eng.shutdown(drain=False, timeout_s=120)
    finally:
        paddle.set_flags({"FLAGS_failpoints": ""})
        failpoints.reset()
    for f in futs:
        with pytest.raises(UnavailableError):
            f.result(timeout=5)


def test_health_and_readyz_lifecycle(model):
    eng = _engine(model, name="gen_readyz")
    try:
        h = eng.health()
        assert h["ready"] and h["reason"] == "ok"
        assert h["warmup_complete"] and h["live_lanes"] == 1
        payload = exporter.readiness_payload()
        assert payload["engines"]["gen_readyz"]["ready"]
    finally:
        eng.shutdown()
    h = eng.health()
    assert not h["ready"] and h["reason"] == "draining"
    assert "gen_readyz" not in exporter.readiness_payload()["engines"]


def test_stats_shape_and_counters(model):
    s0_steps = monitor.stat_get("STAT_gen_steps")
    with _engine(model) as eng:
        eng.generate(_prompts()[0], max_new_tokens=4)
        s = eng.stats()
    assert s["prefills"] >= 1 and s["tokens"] >= 4
    assert s["queue_depth"] == 0
    assert set(s["pages"]) >= {"pages_in_use", "usable_pages",
                               "occupancy", "page_size"}
    assert s["ttft_ms"]["count"] >= 1
    assert monitor.stat_get("STAT_gen_steps") > s0_steps
    assert monitor.stat_get("STAT_gen_completions") >= 1


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["private-pages", "shared-prefix-pages"])
def test_greedy_tokens_identical_on_both_sides_of_the_pool_rule(
        model, prefix_cache):
    """The decode attention is chosen by shape (`ops/paged_ops.py`): a pool
    of max_slots * pages_per_seq pages is scored pool-dense, one of 8 pages
    more takes the gather reference. Same prompts, same greedy tokens — and
    with the prefix cache on, rows that SHARE physical pages (each owning
    them at its own table entry) read them through the ownership mask."""
    rng = np.random.RandomState(5)
    head = rng.randint(0, 512, size=8)          # two full pages of 4
    prompts = [np.concatenate([head, rng.randint(0, 512, size=n)])
               .astype("int64") for n in (3, 6, 1, 5)]
    M, PP = 2, 128 // 4                         # tiny: 128 positions
    outs, paths = {}, {}
    for num_pages in (M * PP, M * PP + 8):
        p0, r0, h0 = (monitor.stat_get("STAT_paged_attn_pool"),
                      monitor.stat_get("STAT_paged_attn_reference"),
                      monitor.stat_get("STAT_prefix_hits"))
        with _engine(model, num_pages=num_pages, prefill_buckets=(8, 16),
                     prefix_cache=prefix_cache) as eng:
            futs = [eng.submit(p, max_new_tokens=9) for p in prompts]
            outs[num_pages] = [f.result(timeout=120) for f in futs]
            s = eng.stats()
        paths[num_pages] = s["decode_attention"]
        assert s["compiles"]["decode[m=2]"] == 1
        assert (monitor.stat_get("STAT_prefix_hits") > h0) == prefix_cache
        traced = (monitor.stat_get("STAT_paged_attn_pool") - p0,
                  monitor.stat_get("STAT_paged_attn_reference") - r0)
        # one trace per layer of the ONE decode program (tiny: 2 layers);
        # the tail-prefill programs count as reference on both sides
        assert traced[0] == (2 if paths[num_pages] == "pool" else 0)
        assert traced[1] >= (0 if paths[num_pages] == "pool" else 2)
    assert paths == {M * PP: "pool", M * PP + 8: "reference"}
    for a, b, p in zip(outs[M * PP], outs[M * PP + 8], prompts):
        np.testing.assert_array_equal(a, b)
        ref = model.generate(paddle.to_tensor(p[None]),
                             max_new_tokens=9).numpy()[0]
        np.testing.assert_array_equal(a, ref)


def test_latency_report_summarizes_gen_spans(model, tmp_path, capsys):
    import importlib.util
    import os
    from paddle_tpu import profiler

    with _engine(model, name="gen_report") as eng:
        for p in _prompts(n=3, seed=13):
            eng.generate(p, max_new_tokens=4)
    path = str(tmp_path / "trace.json")
    profiler.export_chrome_tracing(path)
    spec = importlib.util.spec_from_file_location(
        "latency_report", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "latency_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    gen = [g for g in mod.parse_gen_trace(path)
           if g["engine"] == "gen_report"]
    assert len(gen) >= 3
    assert all(g["n"] == 4 and g["ttft"] > 0 for g in gen)
    rep = mod.gen_report(gen, top=2)
    assert rep["requests"] == len(gen)
    for k in ("ttft", "tpot", "e2e"):
        assert rep["phases_ms"][k]["p50"] <= rep["phases_ms"][k]["max"] + 1e-9
    assert len(rep["slowest"]) == 2
    # CLI renders both serving and generation sections as available
    assert mod.main([path, "--engine", "gen_report"]) == 0
    assert "ttft" in capsys.readouterr().out


@pytest.mark.slow
def test_generation_soak_many_slots(model):
    """Heavy multi-slot churn: mixed lengths, sampling and greedy mixed,
    requests joining/leaving constantly — one decode compile, no page
    leaks, every future delivered."""
    rng = np.random.RandomState(0)
    with _engine(model, max_slots=4, num_pages=64,
                 prefill_buckets=(4, 8)) as eng:
        futs = []
        for i in range(24):
            S = int(rng.randint(2, 9))
            p = rng.randint(0, 512, size=(S,))
            futs.append((S, eng.submit(
                p, max_new_tokens=int(rng.randint(1, 8)),
                do_sample=bool(i % 3 == 0), temperature=0.8)))
        for S, f in futs:
            assert f.result(timeout=240).shape[0] > S
        s = eng.stats()
    decode_compiles = [v for k, v in s["compiles"].items()
                       if k.startswith("decode")]
    assert decode_compiles == [1]
    assert s["pages"]["pages_in_use"] == 0


# -- tokens go out while the chip runs the next step --------------------------

def test_staged_tokens_go_out_under_the_next_launch_never_later(model):
    """With a sequence decoding, an iteration's tokens are released after
    its record and handed to the stream inside the NEXT program's
    read-back (while the device runs), or before anything that could wait
    for a reader (the hook seam here); when nothing decodes any more the
    last ones go out at once. So at every hook call all earlier steps'
    tokens are readable, nothing is ever held across two launches, and the
    stream and the future agree."""
    seen = []

    def hook(eng):
        req = next(r for r in eng._slots if r is not None)
        # flushed before the hook ran: every token decoded so far is in
        # the stream's queue (nobody reads it yet), none is still staged
        seen.append((len(req.toks), req.stream._q.qsize(),
                     len(eng._released), len(eng._stream_q)))

    with _engine(model, max_new_tokens=6) as eng:
        eng._pre_step_hook = hook
        s = eng.submit_stream(_prompts(1)[0])
        out = s.result(timeout=120)
        eng._pre_step_hook = None
        assert list(s) == list(out[-6:])
        assert eng._released == [] and eng._stream_q == []
    assert len(seen) == 5                  # the first token is prefill's
    # the first step shares its iteration with the prefill, whose token
    # waits, staged, for that iteration's record
    assert seen[0] == (1, 0, 0, 1)
    for decoded, readable, released, staged in seen[1:]:
        assert readable == decoded and released == 0 and staged == 0


def test_a_read_back_delivers_only_what_an_earlier_record_released(model):
    """What THIS iteration stages (a request that ends in its prefill, an
    expiry) must wait for this iteration's record: a read-back later in
    the same iteration hands out released batches only."""
    with _engine(model) as eng:
        eng._stream_q.append(("stream", "tok"))     # staged, not released
        eng._flush_released()
        assert eng._stream_q == [("stream", "tok")]
        eng._stream_q.clear()


def test_gc_freeze_keeps_the_warmed_process_out_of_full_collections(model):
    """`gc_freeze=True`: after warm-up everything alive is frozen, so a
    full collection while serving walks only what came after; shutdown
    gives it back. Off by default (process-wide state)."""
    import gc
    base = gc.get_freeze_count()        # the interpreter's own, if any
    with _engine(model) as eng:
        assert gc.get_freeze_count() == base       # default: untouched
        eng.generate(_prompts(1)[0])
    with _engine(model, gc_freeze=True) as eng:
        frozen = gc.get_freeze_count()
        assert frozen > base + 10_000              # jax, programs, weights
        out = eng.generate(_prompts(1)[0])
        assert out.shape[0] == 7 + 5
        assert gc.get_freeze_count() > base + 10_000   # still frozen
    assert gc.get_freeze_count() == 0


# -- one decode step in flight ahead of the host (ISSUE 34) -------------------

def _wait_until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def _alone(model, prompt, n):
    """The tokens `generate()` gives this prompt alone."""
    return model.generate(paddle.to_tensor(prompt[None]),
                          max_new_tokens=n).numpy()[0]


def test_a_mixed_batch_ahead_of_the_host_is_token_identical(model):
    """Different prompt and output lengths, six requests through two slots
    (so slots are reused by queued requests), one request admitted while
    the others decode: each gets the tokens it gets alone, with decode
    steps launched ahead of the last one's read-back all the while."""
    rng = np.random.RandomState(7)
    shapes = [(3, 9), (7, 4), (5, 12), (8, 6), (2, 10), (6, 3)]
    prompts = [rng.randint(0, 512, size=(S,)).astype("int64")
               for S, _ in shapes]
    refs = [_alone(model, p, n) for p, (_, n) in zip(prompts, shapes)]
    late_p = rng.randint(0, 512, size=(4,)).astype("int64")
    late_ref = _alone(model, late_p, 7)
    with _engine(model, prefill_buckets=(4, 8), max_new_tokens=12) as eng:
        first = eng.submit_stream(prompts[0], max_new_tokens=shapes[0][1])
        futs = [eng.submit(p, max_new_tokens=n)
                for p, (_, n) in zip(prompts[1:], shapes[1:])]
        next(iter(first))               # decoding has begun
        late = eng.submit(late_p, max_new_tokens=7)     # mid-run
        outs = [first.result(timeout=120)] + [f.result(timeout=120)
                                              for f in futs]
        late_out = late.result(timeout=120)
        s = eng.stats()
    for out, ref in zip(outs + [late_out], refs + [late_ref]):
        np.testing.assert_array_equal(out, ref)
    look = s["lookahead"]
    assert look["ahead"] > 0 and look["settled"] == {}
    assert look["dropped_tokens"] == 0          # no EOS, nobody evicted
    assert look["ahead"] <= s["steps"]
    assert s["tokens"] == sum(n for _, n in shapes) + 7
    assert s["compiles"] == {"prefill[b=4]": 1, "prefill[b=8]": 1,
                             "decode[m=2]": 1}
    assert s["pages"]["pages_in_use"] == 0


def test_an_eos_with_the_next_step_in_flight_drops_exactly_that_token(model):
    """A request that stops on EOS at step n has run step n+1 as well (it
    was launched before n was read): that token is never streamed nor
    counted, `dropped_tokens` says 1, its pages are zeroed behind the
    overshoot's write, and the next owner of the slot and the pages
    decodes its own reference tokens."""
    ids = _prompts(8, seed=1)
    # a prompt whose greedy stream first shows some token at its 3rd to
    # 8th place: that token is the EOS, `stop` decode steps in
    found = [(p, gen, k) for p in ids[:-1]
             for gen in [_alone(model, p, 10)[7:]] for k in range(2, 8)
             if int(gen[k]) not in gen[:k].tolist()]
    assert found, "no prompt's stream has a late first occurrence"
    prompt, gen, stop = found[0]
    eos = int(gen[stop])
    ids = [prompt, ids[-1]]
    ref_next = _alone(model, ids[1], 12)
    with _engine(model, max_new_tokens=12) as eng:
        t0 = eng.stats()["tokens"]
        s = eng.submit_stream(ids[0], max_new_tokens=10, eos_token_id=eos)
        streamed = list(s)
        out = s.result(timeout=120)
        assert _wait_until(
            lambda: eng.stats()["lookahead"]["dropped_tokens"] == 1)
        st = eng.stats()
        # EOS included, nothing after it: not in the stream, the result
        # or the counters
        assert streamed == gen[:stop + 1].tolist() == out[7:].tolist()
        assert st["tokens"] - t0 == stop + 1
        # steps launched: one a decoded token (stop of them, the first
        # token is the prefill's) and the one overshoot
        assert st["steps"] == stop + 1
        assert st["pages"]["pages_in_use"] == 0
        assert _wait_until(lambda: eng._flight is None)
        for pool in eng._pools():       # the overshoot's K/V went too
            assert float(np.abs(np.asarray(pool)).max()) == 0.0
        out_next = eng.generate(ids[1], max_new_tokens=12)
        assert eng.stats()["lookahead"]["dropped_tokens"] == 1
    np.testing.assert_array_equal(out_next, ref_next)


def test_a_poison_failpoint_fails_its_request_alone_and_settles_first(model):
    """`decode_poison_nan` armed: the engine reads every step before the
    next launch (`settled` counts them under "failpoint", none is ahead),
    the poisoned request alone fails, and the engine goes on — ahead again
    once the flag is cleared."""
    from paddle_tpu.serving import failpoints
    ids = _prompts(3, seed=2)
    refs = [_alone(model, p, 8) for p in ids]
    failpoints.reset()
    paddle.set_flags({"FLAGS_failpoints": "decode_poison_nan@2"})
    try:
        with _engine(model, max_new_tokens=8) as eng:
            fa = eng.submit(ids[0], max_new_tokens=8)
            fb = eng.submit(ids[1], max_new_tokens=8)
            outcomes = []
            for f in (fa, fb):
                try:
                    outcomes.append(f.result(timeout=120))
                except FatalError as e:
                    outcomes.append(e)
            armed = eng.stats()
            paddle.set_flags({"FLAGS_failpoints": ""})
            out_c = eng.generate(ids[2], max_new_tokens=8)
            after = eng.stats()
    finally:
        paddle.set_flags({"FLAGS_failpoints": ""})
        failpoints.reset()
    failed = [o for o in outcomes if isinstance(o, FatalError)]
    assert len(failed) == 1
    for o, ref in zip(outcomes, refs):
        if not isinstance(o, FatalError):
            np.testing.assert_array_equal(o, ref)
    np.testing.assert_array_equal(out_c, refs[2])
    assert armed["lookahead"]["ahead"] == 0
    assert armed["lookahead"]["prefills_ahead"] == 0
    # every prefill read at once too: each request's (a poisoned one's
    # count, none are)
    assert armed["lookahead"]["settled"] == {
        "failpoint": armed["steps"], "prefill:failpoint": 2}
    assert after["lookahead"]["ahead"] > 0
    assert after["pages"]["pages_in_use"] == 0


@pytest.mark.parametrize("reason", ["speculation", "pre_step_hook",
                                    "failpoint"])
def test_what_needs_the_tokens_on_the_host_finds_no_step_in_flight(
        model, reason):
    """Speculation (the proposer reads the history), a `_pre_step_hook`,
    an armed `slow_step_ms`: every step is read before the next launch and
    every prefill at once, decided from the engine's own state, and the
    tokens are the same."""
    from paddle_tpu.serving import failpoints
    ids = _prompts(3, seed=4)
    refs = [_alone(model, p, 6) for p in ids]
    seen = []
    failpoints.reset()
    if reason == "failpoint":
        paddle.set_flags({"FLAGS_failpoints": "slow_step_ms@every:2:1"})
    try:
        kw = {"spec_k": 2} if reason == "speculation" else {}
        with _engine(model, max_new_tokens=6, **kw) as eng:
            if reason == "pre_step_hook":
                eng._pre_step_hook = lambda e: seen.append(
                    e._flight is None)
            outs = [f.result(timeout=120) for f in
                    [eng.submit(p, max_new_tokens=6) for p in ids]]
            s = eng.stats()
    finally:
        paddle.set_flags({"FLAGS_failpoints": ""})
        failpoints.reset()
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    look = s["lookahead"]
    assert look["ahead"] == 0 and look["dropped_tokens"] == 0
    assert look["prefills_ahead"] == 0
    assert look["settled"] == {reason: s["steps"],
                               f"prefill:{reason}": len(ids)}
    assert s["steps"] > 0 and s["prefills"] == len(ids)
    if reason == "pre_step_hook":
        assert seen and all(seen)       # never a step in flight at a hook


def test_the_key_folded_inside_the_program_is_the_eager_fold(model):
    """Step k draws from `fold_in(PRNGKey(seed), k)`, folded inside the
    decode program from the base key and the step's number: bit-identical
    to the eager fold it replaces, so two engines of one seed that see
    the same arrivals sample identical streams."""
    import jax
    from paddle_tpu.serving.generation import step_key
    for seed in (0, 42, 2 ** 31 - 1):
        base = jax.random.PRNGKey(seed)
        folded = jax.jit(step_key)
        for k in (0, 1, 7, 4095, 2 ** 20):
            np.testing.assert_array_equal(
                np.asarray(folded(np.asarray(base), np.int32(k))),
                np.asarray(jax.random.fold_in(base, k)))
    ids = _prompts(3, seed=6)

    def run():
        with _engine(model, seed=42, max_new_tokens=9) as eng:
            outs = [eng.generate(p, max_new_tokens=9, do_sample=True,
                                 temperature=0.9) for p in ids]
            assert eng.stats()["lookahead"]["ahead"] > 0
            return outs
    a, b = run(), run()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    greedy = [_alone(model, p, 9) for p in ids]
    assert any(not np.array_equal(x, g) for x, g in zip(a, greedy))


def test_a_steps_time_is_never_under_its_programs_and_the_buckets_tile(
        model):
    """With the decode program made slow on the device's side (its tokens
    come ready 15 ms after the launch, the launch itself returns at once),
    5 ms of host work an iteration and steps launched ahead: `decode_ms`
    reads that device time — in the median and in the sum: a single record
    may read short by the host's lag in seeing the step BEFORE end (a
    stalled thread on a loaded machine), which that step's record holds —
    where timing only the launch and the blocked wait would read 10 ms; the
    host's 5 ms hide
    under it instead of landing in the bookkeeping bucket, `decode_wait_ms`
    is a part of it, and the six buckets sum to the wall exactly."""
    import jax
    from paddle_tpu.profiler import step_log
    SLOW, HOST = 0.015, 0.005

    def nap(x):
        time.sleep(SLOW)
        return x

    slow = jax.jit(lambda x: jax.pure_callback(
        nap, jax.ShapeDtypeStruct(x.shape, x.dtype), x))
    ids = _prompts(4, seed=8)
    with _engine(model, name="ahead_attr", max_new_tokens=8) as eng:
        real, NP = eng._decode_call, eng._npool

        def slowed(*args):
            out = real(*args)
            return (*out[:NP], slow(out[NP]), *out[NP + 1:])
        eng._decode_call = slowed
        record = eng._record_iteration

        def busy_host():
            time.sleep(HOST)
            record()
        eng._record_iteration = busy_host
        futs = [eng.submit(p, max_new_tokens=8) for p in ids]
        for f in futs:
            f.result(timeout=120)
        s = eng.stats()
        recs = step_log.steps_payload()["engines"]["ahead_attr"]["records"]
    assert s["lookahead"]["ahead"] > 0
    stepped = [r for r in recs if r["decode_ms"] > 0]
    assert len(stepped) == s["steps"]
    assert sum(r["ahead"] for r in recs) == s["lookahead"]["ahead"]
    times = sorted(r["decode_ms"] for r in stepped)
    assert times[len(times) // 2] >= SLOW * 1e3 - 1.0, times
    assert sum(times) >= SLOW * 1e3 * len(times), times
    # the host's sleep ran under a step in flight wherever one was
    assert (sum(r["attr_bookkeep_ms"] for r in recs)
            < 0.5 * HOST * 1e3 * len(recs))
    for r in recs:
        assert 0 <= r["decode_wait_ms"] <= r["decode_ms"], r
        assert 0 <= r["prefill_wait_ms"] <= r["prefill_ms"], r
        assert r["attr_admit_ms"] >= 0 and r["attr_bookkeep_ms"] > -0.01, r
        total = (r["attr_admit_ms"] + r["prefill_ms"]
                 + r["attr_promote_ms"] + r["decode_ms"]
                 + r["attr_bookkeep_ms"] + r["attr_idle_ms"])
        assert abs(total - r["attr_wall_ms"]) < 1e-9, r
    # the host's work hides under the chip's: the steps' own time is most
    # of the walls of the iterations that read one
    assert (sum(r["decode_ms"] for r in stepped)
            > 0.8 * sum(r["attr_wall_ms"] - r["prefill_ms"]
                        for r in stepped))
    assert sum(r["tokens"] for r in recs) == s["tokens"]


# -- the prefill's first token stays on the device ----------------------------
#
# Every prefill is followed by the small first-token program, which samples
# the request's first token on the device and writes it into the next decode
# step's token input; the engine launches that step before it reads the
# prefill. A test that holds `eng._cv` while it submits makes the step thread
# admit all of those requests in ONE iteration, their prefills chained on the
# device with no read in between.

def _family_net(family):
    if family == "gpt":
        paddle.seed(11)
        net = GPTForCausalLM(GPTConfig.tiny(dropout=0.0))
    elif family == "latent":
        from paddle_tpu.models import GlmMoeLiteConfig, GlmMoeLiteForCausalLM
        paddle.seed(27)
        net = GlmMoeLiteForCausalLM(GlmMoeLiteConfig.tiny())
    else:
        from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM
        paddle.seed(36)
        net = FalconH1ForCausalLM(FalconH1Config.tiny())
    net.eval()
    return net


_FAMILY_ENGINE = {
    "gpt": dict(page_size=4, num_pages=64, prefill_buckets=(4, 8)),
    "latent": dict(page_size=4, num_pages=64, pages_per_seq=16,
                   prefill_buckets=(16, 32)),
    "hybrid": dict(page_size=8, num_pages=25, pages_per_seq=8,
                   prefill_buckets=(16, 32)),
}


def _full_forward_greedy(net, prompt, n, width=48):
    """Greedy decoding by the Layer's whole causal forward over what there is
    so far (right-padded to one width, which no real position attends to)."""
    seq = [int(t) for t in prompt]
    for _ in range(n):
        ids = np.zeros((1, width), "int32")
        ids[0, :len(seq)] = seq
        lg = net(paddle.to_tensor(ids)).numpy()[0, len(seq) - 1]
        seq.append(int(lg.argmax()))
    return np.asarray(seq)


@pytest.mark.parametrize("family", ["gpt", "latent", "hybrid"])
def test_prefills_read_behind_the_next_launch_are_token_identical(family):
    """Five requests over three slots, the first three admitted in one
    iteration (three prefills and their first tokens chained on the device,
    then the first decode step launched behind them, then the three read),
    the last two admitted as slots free, behind a step in flight: every
    stream is its prompt's greedy decoding alone, every prefill was read
    after the next launch, and nothing compiles after the warm-up — the
    first-token program included."""
    net = _family_net(family)
    vocab = 512 if family == "gpt" else 384
    rs = np.random.RandomState(42)
    shapes = [(3, 9), (8, 4), (5, 11), (7, 6), (2, 8)]
    if family != "gpt":
        shapes = [(S + 8, n) for S, n in shapes]
    prompts = [rs.randint(0, vocab, size=(S,)).astype("int64")
               for S, _ in shapes]
    if family == "gpt":
        refs = [_alone(net, p, n) for p, (_, n) in zip(prompts, shapes)]
    else:
        refs = [_full_forward_greedy(net, p, n)
                for p, (_, n) in zip(prompts, shapes)]
    eng = serving.GenerationEngine(
        net, name=f"first_tok_{family}", max_slots=3, max_new_tokens=12,
        request_timeout_ms=0, **_FAMILY_ENGINE[family])
    try:
        warm = dict(eng.stats()["compiles"])
        cached = eng._first_jit._cache_size()
        with eng._cv:
            streams = [eng.submit_stream(p, max_new_tokens=n)
                       for p, (_, n) in zip(prompts, shapes)]
        got = [list(s) for s in streams]
        outs = [s.result(timeout=120) for s in streams]
        assert _wait_until(lambda: eng._flight is None)
        s = eng.stats()
        recs = eng._step_log.tail(10_000)
        assert eng._first_jit._cache_size() == cached
    finally:
        eng.shutdown()
    for g, out, ref, p in zip(got, outs, refs, prompts):
        np.testing.assert_array_equal(out, ref)
        assert g == out[len(p):].tolist()
    look = s["lookahead"]
    assert look["prefills_ahead"] == s["prefills"] == len(prompts)
    assert look["settled"] == {} and look["dropped_tokens"] == 0
    assert max(r["admitted"] for r in recs) == 3    # three in one iteration
    assert s["compiles"] == warm
    assert s["tokens"] == sum(n for _, n in shapes)
    assert s["pages"]["pages_in_use"] == 0


def test_max_new_one_and_a_first_token_eos_behind_a_step_in_flight(model):
    """With another request decoding (a step always in flight): a request of
    one token joins no decode step (its unread token counts against
    max_new), so nothing of it is dropped; a request whose FIRST token is
    its EOS has already joined the step launched behind its prefill — that
    step's token for it is dropped exactly once, neither streamed nor
    counted. The pools are zero once all is done, and the next request, on
    the pages they freed, decodes its own tokens."""
    ids = _prompts(4, seed=9)
    ref_one = _alone(model, ids[1], 1)
    ref_eos = _alone(model, ids[2], 1)
    eos = int(ref_eos[-1])
    ref_long = _alone(model, ids[0], 30)
    ref_next = _alone(model, ids[3], 8)
    with _engine(model, max_new_tokens=30) as eng:
        long_s = eng.submit_stream(ids[0], max_new_tokens=30)
        it = iter(long_s)
        next(it)                        # decoding has begun
        d0 = eng.stats()["lookahead"]["dropped_tokens"]
        one = eng.submit_stream(ids[1], max_new_tokens=1)
        assert list(one) == ref_one[7:].tolist()
        assert eng.stats()["lookahead"]["dropped_tokens"] == d0
        stop = eng.submit_stream(ids[2], max_new_tokens=10,
                                 eos_token_id=eos)
        assert list(stop) == [eos]
        np.testing.assert_array_equal(stop.result(timeout=120), ref_eos)
        np.testing.assert_array_equal(one.result(timeout=120), ref_one)
        assert _wait_until(
            lambda: eng.stats()["lookahead"]["dropped_tokens"] == d0 + 1)
        np.testing.assert_array_equal(long_s.result(timeout=120), ref_long)
        assert _wait_until(lambda: eng._flight is None)
        st = eng.stats()
        # the two short requests' tokens: one each, the dropped one not
        assert st["tokens"] == 30 + 1 + 1
        assert st["lookahead"]["dropped_tokens"] == d0 + 1
        assert st["lookahead"]["prefills_ahead"] == st["prefills"] == 3
        assert st["pages"]["pages_in_use"] == 0
        for pool in eng._pools():
            assert float(np.abs(np.asarray(pool)).max()) == 0.0
        out_next = eng.generate(ids[3], max_new_tokens=8)
    np.testing.assert_array_equal(out_next, ref_next)


def test_a_poisoned_prefill_read_behind_a_step_fails_its_request_alone(
        model):
    """A prefill whose logits come back non-finite (planted here: the
    marked prompt's logits times NaN) while another request decodes: its
    first token's poison flag is read after the next step was launched with
    the request in it; only that request fails, its token of that step is
    dropped, its pages come back zeroed, and the next request decodes its
    own tokens."""
    import jax.numpy as jnp
    ids = _prompts(3, seed=12)
    MARK = 511
    bad_prompt = ids[1].copy()
    bad_prompt[0] = MARK
    ref_long = _alone(model, ids[0], 40)
    ref_next = _alone(model, ids[2], 8)
    p0 = monitor.stat_get("STAT_gen_poisoned")
    with _engine(model, max_new_tokens=40) as eng:
        real, NP = eng._prefill_jit, eng._npool

        def planted(W, *args):
            out = real(W, *args)
            if int(args[NP + 1][0, 0]) == MARK:     # the prompt's ids
                return (*out[:-1], out[-1] * jnp.nan)
            return out
        eng._prefill_jit = planted
        long_s = eng.submit_stream(ids[0], max_new_tokens=40)
        it = iter(long_s)
        next(it)                        # decoding has begun
        bad = eng.submit(bad_prompt, max_new_tokens=8)
        with pytest.raises(FatalError, match="non-finite prefill"):
            bad.result(timeout=120)
        assert _wait_until(
            lambda: eng.stats()["lookahead"]["dropped_tokens"] == 1)
        np.testing.assert_array_equal(long_s.result(timeout=120), ref_long)
        assert _wait_until(lambda: eng._flight is None)
        st = eng.stats()
        for pool in eng._pools():
            assert float(np.abs(np.asarray(pool)).max()) == 0.0
        out_next = eng.generate(ids[2], max_new_tokens=8)
    np.testing.assert_array_equal(out_next, ref_next)
    assert monitor.stat_get("STAT_gen_poisoned") == p0 + 1
    assert st["lookahead"]["prefills_ahead"] == 2   # the poisoned one too
    assert st["prefills"] == 1                      # delivered: the long
    assert st["pages"]["pages_in_use"] == 0


def test_two_engines_of_one_seed_sample_the_same_first_tokens(model):
    """A sampled request's first token is drawn on the device from the
    first-token key folded with the request's engine-local ordinal: two
    engines of one seed give the same first tokens whether the requests
    arrive together (admitted in one iteration) or one at a time, another
    seed gives others, and the first-token key is not the decode steps'."""
    ids = _prompts(6, seed=14)
    kw = dict(max_new_tokens=1, do_sample=True, temperature=0.9)

    def firsts(seed, together):
        with _engine(model, seed=seed, max_slots=6) as eng:
            assert not np.array_equal(eng._first_key_host, eng._key_host)
            if together:
                with eng._cv:
                    futs = [eng.submit(p, **kw) for p in ids]
                outs = [f.result(timeout=120) for f in futs]
            else:
                outs = [eng.generate(p, **kw) for p in ids]
            assert eng.stats()["lookahead"]["prefills_ahead"] == 0
            assert eng.stats()["lookahead"]["settled"] == {
                "prefill:no_decode": len(ids)}
        return [int(o[-1]) for o in outs]

    a = firsts(42, together=True)
    assert firsts(42, together=False) == a
    assert firsts(7, together=True) != a
    greedy = [int(_alone(model, p, 1)[-1]) for p in ids]
    assert a != greedy


def test_prefills_ahead_counts_every_admission_of_a_loaded_run(model):
    """A loaded run — more requests than slots, of mixed lengths, a step in
    flight all along: every admission's prefill is read after the next
    decode step was launched behind it, as the step ring's records count
    them, and `STAT_gen_prefills_ahead` moves by as many."""
    rs = np.random.RandomState(17)
    prompts = [rs.randint(0, 512, size=(int(n),)).astype("int64")
               for n in rs.randint(2, 9, size=12)]
    news = [int(n) for n in rs.randint(2, 14, size=12)]
    c0 = monitor.stat_get("STAT_gen_prefills_ahead")
    with _engine(model, name="first_tok_loaded", max_slots=4,
                 prefill_buckets=(4, 8), max_new_tokens=16) as eng:
        futs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        outs = [f.result(timeout=120) for f in futs]
        assert _wait_until(lambda: eng._flight is None)
        s = eng.stats()
        recs = eng._step_log.tail(10_000)
    for p, n, out in zip(prompts, news, outs):
        np.testing.assert_array_equal(out, _alone(model, p, n))
    admitted = sum(r["admitted"] for r in recs)
    assert admitted == len(prompts)
    assert s["lookahead"]["prefills_ahead"] == admitted
    assert monitor.stat_get("STAT_gen_prefills_ahead") - c0 == admitted
    assert s["lookahead"]["settled"] == {}


def test_prefills_read_ahead_keep_the_records_tiling_the_thread(model):
    """Several admissions an iteration, their prefills read after the decode
    step launched behind them: every record's six buckets still sum to its
    wall, a prefill's time never under its blocked read, and the walls of
    the records between two moments the engine had nothing in flight sum to
    the step thread's time between them — no stretch counted twice, none
    lost."""
    ids = _prompts(6, seed=19)
    with _engine(model, name="first_tok_tiles", max_slots=3,
                 max_new_tokens=10) as eng:
        eng.generate(ids[0], max_new_tokens=3)
        assert _wait_until(lambda: eng._flight is None and all(
            r is None for r in eng._slots))
        time.sleep(0.05)                # the loop is back in its wait
        n0 = eng._step_log.recorded
        t_a = eng._step_log.tail(1)[0]["t"]
        with eng._cv:
            futs = [eng.submit(p, max_new_tokens=8) for p in ids]
        for f in futs:
            f.result(timeout=120)
        assert _wait_until(lambda: eng._flight is None and all(
            r is None for r in eng._slots))
        time.sleep(0.05)
        recs = eng._step_log.tail(eng._step_log.recorded - n0)
        s = eng.stats()
    assert s["lookahead"]["prefills_ahead"] == 1 + len(ids)
    assert sum(r["admitted"] for r in recs) == len(ids)
    assert sum(r["prefill_ms"] > 0 for r in recs) >= 2
    for r in recs:
        assert 0 <= r["prefill_wait_ms"] <= r["prefill_ms"], r
        assert 0 <= r["decode_wait_ms"] <= r["decode_ms"], r
        total = (r["attr_admit_ms"] + r["prefill_ms"]
                 + r["attr_promote_ms"] + r["decode_ms"]
                 + r["attr_bookkeep_ms"] + r["attr_idle_ms"])
        assert abs(total - r["attr_wall_ms"]) < 1e-9, r
    # (each record's `t` follows the charge that closes its wall by the
    # record's own bookkeeping, some microseconds, more on a loaded host)
    walls = sum(r["attr_wall_ms"] for r in recs)
    assert walls == pytest.approx((recs[-1]["t"] - t_a) * 1e3, abs=5.0)
