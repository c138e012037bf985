"""The generation engine's own timeline of the device
(`serving/device_clock.py`): the arithmetic on hand-made stamps, and the
records of a short CPU run.

What a CPU run can show is that the stamps are taken and the records add
up: device time plus idle tiles the span from the first program's enqueue
to the last one's end, each record's idle by scope sums to its idle, and
the watcher thread lives exactly as long as the step ring is on and the
engine runs. How close `done` comes to the device's own end is a chip
reading (`tools/trace_report.py` prints the lag of `generation::await`).
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.profiler import step_log, tracer
from paddle_tpu.serving import device_clock as dc

MS = 1e-3


class Clock(dc.DeviceClock):
    """A device clock whose stamps are given by hand: no watcher thread,
    and the step thread's scopes are a list."""

    def __init__(self, scopes=()):     # noqa: D107 — no thread started
        from collections import deque
        self._pending = deque()
        self.first_enq = self.last_done = None
        self.scopes = list(scopes)

    def launch(self, kind, enq, phase="attr_bookkeep_ms"):
        t = dc.Launch(kind, None, enq, phase)
        if self.first_enq is None:
            self.first_enq = enq
        self._pending.append(t)
        return t

    def _idle_by(self, lo, hi, phase):
        return (dc.cut(lo, hi, self.scopes) if self.scopes
                else {phase: hi - lo})


def close_ms(clock):
    dev, idle = clock.close()
    return ({k: v / MS for k, v in dev.items()},
            {k: v / MS for k, v in idle.items()})


def test_a_program_queued_behind_another_starts_at_its_end():
    c = Clock()
    a = c.launch("decode", 0 * MS)
    b = c.launch("decode", 2 * MS)     # launched while a still runs
    a.read, b.read = 10 * MS, 20 * MS
    dev, idle = close_ms(c)
    assert dev == {"decode": pytest.approx(20.0), "prefill": 0.0}
    assert idle == {}
    assert c.last_done == 20 * MS


def test_a_program_after_idle_starts_at_its_enqueue_and_the_gap_is_idle():
    c = Clock()
    a = c.launch("prefill", 0 * MS)
    a.read = 10 * MS
    b = c.launch("decode", 13 * MS, phase="attr_admit_ms")
    b.read = 20 * MS
    dev, idle = close_ms(c)
    assert dev == {"decode": pytest.approx(7.0),
                   "prefill": pytest.approx(10.0)}
    assert idle == {"attr_admit_ms": pytest.approx(3.0)}


@pytest.mark.parametrize("watched, read, done", [
    (9.2, 10.0, 9.2),       # the step thread was busy: the watcher's
    (10.6, 10.0, 10.0),     # the watcher lagged: the read-back's
    (None, 10.0, 10.0),     # the watcher had not stamped yet
])
def test_done_is_the_earlier_of_the_two_readings(watched, read, done):
    c = Clock()
    a = c.launch("decode", 1 * MS)
    a.watched = None if watched is None else watched * MS
    a.read = read * MS
    dev, _ = close_ms(c)
    assert dev["decode"] == pytest.approx(done - 1.0)
    assert c.last_done == pytest.approx(done * MS)


def test_a_late_reading_before_an_early_one_charges_no_negative_time():
    """The step before read late (an upper bound), this one's watcher
    stamp close to its end: done is held at the one before."""
    c = Clock()
    a = c.launch("decode", 0 * MS)
    b = c.launch("decode", 1 * MS)
    a.read = 12 * MS
    b.watched, b.read = 11.5 * MS, 30 * MS
    dev, idle = close_ms(c)
    assert dev["decode"] == pytest.approx(12.0)
    assert idle == {}


def test_an_untimed_program_folds_into_the_next_timed_one():
    """A page zeroing queued between a prefill and a decode step: the
    clock never sees it, so its device time is the decode step's."""
    c = Clock()
    p = c.launch("prefill", 0 * MS)
    p.read = 10 * MS
    # zero_pages runs 10.0-10.3 on the device; the decode step is queued
    # at 10.1, behind it, and ends at 18
    d = c.launch("decode", 10.1 * MS)
    d.read = 18 * MS
    dev, idle = close_ms(c)
    assert dev["decode"] == pytest.approx(7.9)
    assert dev["prefill"] + dev["decode"] + sum(idle.values()) \
        == pytest.approx(18.0)


def test_idle_is_cut_by_the_innermost_scope_and_suffixes_are_dropped():
    scopes = [("generation::admit", 10 * MS, 16 * MS),
              ("generation::prefill[b=256]", 11 * MS, 12.5 * MS),
              ("generation::step[m=96]", 17 * MS, 18.5 * MS)]
    c = Clock(scopes)
    a = c.launch("prefill", 5 * MS)
    a.read = 11.5 * MS
    b = c.launch("decode", 18 * MS)
    b.read = 30 * MS
    dev, idle = close_ms(c)
    # 11.5-12.5 under prefill, 12.5-16 under admit, 16-17 under none,
    # 17-18 under step
    assert idle == {"generation::prefill": pytest.approx(1.0),
                    "generation::admit": pytest.approx(3.5),
                    "none": pytest.approx(1.0),
                    "generation::step": pytest.approx(1.0)}
    assert dev["decode"] == pytest.approx(12.0)
    assert dc.cut(0.0, 1.0, []) == {"none": 1.0}


def test_a_step_goes_to_the_record_that_read_it_its_idle_to_the_launch():
    """Iteration 1 launches step n (after idle) and does not read it;
    iteration 2 reads it: the idle is iteration 1's, the device time
    iteration 2's."""
    c = Clock()
    p = c.launch("prefill", 0 * MS)
    p.read = 5 * MS
    d = c.launch("decode", 6 * MS)
    dev1, idle1 = close_ms(c)
    assert dev1 == {"decode": 0.0, "prefill": pytest.approx(5.0)}
    assert idle1 == {"attr_bookkeep_ms": pytest.approx(1.0)}
    d.read = 20 * MS
    dev2, idle2 = close_ms(c)
    assert dev2 == {"decode": pytest.approx(14.0), "prefill": 0.0}
    assert idle2 == {}
    # a dropped program (abort) is charged nothing
    e = c.launch("decode", 25 * MS)
    c.dropped(e)
    assert close_ms(c) == ({"decode": 0.0, "prefill": 0.0}, {})


def test_the_ring_reader_walks_back_only_as_far_as_asked():
    def scope_on_a_thread(out):
        with paddle.profiler.RecordEvent("generation::old"):
            pass
        t = time.perf_counter()
        with paddle.profiler.RecordEvent("generation::admit"):
            with paddle.profiler.RecordEvent("generation::prefill[b=8]"):
                pass
        with paddle.profiler.RecordEvent("other"):
            pass
        out.extend(tracer.own_scopes(t, "generation::"))
    got = []
    th = threading.Thread(target=scope_on_a_thread, args=(got,))
    th.start()
    th.join(10)
    assert [n for n, _, _ in got] == ["generation::admit",
                                      "generation::prefill[b=8]"]


# -- the engine ---------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    paddle.seed(38)
    net = GPTForCausalLM(GPTConfig.tiny(dropout=0.0))
    net.eval()
    return net


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("request_timeout_ms", 0)
    return serving.GenerationEngine(model, **kw)


def _watchers(name):
    return [t for t in threading.enumerate()
            if t.name == f"{name}-genwatch" and t.is_alive()]


def test_the_records_tile_the_devices_timeline_and_the_watcher_ends(model):
    name = "devclock_on"
    eng = _engine(model, name=name)
    assert len(_watchers(name)) == 1
    rs = np.random.RandomState(0)
    futs = [eng.submit(rs.randint(1, 500, size=int(n)).astype("int64"),
                       max_new_tokens=int(m))
            for n, m in zip(rs.randint(3, 15, size=6),
                            rs.randint(2, 7, size=6))]
    for f in futs:
        f.result(timeout=120)
    recs = eng._step_log.tail(10_000)
    clock = eng._devclock
    eng.shutdown(drain=True, timeout_s=30)
    assert _watchers(name) == []
    assert not clock._thread.is_alive() and not eng._thread.is_alive()
    total = sum(r["decode_dev_ms"] + r["prefill_dev_ms"] + r["dev_idle_ms"]
                for r in recs)
    span_ms = (clock.last_done - clock.first_enq) * 1000.0
    assert total == pytest.approx(span_ms, abs=0.002 * 4 * len(recs))
    assert sum(r["prefill_dev_ms"] > 0 for r in recs) >= 1
    assert sum(r["decode_dev_ms"] > 0 for r in recs) >= 1
    for r in recs:
        assert sum(r["dev_idle_by"].values()) == pytest.approx(
            r["dev_idle_ms"], abs=1e-6)
        assert all(v > 0 for v in r["dev_idle_by"].values())
        assert all(k == "none" or (k.startswith("generation::")
                                   and "[" not in k)
                   for k in r["dev_idle_by"])
        # a record's device time is that of what it READ: decode steps
        # where decode_ms is charged, prefills where prefill_ms is
        assert (r["decode_dev_ms"] > 0) <= (r["decode_ms"] > 0)
        assert (r["prefill_dev_ms"] > 0) <= (r["prefill_ms"] > 0)
    # the idle includes the engine's own wait for requests, under its scope
    assert sum(r["dev_idle_ms"] for r in recs) > 0


def test_no_watcher_without_the_step_ring(model):
    name = "devclock_off"
    paddle.set_flags({"FLAGS_gen_step_log": False})
    try:
        eng = _engine(model, name=name)
    finally:
        paddle.set_flags({"FLAGS_gen_step_log": True})
    try:
        assert eng._devclock is None and _watchers(name) == []
        out = eng.submit(np.arange(1, 6, dtype="int64"),
                         max_new_tokens=3).result(timeout=120)
        assert len(out) == 5 + 3
    finally:
        eng.shutdown(drain=True, timeout_s=30)
    assert not eng._thread.is_alive()


def test_with_the_trace_ring_off_idle_goes_to_the_host_bucket(model):
    name = "devclock_noring"
    paddle.set_flags({"FLAGS_flight_recorder": False})
    try:
        eng = _engine(model, name=name)
        for f in [eng.submit(np.arange(1, 9, dtype="int64"),
                             max_new_tokens=4) for _ in range(3)]:
            f.result(timeout=120)
        recs = eng._step_log.tail(10_000)
        eng.shutdown(drain=True, timeout_s=30)
    finally:
        paddle.set_flags({"FLAGS_flight_recorder": True})
    labels = {k for r in recs for k in r["dev_idle_by"]}
    assert labels and labels <= {"attr_admit_ms", "attr_bookkeep_ms",
                                 "attr_promote_ms"}


def test_an_older_record_parses_with_the_new_fields_at_their_defaults():
    rec = step_log.StepRecord(it=1, decode_ms=3.0)
    d = rec.to_dict()
    assert d["decode_dev_ms"] == 0 and d["dev_idle_ms"] == 0
    assert d["dev_idle_by"] == {}
    assert step_log.StepRecord(it=2).dev_idle_by is not d["dev_idle_by"]


def test_engine_report_shows_the_device_columns_and_idle_by_span(
        model, tmp_path, capsys):
    import importlib.util
    import json
    import os
    name = "devclock_report"
    eng = _engine(model, name=name)
    for f in [eng.submit(np.arange(1, 9, dtype="int64"), max_new_tokens=4)
              for _ in range(3)]:
        f.result(timeout=120)
    payload = step_log.steps_payload()
    eng.shutdown(drain=True, timeout_s=30)
    path = tmp_path / "steps.json"
    path.write_text(json.dumps(payload))
    spec = importlib.util.spec_from_file_location(
        "engine_report", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "engine_report.py"))
    er = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(er)
    assert er.main([str(path), "--engine", name]) == 0
    text = capsys.readouterr().out
    assert "dev_idle" in text and "device inc 0: busy" in text
    assert "device idle by span: generation::" in text
    recs = payload["engines"][name]["records"]
    d = er.summarize(recs)["device"][0]
    assert d["idle_ms"] == pytest.approx(
        sum(r["dev_idle_ms"] for r in recs), abs=1e-3)
    assert sum(d["idle_by"].values()) == pytest.approx(d["idle_ms"],
                                                       abs=1e-3)
    # records from before the timeline roll up to nothing
    assert er.summarize([{k: v for k, v in r.items()
                          if not k.startswith("dev_idle")}
                         for r in recs])["device"] == {}
