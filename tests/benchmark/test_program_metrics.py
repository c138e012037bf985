"""CPU tests of the four per-layer readers of PR 25, which read the
program's own step records: each on hand-made records against hand-worked
numbers, what each does with a ring that holds nothing to read (None: the
line is refused) and with a program from before the records existed
(NO_RECORD), and both rehearsals printing all four, finite and above 0.
Nothing here measures anything.
"""
import json
import math
import os
import statistics
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_line, program_records, run  # noqa: E402
from benchmark.program_records import NO_RECORD  # noqa: E402

NEW = {"train": ("input_wait_share.train", "host_step_ms.train"),
       "serve": ("host_step_ms.serve", "queue_wait_ms.serve")}


def reader(name):
    return run.load_by_name("metrics", name)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def fit_rec(fit, step, wait, sync, wall, dispatch=1.0):
    return {"fit": fit, "step": step, "t": float(step), "input_wait_ms": wait,
            "prep_ms": 0.0, "dispatch_ms": dispatch, "sync_ms": sync,
            "callback_ms": 0.0, "other_ms": wall - wait - sync - dispatch,
            "wall_ms": wall}


def test_input_wait_share_is_a_ratio_of_sums_over_the_same_records():
    recs = [fit_rec(2, 0, 1.0, 0.0, 10.0), fit_rec(2, 1, 3.0, 50.0, 70.0),
            fit_rec(2, 2, 0.0, 0.0, 20.0)]
    assert reader("input_wait_share.train").share(recs) == 100.0 * 4 / 100
    assert reader("input_wait_share.train").share([]) is None


def test_host_step_reads_the_steps_after_a_sync_where_the_launch_blocks():
    """The v5e's records (PR 25): the jitted call returns when the step
    before has finished, so dispatch_ms holds 183 ms of waiting on every
    step but the one after a sync."""
    recs = []
    for base, after in ((10, 11.0), (20, 12.5), (30, 10.5)):
        recs.append(fit_rec(2, base, 0.1, 5.0, 195.0, dispatch=188.0))
        recs.append(fit_rec(2, base + 1, 0.1, 0.0, after, dispatch=9.0))
        recs += [fit_rec(2, base + k, 0.1, 0.0, 190.0, dispatch=186.0)
                 for k in range(2, 10)]
    # what the host does alone: 11.0, 12.5, 10.5 -> median 11.0 ...
    assert reader("host_step_ms.train").host_ms(recs) == 11.0
    # ... where wall - sync over all the records reads the device's step
    assert statistics.median(r["wall_ms"] - r["sync_ms"]
                             for r in recs) == 190.0
    # a step that follows a sync and syncs itself (log_freq 1) counts its
    # wall less its own wait
    each = [fit_rec(2, s, 0.0, 180.0, 191.0 + s) for s in range(3)]
    assert reader("host_step_ms.train").host_ms(each) == 12.5
    assert reader("host_step_ms.train").host_ms(recs[1:10]) is None
    assert reader("host_step_ms.train").host_ms([]) is None


@pytest.mark.parametrize("cadence", [1, 4, 10])
def test_the_train_readers_take_the_newest_fit_from_its_second_sync(
        monkeypatch, cadence):
    from paddle_tpu.profiler import step_log

    def fit(n, steps, wait, after):
        return [fit_rec(n, s, wait, 6.0 if s % cadence == 0 else 0.0,
                        after if (s - 1) % cadence == 0 else 10.0)
                for s in range(steps)]
    recs = fit(1, 8, 9.0, 10.0) + fit(2, 2 * cadence + 2, 1.0, 8.0)
    monkeypatch.setattr(step_log, "fit_records", lambda: recs)
    window = program_records.fit_window()
    assert [r["fit"] for r in window] == [2] * (cadence + 2)
    assert [r["step"] for r in window] == list(range(cadence,
                                                     2 * cadence + 2))
    wall = sum(r["wall_ms"] for r in window)
    assert reader("input_wait_share.train").read({}) == pytest.approx(
        100.0 * (cadence + 2) / wall)
    # the steps after a sync: wall 8, less their own wait where every step
    # syncs
    assert reader("host_step_ms.train").read({}) == pytest.approx(
        2.0 if cadence == 1 else 8.0)


def test_a_ring_with_nothing_to_read_leaves_the_metric_out(monkeypatch):
    """Empty, cleared, or a fit that never reached its second log cadence:
    None, which run.py drops and check_line then refuses."""
    from paddle_tpu.profiler import step_log
    short = [fit_rec(1, s, 1.0, 6.0 if s == 0 else 0.0, 10.0)
             for s in range(7)]
    for recs in ([], short):
        monkeypatch.setattr(step_log, "fit_records", lambda r=recs: r)
        assert program_records.fit_window() == []
        for name in NEW["train"]:
            assert reader(name).read({}) is None
    m = manifest()
    cell = m["per_layer"][-4]["workloads"][0]
    line = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
            "device": {"platform": "cpu", "kind": "cpu", "count": 1,
                       "memory_peak_bytes": 1}}
    with pytest.raises(check_line.BadLine, match="missing from the line"):
        check_line.check_line(m, cell, 1, line)


def test_a_program_older_than_the_fit_ring_reads_no_record(monkeypatch):
    """The parent of PR 25 under this PR's benchmark files: finite, so its
    traced line passes check_line, and no possible reading."""
    from paddle_tpu.profiler import step_log
    monkeypatch.delattr(step_log, "fit_records")
    assert program_records.fit_window() is None
    for name in NEW["train"]:
        assert reader(name).read({}) == NO_RECORD
    assert NO_RECORD < 0 and math.isfinite(NO_RECORD)


def step_rec(decode, wall, idle=0.0, dwait=None, pwait=None, admitted=0,
             await_ms=None):
    r = {"decode_ms": decode, "prefill_ms": 0.0, "attr_wall_ms": wall,
         "attr_idle_ms": idle, "admitted": admitted}
    for k, v in (("decode_wait_ms", dwait), ("prefill_wait_ms", pwait),
                 ("admit_wait_ms", await_ms)):
        if v is not None:
            r[k] = v
    return r


def test_host_step_serve_leaves_out_idle_and_both_waits():
    steps = [step_rec(140.0, 150.0, idle=1.0, dwait=138.0, pwait=0.0),
             step_rec(140.0, 190.0, idle=0.0, dwait=137.0, pwait=40.0),
             step_rec(0.0, 30.0, idle=29.0, dwait=0.0, pwait=0.0),
             step_rec(140.0, 147.0, idle=0.0, dwait=139.0, pwait=0.0)]
    # decode iterations only: 11, 13, 8 -> median 11
    assert reader("host_step_ms.serve").read({"steps": steps}) == 11.0
    # a window that ran no decode step has nothing to read
    assert reader("host_step_ms.serve").read({"steps": steps[2:3]}) is None
    assert reader("host_step_ms.serve").read({"steps": []}) is None
    # records from before the wait fields: not the whole busy iteration
    # (148 ms here) under the host's name
    old = [step_rec(140.0, 150.0, idle=1.0), step_rec(140.0, 147.0)]
    assert reader("host_step_ms.serve").read({"steps": old}) == NO_RECORD


def test_queue_wait_is_the_mean_over_the_admissions():
    steps = [step_rec(1.0, 2.0, admitted=2, await_ms=9000.0),
             step_rec(1.0, 2.0, await_ms=0.0),
             step_rec(1.0, 2.0, admitted=1, await_ms=3000.0)]
    assert reader("queue_wait_ms.serve").read({"steps": steps}) == 4000.0
    assert reader("queue_wait_ms.serve").read({"steps": steps[1:2]}) is None
    assert reader("queue_wait_ms.serve").read({"steps": []}) is None
    assert reader("queue_wait_ms.serve").read({"steps": [
        step_rec(1.0, 2.0, admitted=3)]}) == NO_RECORD


def test_the_four_metrics_are_entries_appended_to_the_manifest():
    """Looked up by name, wherever in `per_layer` they stand: a later PR
    may only append to the list, so PR 27's two entries follow them."""
    m = manifest()
    four = [p for p in m["per_layer"]
            if p["name"] in NEW["train"] + NEW["serve"]]
    assert [p["name"] for p in four] == list(NEW["train"] + NEW["serve"])
    assert all(p["source"] == "program_span" for p in four)
    check_line.check_manifest(m, ROOT)


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_a_traced_rehearsal_prints_the_new_metrics_finite(kind):
    m = manifest()
    cell = next(w["name"] for w in m["workloads"]
                if run.load_json(next(c["file"] for c in m["configs"]
                                      if c["name"] == w["config"]))["kind"]
                == kind)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 25), "--seconds", "3",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=ROOT), cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    check_line.check_line(m, cell, 1, line)
    units = {p["name"]: p["unit"] for p in m["per_layer"]}
    for name in NEW[kind]:
        got = line["metrics"][name]
        assert math.isfinite(got["value"]) and got["value"] > 0, (name, got)
        assert got["unit"] == units[name]
