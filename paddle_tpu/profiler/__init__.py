"""Profiler (reference `paddle/fluid/platform/profiler.h:127` RecordEvent /
`:210` EnableProfiler, CUPTI `device_tracer.h`, Python `fluid/profiler.py`).

TPU-native: RecordEvent scopes wrap host-side dispatch and annotate traces
via jax.profiler.TraceAnnotation (visible in the XLA/TPU trace); the
device side is jax.profiler (XPlane → TensorBoard). Host events land in a
thread-aware bounded trace store (`tracer.py` — per-thread rings, real
tids and thread names), so one `export_chrome_tracing` file renders the
collector, dispatch lanes, DeviceFeeder and fit loop as separate named
tracks next to the device trace, plus "C" counter tracks sampled from
`framework.monitor`. The same store feeds the crash flight recorder
(`flight_recorder.py`) and the live `/trace` endpoint
(`exporter.MetricsServer`). The reference's summary table is reproduced
from host timings via `summary()` — `stop_profiler` returns rows and
never prints.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from collections import defaultdict
from typing import Optional

from . import tracer

__all__ = ["RecordEvent", "Profiler", "profiler", "start_profiler",
           "stop_profiler", "export_chrome_tracing", "summary"]


class _StateView:
    """Back-compat shim for the old module-global `_state`: `.events` is
    a merged snapshot of every thread's ring (the old shape —
    `(name, t0, t1)` tuples), `.enabled` the profiler session bit.
    Appending directly is gone; record through RecordEvent/tracer."""

    @property
    def enabled(self) -> bool:
        return tracer.profiler_enabled()

    @property
    def events(self):
        return tracer.events(since=tracer.session_start())


_state = _StateView()


class RecordEvent:
    """RAII scope (reference platform/profiler.h RecordEvent). Usable as a
    context manager or decorator; also emits a jax TraceAnnotation so the
    name shows up in device traces. Events are recorded into the calling
    thread's own ring with its real tid/thread name."""

    def __init__(self, name: str):
        self.name = name
        # stacks, not scalars: one RecordEvent instance may be entered
        # re-entrantly (recursive decorated function, nested `with ev:`)
        self._t0s = []
        self._jax_ctxs = []

    def __enter__(self):
        self.begin()
        return self

    def begin(self):
        self._t0s.append(time.perf_counter())
        ctx = None
        try:
            import jax.profiler
            ctx = jax.profiler.TraceAnnotation(self.name)
            ctx.__enter__()
        except Exception:
            ctx = None
        self._jax_ctxs.append(ctx)

    def end(self):
        """Close the innermost open scope. Safe to call when none is open
        (idempotent tail call), and closes the jax TraceAnnotation even if
        host-side bookkeeping raises."""
        if not self._t0s:
            return
        ctx = self._jax_ctxs.pop()
        t0 = self._t0s.pop()
        try:
            tracer.record_complete(self.name, t0, time.perf_counter())
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)

    def __exit__(self, *exc):
        # runs on the exception path too — the scope must not leak an open
        # TraceAnnotation or a dangling _t0 when the body raises
        self.end()
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*a, **k):
            with RecordEvent(self.name):
                return fn(*a, **k)
        return wrapper


def start_profiler(state="All", tracer_option="Default"):
    tracer.enable()


def _aggregate(events):
    """[(name, t0, t1)] → rows sorted by total ms:
    (name, [calls, total_ms, min_ms, max_ms])."""
    agg = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
    for name, t0, t1 in events:
        dt = (t1 - t0) * 1000
        a = agg[name]
        a[0] += 1
        a[1] += dt
        a[2] = min(a[2], dt)
        a[3] = max(a[3], dt)
    return sorted(agg.items(), key=lambda kv: -kv[1][1])


def summary(rows=None, sorted_key="total", file=None) -> str:
    """Format the reference profiler's event table. `rows` defaults to
    the current session's aggregation; writes to `file` when given (pass
    `sys.stdout` for the old print behavior) and returns the string."""
    if rows is None:
        rows = _aggregate(tracer.events(since=tracer.session_start()))
    lines = [f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Min':>10}"
             f"{'Max':>10}{'Ave':>10}"]
    for name, (calls, total, mn, mx) in rows:
        lines.append(f"{name:<40}{calls:>8}{total:>12.3f}{mn:>10.3f}"
                     f"{mx:>10.3f}{total / max(calls, 1):>10.3f}")
    text = "\n".join(lines)
    if file is not None:
        print(text, file=file)
    return text


def stop_profiler(sorted_key="total", profile_path=None, file=None):
    """End the profiling session and return the aggregated rows. Quiet by
    default (library users and pytest runs stay clean); pass
    `file=sys.stdout` — or call `summary()` — for the table."""
    events = tracer.events(since=tracer.session_start())
    tracer.sample_counters()
    tracer.disable()
    rows = _aggregate(events)
    if file is not None:
        summary(rows, sorted_key, file)
    if profile_path:
        export_chrome_tracing(profile_path)
    return rows


def export_chrome_tracing(path: str):
    """chrome://tracing json of host events: named per-thread tracks plus
    counter tracks (reference profiler chrome trace export merged with
    device_tracer-style per-stream lanes)."""
    tracer.sample_counters()  # at least one sample → counter tracks render
    since = tracer.session_start() or None
    trace = tracer.chrome_trace(since=since)
    from . import step_log
    trace["traceEvents"].extend(step_log.chrome_counter_events(since))
    with open(path, "w") as f:
        json.dump(trace, f)


@contextlib.contextmanager
def profiler(state="All", tracer_option="Default", profile_path=None,
             sorted_key="total", file=None):
    """fluid.profiler.profiler context manager. Pass `file=sys.stdout`
    to print the summary table on exit (the old unconditional print is
    gone — see `summary()`)."""
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path, file=file)


class Profiler:
    """paddle.profiler.Profiler 2.x-style wrapper; with `log_dir` it also
    drives jax.profiler for a device trace: trace with
    `Profiler(log_dir=d)`, read with `python tools/trace_report.py d`
    (device time per program and per named scope, idle gaps by the
    program's spans).

    `step()` is a real step marker: it closes a `ProfilerStep#N` scope on
    the calling thread and snapshots the monitor counters, so the chrome
    trace shows step boundaries and live counter tracks."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, log_dir: Optional[str] = None):
        self.log_dir = log_dir
        self._jax_started = False
        self._step_n = 0
        self._step_t0 = None

    def start(self):
        if self.log_dir:
            # a device trace that cannot start is an error, not a quiet
            # run without one. The Python tracer is off: it hooks every
            # call on every thread and would slow the very host code
            # whose gaps the trace is read for (the benchmark's reason)
            import jax.profiler
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir,
                                     profiler_options=options)
            self._jax_started = True
        start_profiler()
        self._step_n = 0
        self._step_t0 = time.perf_counter()
        return self

    def stop(self):
        try:
            self.step()  # close the open ProfilerStep scope
        finally:
            self._step_t0 = None
            try:
                if self._jax_started:
                    self._jax_started = False
                    import jax.profiler
                    jax.profiler.stop_trace()
            finally:
                stop_profiler()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def step(self):
        """Mark a train-step boundary: one `ProfilerStep#N` scope since
        the previous call plus a counter snapshot."""
        t = time.perf_counter()
        if self._step_t0 is not None:
            tracer.record_complete(f"ProfilerStep#{self._step_n}",
                                   self._step_t0, t)
            self._step_n += 1
        self._step_t0 = t
        tracer.sample_counters()

    def summary(self, sorted_key="total", file=None, **kwargs):
        return summary(sorted_key=sorted_key,
                       file=file if file is not None else sys.stdout)
