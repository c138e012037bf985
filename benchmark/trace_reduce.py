"""From a profiler trace (.xplane.pb) to busy time, top operations and gaps.

What the trace looks like on this libtpu (looked at by hand, PR 24, on the
chip): one plane per chip named `/device:TPU:<n>` with the lines `XLA
Modules` (one event per program run), `XLA Ops` (one per operation, not
overlapping; a wait for an asynchronous copy shows as a long `copy-done`),
`Async XLA Ops` (copies and collectives in flight, from start to done,
overlapping the operations: not busy time, and read only for collectives)
and `TC Overlay`; one plane `/host:CPU` with a line per thread, where the line
`python` carries `jax.profiler.TraceAnnotation` spans. Times are
nanoseconds on one axis, but the device's events read about 1 ms earlier
than the host span that launched them, so a gap shorter than a few
milliseconds cannot be laid to a host span.

`busy_s` is, per device, the length of the UNION of its operations'
intervals clipped to the window, then the MEAN over the devices: never a sum
across devices and never a sum of overlapping operations, either of which
can pass the window's length.
"""
import glob
import os
import re

OP_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute")
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


class NoDeviceTrace(RuntimeError):
    """No device plane, or no operation inside the window."""


def open_window(trace_dir):
    """Start a trace and open the `bench:window` span; returns the span for
    `close_window`. The Python tracer is off: it hooks every call on every
    thread and would slow the very host code whose gaps are being read."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
    span.__enter__()
    return span


def close_window(span):
    import jax
    span.__exit__(None, None, None)
    jax.profiler.stop_trace()


def load(trace_dir):
    """Planes of the newest trace under `trace_dir`, as plain lists:
    [{"name", "lines": [{"name", "events": [(name, start_ns, end_ns)]}]}]."""
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise NoDeviceTrace(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(found[-1])
    return [{"name": p.name, "lines": [
        {"name": ln.name,
         "events": [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in ln.events]} for ln in p.lines]}
        for p in data.planes]


def union(intervals, lo, hi):
    """Total length of the union of (start, end) intervals inside
    [lo, hi], and the gaps between them there, as (start, end) pairs."""
    total, gaps, at = 0.0, [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if s > at:
            gaps.append((at, s))
        if e > at:
            total += e - max(s, at)
            at = e
    if hi > at:
        gaps.append((at, hi))
    return total, gaps


def _op_name(name):
    return name.split(" = ")[0].strip()


def reduce(planes, n_devices, device_prefix="/device:TPU:", op_line=OP_LINE):
    """Busy time, operations and idle gaps of the traced window.

    The window is the host span `bench:window`; the events of `op_line` on
    each plane named `device_prefix`* are clipped to it (`op_line` None:
    every line, for a CPU rehearsal whose "device" is the host plane).
    Returns seconds throughout."""
    spans = [(n, s, e) for p in planes if p["name"].startswith("/host:")
             for ln in p["lines"] for n, s, e in ln["events"]
             if n.startswith(SPAN_PREFIX)]
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(window) != 1:
        raise NoDeviceTrace(f"{len(window)} {WINDOW_SPAN} spans in the "
                            f"trace, expected 1")
    lo, hi = window[0]
    devices = sorted((p for p in planes
                      if p["name"].startswith(device_prefix)),
                     key=lambda p: p["name"])
    if len(devices) < n_devices:
        raise NoDeviceTrace(
            f"{len(devices)} planes named {device_prefix}* in the trace, "
            f"the cell uses {n_devices}: "
            f"{[p['name'] for p in planes]}")
    busy, first = [], None
    for p in devices[:n_devices]:
        ops = [ev for ln in p["lines"] if op_line in (None, ln["name"])
               for ev in ln["events"] if not ev[0].startswith(SPAN_PREFIX)]
        total, gaps = union([(s, e) for _, s, e in ops], lo, hi)
        if total <= 0:
            raise NoDeviceTrace(f"no operation ran on {p['name']} inside "
                                f"the traced window")
        busy.append(total)
        if first is None:
            flight = [ev for ln in p["lines"] if ln["name"] == ASYNC_LINE
                      for ev in ln["events"]]
            first = (ops, flight, total, gaps)
    ops, flight, busy0, gaps = first
    by_name = {}
    for n, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            by_name[_op_name(n)] = by_name.get(_op_name(n), 0.0) + d
    coll, _ = union([(s, e) for n, s, e in ops + flight
                     if COLLECTIVE.search(n)], lo, hi)
    labelled = {}
    for s, e in gaps:
        mid = (s + e) / 2
        inside = [(b - a, n) for n, a, b in spans
                  if a <= mid <= b and n != WINDOW_SPAN]
        label = min(inside)[1] if inside else "inside the program"
        labelled[label] = labelled.get(label, 0.0) + (e - s)
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "per_device_busy_s": [b / 1e9 for b in busy],
            "busy0_s": busy0 / 1e9, "collective0_s": coll / 1e9,
            "longest_gap_s": max((e - s for s, e in gaps), default=0.0) / 1e9,
            "device_ops": top(by_name), "idle_gaps": top(labelled)}
