#!/usr/bin/env python3
"""Where a traced window's device time and idle time went, by the names the
program gives: the operator's reader for `Profiler(log_dir=d)`.

    python tools/trace_report.py <trace_dir>

For the first chip of the newest trace under <trace_dir> it prints

  programs   device time by `XLA Modules` name: one row per jitted program
             (`jit_gen_decode` against `jit_gen_prefill`), runs and time;
  scopes     device time of `XLA Ops` grouped by the leading levels of the
             name stack each operation carries (`jax.named_scope`, Layer
             names, `jvp(...)` / `transpose(jvp(...))` as JAX writes them;
             `jit(...)` wrappers dropped, numbers collapsed to `*` so the
             twelve layers are one row), the operation's own name last;
             operations with no scope below the program are `unscoped`;
             an operation the compiler added (a copy, a slice: no name
             stack of its own) takes the scope of the operation whose
             output it reads, and what is still unscoped is split by the
             compiler's category (`unscoped: data formatting` is then
             the copies of a program's ARGUMENTS, such as the K/V pools);
  kernels    custom calls (Pallas kernels) by their own name;
  gaps       idle gaps longer than 0.5 ms on that chip, summed by the
             innermost program span (`fit::`, `feeder::`, `generation::`)
             that covers each gap's midpoint — a span of the thread that
             launches the programs first, any other thread's second —
             else `no program span`;
  await      by program the engine times (`jit_gen_decode`,
             `jit_gen_prefill`, ...), the median and p95 of how far the
             end of each `generation::await` span — the generation
             engine's own stamp of that program's end — lies after the
             device's end of the run (plus the clock offset) and after
             the run's `CompleteCallbacks`: how late the engine's device
             clock reads (paddle_tpu/serving/device_clock.py).

The device's events read earlier than the host span that launched them
(1.3 ms in one trace, 5.5 ms in another: PERF.md); no program run starts
before the span that launched it, so the least shift that makes that true
of every run is taken as the clock offset, printed, and added to device
times before a gap is laid to a span. A run is paired with the runtime's
own `DoEnqueueProgram` span of the same `run_id` (the innermost launch, so
the bound is tight; the completion callbacks of the same runs bound it from
above, and that is printed too). The window is the `bench:window` span
where the trace has one, else the extent of the device's operations.

What the trace looks like on this libtpu (0.0.34, looked at by hand,
PR 25): an `XLA Ops` event's own statistics are times only; its name stack
(`tf_op`, JAX's op_name with a trailing colon) and `hlo_category` are
statistics of the event's METADATA, which `jax.profiler.ProfileData` does
not show, so the plane's metadata is read from the file's wire format
(paddle_tpu/onnx/wire.py, no TensorFlow), in the one pass that also takes
the `run_id`s. Copies and slices the compiler adds carry no `tf_op`. A Pallas
kernel shows as a custom call named by the `name=` of its `pl.pallas_call`
(`%flash_bwd_dkv.16`).

`load` and `union` are the benchmark's (benchmark/trace_reduce.py); what
this adds is the grouping, which the next benchmark issue lifts there.
"""
import argparse
import glob
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark.trace_reduce import (OP_LINE, WINDOW_SPAN, load,  # noqa: E402
                                    union)

DEPTH = 7       # levels of the name stack a scope keeps
GAP_MS = 0.5    # gaps longer than this are laid to a span
TOP = 30        # rows a table prints before it sums the rest
MODULE_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIXES = ("fit::", "feeder::", "generation::")
# the statistics of an operation's metadata that may hold its name stack, in
# the order they are tried (libtpu 0.0.34 on the v5e: `tf_op`)
STACK_STATS = ("tf_op", "op_name", "name_stack", "long_name")
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
# the program's spans around its launches: the thread that has them is the
# one whose spans a gap is laid to first
LAUNCH_SPANS = ("fit::train_step", "generation::step[",
                "generation::verify[", "generation::prefill")
# the engine's watcher waits for each program it times under this span,
# whose end is the engine's stamp of the program's end
# (paddle_tpu/serving/device_clock.py)
AWAIT = "generation::await"
TIMED = ("gen_decode", "gen_verify", "gen_prefill", "gen_prefill_tail")
RUN_MATCH_NS = 1000     # a run's start as `load` and the wire pass read it
_WRAPPER = re.compile(r"^p?jit\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CUSTOM_CALL = re.compile(r"[\]})] custom-call\(")
_NUMBERED = re.compile(r"(\.remat\d*|\.\d+)+$")
_OPERAND = re.compile(r"%[\w.\-]+")


def newest_trace(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        sys.exit(f"trace_report: no .xplane.pb under {trace_dir}")
    return found[-1]


def stack_of(stats):
    """The name stack among an operation's statistics, '' where none holds
    one. A stat that holds the whole HLO text gives its op_name."""
    for key in STACK_STATS:
        v = stats.get(key)
        if not v:
            continue
        v = str(v)
        m = _OP_NAME.search(v)
        if m:
            return m.group(1)
        if "/" in v and " = " not in v:
            return v.rstrip(":")
    return ""


def _text(msg, field):
    return msg.get(field, [b""])[0].decode(errors="replace")


def read_wire(path, plane_name):
    """What `load` does not give, in one pass over the .xplane.pb's wire
    format: ({event name: (name stack, hlo category)} from the event
    metadata of the plane `plane_name`, {run_id: (start, end)} of the
    program runs on its `XLA Modules` line, [(name, run_id, start, end)] of
    the host events that name a run — the runtime stamps both sides of a
    launch with one `run_id`). Times in ns, as `load` gives them.

    XSpace 1=planes; XPlane 2=name 3=lines 4=event_metadata 5=stat_metadata
    (maps: 1=key 2=value); XLine 2=name 3=timestamp_ns 4=events; XEvent
    1=metadata_id 2=offset_ps 3=duration_ps 4=stats; XEventMetadata 2=name
    5=stats; XStatMetadata 2=name; XStat 1=metadata_id 3=uint64_value
    4=int64_value 5=str_value 7=ref_value (a stat_metadata id whose name is
    the value)."""
    from paddle_tpu.onnx.wire import decode
    with open(path, "rb") as f:
        space = decode(f.read())
    metadata, runs, launches = {}, {}, []
    for raw in space.get(1, []):
        plane = decode(raw)
        on_chip = _text(plane, 2) == plane_name
        if not on_chip and not _text(plane, 2).startswith("/host:"):
            continue
        stat_names = {}
        for entry in plane.get(5, []):
            e = decode(entry)
            stat_names[e[1][0]] = _text(decode(e[2][0]), 2)
        event_names = {}
        for entry in plane.get(4, []):
            e = decode(entry)
            meta = decode(e[2][0])
            event_names[e[1][0]] = _text(meta, 2)
            if not on_chip:
                continue
            stats = {}
            for raw_stat in meta.get(5, []):
                st = decode(raw_stat)
                key = stat_names.get(st.get(1, [0])[0])
                if 5 in st:
                    stats[key] = st[5][0].decode(errors="replace")
                elif 7 in st:
                    stats[key] = stat_names.get(st[7][0], "")
            metadata[_text(meta, 2)] = (stack_of(stats),
                                        stats.get("hlo_category", ""))
        for raw_line in plane.get(3, []):
            line = decode(raw_line)
            if on_chip and _text(line, 2) != MODULE_LINE:
                continue
            t0 = line.get(3, [0])[0]
            for raw_event in line.get(4, []):
                ev = decode(raw_event)
                name = event_names.get(ev.get(1, [0])[0], "")
                if not on_chip and name not in (ENQUEUE, COMPLETE):
                    continue
                rid = None
                for raw_stat in ev.get(4, []):
                    st = decode(raw_stat)
                    if stat_names.get(st.get(1, [0])[0]) == "run_id":
                        rid = (st.get(3) or st.get(4) or [None])[0]
                if rid is None:
                    continue
                start = t0 + ev.get(2, [0])[0] / 1e3
                end = start + ev.get(3, [0])[0] / 1e3
                if on_chip:
                    runs[rid] = (start, end)
                else:
                    launches.append((name, rid, start, end))
    return metadata, runs, launches


def clock_offset(runs, host):
    """(least offset, runs paired, greatest offset or None) in ns: no run
    starts before its own enqueue starts, and none ends after its
    completion callback starts. (0, 0, None) where the trace pairs none."""
    low = [s - runs[rid][0] for n, rid, s, _ in host
           if n == ENQUEUE and rid in runs]
    high = [s - runs[rid][1] for n, rid, s, _ in host
            if n == COMPLETE and rid in runs]
    if not low:
        return 0.0, 0, None
    return max(0.0, max(low)), len(low), (min(high) if high else None)


def scope_of(stack):
    """The leading DEPTH levels of a name stack: `jit(...)` wrappers
    dropped, the operation's own name (the last level) dropped, numbers
    collapsed. '' where nothing is left: the operation is unscoped."""
    parts = []
    for c in stack.split("/")[:-1]:
        m = _WRAPPER.match(c)
        if m:
            if not parts:
                continue        # jit(train_step), jit(main): the program
            c = m.group(1)
        parts.append(re.sub(r"\d+", "*", c))
    return "/".join(parts[:DEPTH])


def program_of(module_event):
    """`jit_gen_decode(1234)` -> `gen_decode`."""
    name = module_event.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def await_lags(modules, awaits, runs, launches, offset):
    """How late the engine's watcher saw each timed program end: for every
    run of a program the engine times (TIMED), the end of the
    `generation::await` span that waited for it less the run's end on the
    device (shifted by the clock offset, which is a lower bound: the lag is
    then an upper bound) and less the start of the run's own
    `CompleteCallbacks` (paired by run_id). The watcher waits for the
    programs one at a time in launch order and no wait ends before its
    program has, so a run takes the first wait not yet taken that ends at
    or after it — and before the next timed run ends: a run whose wait
    began before the trace did has none, and is left out.
    {program: {"device": [ms], "complete": [ms]}}."""
    import bisect
    starts = sorted((s, rid) for rid, (s, _) in runs.items())
    complete = {}
    for n, rid, s, _ in launches:
        if n == COMPLETE:
            complete[rid] = min(s, complete.get(rid, s))
    ends = sorted(e for _, _, e in awaits)
    timed = sorted((e, s, program_of(n)) for n, s, e in modules
                   if program_of(n) in TIMED)
    out, i = {}, 0
    for k, (e, s, prog) in enumerate(timed):
        i = bisect.bisect_left(ends, e + offset, i)
        if i == len(ends):
            break
        if k + 1 < len(timed) and ends[i] >= timed[k + 1][0] + offset:
            continue
        row = out.setdefault(prog, {"device": [], "complete": []})
        row["device"].append((ends[i] - e - offset) / 1e6)
        j = bisect.bisect_left(starts, (s - RUN_MATCH_NS,))
        if j < len(starts) and abs(starts[j][0] - s) <= RUN_MATCH_NS \
                and starts[j][1] in complete:
            row["complete"].append(
                (ends[i] - complete[starts[j][1]]) / 1e6)
        i += 1
    return out


def _spread(ms):
    """(median, p95) of a list of ms, or (None, None)."""
    if not ms:
        return None, None
    import numpy as np
    return float(np.median(ms)), float(np.percentile(ms, 95))


def reduce(planes, metadata, runs, launches):
    """The report's numbers from `load`'s planes and `read_wire`'s three."""
    devices = sorted((p for p in planes
                      if p["name"].startswith(DEVICE_PREFIX)),
                     key=lambda p: p["name"])
    if not devices:
        sys.exit(f"trace_report: no plane named {DEVICE_PREFIX}* among "
                 f"{[p['name'] for p in planes]}")
    chip = devices[0]
    lines = {ln["name"]: ln["events"] for ln in chip["lines"]}
    ops, modules = lines.get(OP_LINE, []), lines.get(MODULE_LINE, [])
    if not ops:
        sys.exit(f"trace_report: no `{OP_LINE}` line on {chip['name']}")
    host = [ev for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"] for ev in ln["events"]]
    offset, pairs, offset_max = clock_offset(runs, launches)
    window = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if window:      # host clock: bring it to the device's
        lo, hi = window[0][0] - offset, window[0][1] - offset
    else:
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    busy, gaps = union([(s, e) for _, s, e in ops], lo, hi)

    def clipped(s, e):
        return max(0.0, min(e, hi) - max(s, lo))

    by_program = {}
    for n, s, e in modules:
        if clipped(s, e) > 0:
            row = by_program.setdefault(program_of(n), [0, 0.0])
            row[0] += 1
            row[1] += clipped(s, e)
    producer = {n.split(" = ")[0].strip(): n for n in metadata}

    def scope_and_category(n):
        """An operation's scope; without one, that of the operation whose
        output it reads (its first operand), a few hops back."""
        category = metadata.get(n, ("", ""))[1]
        for _ in range(4):
            scope = scope_of(metadata.get(n, ("", ""))[0])
            operands = _OPERAND.findall(n.split(" = ", 1)[-1])
            if scope or not operands or operands[0] not in producer:
                return scope, category
            n = producer[operands[0]]
        return "", category

    by_scope, by_kernel, unscoped = {}, {}, 0.0
    for n, s, e in ops:
        d = clipped(s, e)
        if d <= 0:
            continue
        scope, category = scope_and_category(n)
        if not scope:
            unscoped += d
            scope = f"unscoped: {category}" if category else "unscoped"
        by_scope[scope] = by_scope.get(scope, 0.0) + d
        if category == "custom-call" or _CUSTOM_CALL.search(n):
            kernel = _NUMBERED.sub("", n.split(" = ")[0].strip("% "))
            by_kernel[kernel] = by_kernel.get(kernel, 0.0) + d
    spans = []      # (on another thread than the launches, name, start, end)
    for p in planes:
        for ln in p["lines"] if p["name"].startswith("/host:") else ():
            ours = [ev for ev in ln["events"]
                    if ev[0].startswith(SPAN_PREFIXES)]
            other = not any(n.startswith(LAUNCH_SPANS) for n, _, _ in ours)
            spans += [(other, n, s, e) for n, s, e in ours]
    long_gaps = [(s, e) for s, e in gaps if e - s > GAP_MS * 1e6]
    by_span, named = {}, 0.0
    for s, e in long_gaps:
        mid = (s + e) / 2 + offset
        inside = [(other, b - a, n) for other, n, a, b in spans
                  if a <= mid <= b]
        label = min(inside)[2] if inside else "no program span"
        label = re.sub(r"\[.*\]$", "", label)
        by_span[label] = by_span.get(label, 0.0) + (e - s)
        named += (e - s) if inside else 0.0
    idle_long = sum(e - s for s, e in long_gaps)
    lags = await_lags(modules, [ev for ev in host if ev[0] == AWAIT],
                      runs, launches, offset)
    return {
        "await_lag_ms": {
            prog: dict(zip(("runs", "median", "p95", "complete_median",
                            "complete_p95"),
                           (len(row["device"]), *_spread(row["device"]),
                            *_spread(row["complete"]))))
            for prog, row in lags.items()},
        "chip": chip["name"], "window_ms": (hi - lo) / 1e6,
        "busy_ms": busy / 1e6, "idle_share": 1.0 - busy / (hi - lo),
        "clock_offset_ms": offset / 1e6, "offset_pairs": pairs,
        "clock_offset_max_ms": (None if offset_max is None
                                else offset_max / 1e6),
        "programs": {k: {"runs": c, "ms": t / 1e6}
                     for k, (c, t) in by_program.items()},
        "scopes_ms": {k: v / 1e6 for k, v in by_scope.items()},
        "kernels_ms": {k: v / 1e6 for k, v in by_kernel.items()},
        "unscoped_share": unscoped / busy if busy else 0.0,
        "scoped": any(s for s, _ in metadata.values()),
        "gaps": {"count": len(long_gaps), "idle_ms": idle_long / 1e6,
                 "all_idle_ms": (hi - lo - busy) / 1e6,
                 "by_span_ms": {k: v / 1e6 for k, v in by_span.items()},
                 "named_share": named / idle_long if idle_long else 1.0},
    }


def _table(title, rows, total):
    out = [title]
    rows = sorted(rows.items(), key=lambda kv: -kv[1])
    if len(rows) > TOP:
        rows = rows[:TOP] + [(f"({len(rows) - TOP} more rows)",
                              sum(ms for _, ms in rows[TOP:]))]
    for name, ms in rows:
        share = 100 * ms / total if total else 0.0
        out.append(f"  {ms:10.3f} ms {share:5.1f}%  {name}")
    return out


def render(r):
    g = r["gaps"]
    out = [f"{r['chip']}: window {r['window_ms']:.3f} ms, busy "
           f"{r['busy_ms']:.3f} ms, idle {100 * r['idle_share']:.2f}%",
           "clock offset: no run of this trace is paired with its launch; 0 "
           "taken" if not r["offset_pairs"] else
           f"clock offset: device reads {r['clock_offset_ms']:.3f} ms early "
           f"({r['offset_pairs']} runs against their launches, paired by "
           f"run_id"
           + ("" if r["clock_offset_max_ms"] is None else
              f"; at most {r['clock_offset_max_ms']:.3f} ms by their "
              f"completions") + ")",
           "", "programs (`XLA Modules`): device time, share of busy, runs"]
    for name, p in sorted(r["programs"].items(),
                          key=lambda kv: -kv[1]["ms"]):
        out.append(f"  {p['ms']:10.3f} ms "
                   f"{100 * p['ms'] / r['busy_ms']:5.1f}%  jit_{name}  "
                   f"x{p['runs']}  ({p['ms'] / p['runs']:.3f} ms a run)")
    out += [""] + _table("scopes (`XLA Ops` by name stack): device time, "
                         "share of busy", r["scopes_ms"], r["busy_ms"])
    if not r["scoped"]:
        out.append("  (no operation of this trace carries a name stack)")
    out.append(f"  unscoped: {100 * r['unscoped_share']:.1f}% of busy")
    if r["kernels_ms"]:
        out += [""] + _table("kernels (custom calls): device time, share "
                             "of busy", r["kernels_ms"], r["busy_ms"])
    out += [""] + _table(
        f"gaps: {g['count']} idle gaps over the limit, {g['idle_ms']:.3f} "
        f"ms of {g['all_idle_ms']:.3f} ms idle, by program span",
        g["by_span_ms"], g["idle_ms"])
    out.append(f"  under a program span: {100 * g['named_share']:.1f}% of "
               f"the idle time in these gaps")
    if r["await_lag_ms"]:
        out += ["", f"`{AWAIT}`: its end after the end of the program run "
                "it waited for (device end + clock offset), and after the "
                "run's CompleteCallbacks, ms"]
        for prog, a in sorted(r["await_lag_ms"].items()):
            cc = ("no CompleteCallbacks paired" if a["complete_median"]
                  is None else f"CompleteCallbacks median "
                  f"{a['complete_median']:.3f} p95 {a['complete_p95']:.3f}")
            out.append(f"  jit_{prog} x{a['runs']}: device median "
                       f"{a['median']:.3f} p95 {a['p95']:.3f}; {cc}")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    args = ap.parse_args(argv)
    path = newest_trace(args.trace_dir)
    planes = load(args.trace_dir)
    chip = min((p["name"] for p in planes
                if p["name"].startswith(DEVICE_PREFIX)), default="")
    print(render(reduce(planes, *read_wire(path, chip))))


if __name__ == "__main__":
    main()
