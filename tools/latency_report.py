#!/usr/bin/env python
"""Offline per-request latency attribution from an exported chrome trace.

The serving engine's request spans (profiler/spans.py) drop one
self-contained `reqspan:` instant into the trace per resolved request:

    reqspan:<rid>:<engine>:lane<lane>:b<bucket>:q=…,p=…,d=…,r=…,e=…

with the four phase durations (queue / pad / device / resolve) and the
end-to-end latency in milliseconds. This tool reads a trace written by
`profiler.export_chrome_tracing` or `/trace`, and prints:

- per-phase p50 / p99 / mean / max over every request in the trace,
- the top-N slowest requests with their full phase breakdown — the
  "why was THIS request slow" question `/metrics` histograms cannot
  answer.

The continuous-batching GenerationEngine emits a second, slot-flavored
reqspan shape per resolved request (profiler/spans.py GenSpan):

    reqspan:<rid>:<engine>:slot<slot>:n=<tokens>:ttft=…,tpot=…,e=…
                                  [,pfx=…][,acc=…][,inc=…][,tid=…]

with TTFT (queue + prefill to first token), TPOT (steady decode cadence
per output token) and end-to-end milliseconds; `pfx` (ISSUE 12) counts
prompt tokens served from the prefix cache, `acc` (ISSUE 14) the
speculative draft tokens accepted, `inc` (ISSUE 15) the engine
incarnation that resolved the request (>0 = served after a supervised
restart), `tid` (ISSUE 20) the fleet-wide 16-hex trace id — all
optional, so traces from any era parse. Both shapes are parsed;
whichever is present gets its own report section (phase percentiles +
top-N slowest, plus a tokens-per-step summary for generation spans).
When trace ids are present the report also groups reqspans BY REQUEST:
one row per trace id across incarnations and replicas, so a replayed
or re-routed request reads as one logical request, not two.

Usage:  python tools/latency_report.py trace.json [--top 10]
                                       [--engine NAME] [--json]
"""
from __future__ import annotations

import argparse
import json
import re
import sys

_REQSPAN = re.compile(
    r"^reqspan:(?P<rid>\d+):(?P<engine>.*):lane(?P<lane>[^:]*):"
    r"b(?P<bucket>[^:]*):"
    r"q=(?P<q>[0-9.]+),p=(?P<p>[0-9.]+),d=(?P<d>[0-9.]+),"
    r"r=(?P<r>[0-9.]+),e=(?P<e>[0-9.]+)$")

_GENSPAN = re.compile(
    r"^reqspan:(?P<rid>\d+):(?P<engine>.*):slot(?P<slot>[^:]*):"
    r"n=(?P<n>\d+):"
    r"ttft=(?P<ttft>[0-9.]+),tpot=(?P<tpot>[0-9.]+),e=(?P<e>[0-9.]+)"
    r"(?:,pfx=(?P<pfx>\d+))?(?:,acc=(?P<acc>\d+))?"
    r"(?:,inc=(?P<inc>\d+))?(?:,tid=(?P<tid>[0-9a-f]+))?$")

PHASES = (("queue", "q"), ("pad", "p"), ("device", "d"), ("resolve", "r"))
GEN_PHASES = (("ttft", "ttft"), ("tpot", "tpot"))


def _load_events(path):
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return data.get("traceEvents", data if isinstance(data, list) else [])


def parse_trace(path, events=None):
    """[{rid, engine, lane, bucket, q, p, d, r, e, ts_us}] from the
    trace's reqspan instants. Pass `events` to reuse an already-loaded
    trace (main() loads the file once for both span shapes)."""
    events = _load_events(path) if events is None else events
    out = []
    for ev in events:
        m = _REQSPAN.match(str(ev.get("name", "")))
        if not m:
            continue
        g = m.groupdict()
        out.append({"rid": int(g["rid"]), "engine": g["engine"],
                    "lane": g["lane"], "bucket": g["bucket"],
                    "q": float(g["q"]), "p": float(g["p"]),
                    "d": float(g["d"]), "r": float(g["r"]),
                    "e": float(g["e"]), "ts_us": ev.get("ts", 0.0)})
    return out


def parse_gen_trace(path, events=None):
    """[{rid, engine, slot, n, pfx, acc, ttft, tpot, e, ts_us}] from
    the trace's generation-engine reqspan instants (`pfx` = prompt
    tokens served from the prefix cache, 0 in traces predating
    ISSUE 12; `acc` = speculative draft tokens accepted, 0 in traces
    predating ISSUE 14 — both fields are optional in the regex, so old
    traces still parse)."""
    events = _load_events(path) if events is None else events
    out = []
    for ev in events:
        m = _GENSPAN.match(str(ev.get("name", "")))
        if not m:
            continue
        g = m.groupdict()
        out.append({"rid": int(g["rid"]), "engine": g["engine"],
                    "slot": g["slot"], "n": int(g["n"]),
                    "pfx": int(g["pfx"] or 0),
                    "acc": int(g["acc"] or 0),
                    "inc": int(g["inc"] or 0),
                    "tid": g["tid"],
                    "ttft": float(g["ttft"]), "tpot": float(g["tpot"]),
                    "e": float(g["e"]), "ts_us": ev.get("ts", 0.0)})
    return out


def group_by_trace(gens):
    """One row per fleet trace id (ISSUE 20): a request replayed after
    a restart (or re-routed across replicas) resolves several reqspans
    under the SAME tid — fold them into one logical request carrying
    every engine/incarnation it touched. Spans without a tid (older
    traces, propagation off) are left out — they already render one
    row each in the per-span sections."""
    by_tid = {}
    for g in gens:
        if g.get("tid"):
            by_tid.setdefault(g["tid"], []).append(g)
    rows = []
    for tid, spans in by_tid.items():
        spans = sorted(spans, key=lambda g: g["ts_us"])
        rows.append({"tid": tid,
                     "spans": len(spans),
                     "rids": [g["rid"] for g in spans],
                     "engines": sorted({g["engine"] for g in spans}),
                     "incarnations": sorted({g["inc"] for g in spans}),
                     "n": spans[-1]["n"],
                     "e": round(max(g["e"] for g in spans), 3),
                     "ttft": spans[0]["ttft"]})
    rows.sort(key=lambda r: -r["e"])
    return rows


def _pctl(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    k = min(len(sorted_vals) - 1,
            max(0, int(round(p / 100.0 * len(sorted_vals))) - 1))
    return sorted_vals[k]


def phase_stats(requests):
    """{phase: {count, mean, p50, p99, max}} plus 'e2e'."""
    out = {}
    for label, key in PHASES + (("e2e", "e"),):
        vals = sorted(req[key] for req in requests)
        n = len(vals)
        out[label] = {
            "count": n,
            "mean": round(sum(vals) / n, 3) if n else 0.0,
            "p50": round(_pctl(vals, 50), 3),
            "p99": round(_pctl(vals, 99), 3),
            "max": round(vals[-1], 3) if n else 0.0,
        }
    return out


def report(requests, top=10):
    stats = phase_stats(requests)
    slowest = sorted(requests, key=lambda r: -r["e"])[:top]
    return {"requests": len(requests), "phases_ms": stats,
            "slowest": slowest}


def gen_phase_stats(gens):
    """{ttft/tpot/e2e: {count, mean, p50, p99, max}} over gen spans
    (tpot percentiles exclude single-token requests — they have no
    decode cadence to measure)."""
    out = {}
    for label, key in GEN_PHASES + (("e2e", "e"),):
        rows = [g for g in gens if not (key == "tpot" and g["n"] <= 1)]
        vals = sorted(g[key] for g in rows)
        n = len(vals)
        out[label] = {
            "count": n,
            "mean": round(sum(vals) / n, 3) if n else 0.0,
            "p50": round(_pctl(vals, 50), 3),
            "p99": round(_pctl(vals, 99), 3),
            "max": round(vals[-1], 3) if n else 0.0,
        }
    return out


def gen_report(gens, top=10):
    toks = sum(g["n"] for g in gens)
    acc = sum(g["acc"] for g in gens)
    return {"requests": len(gens), "phases_ms": gen_phase_stats(gens),
            "tokens": toks,
            "prefix_hit_requests": sum(1 for g in gens if g["pfx"] > 0),
            "prefix_hit_tokens": sum(g["pfx"] for g in gens),
            # speculative decoding (ISSUE 14): accepted draft tokens
            # arrived without their own decode step — the tokens-per-
            # step summary is total tokens over the steps actually paid
            "spec_accepted_requests": sum(1 for g in gens
                                          if g["acc"] > 0),
            "spec_accepted_tokens": acc,
            "tokens_per_step": round(toks / (toks - acc), 3)
            if toks > acc else (1.0 if toks else 0.0),
            # engine resurrection (ISSUE 15): requests resolved by a
            # restarted incarnation (inc > 0) — the replayed/late share
            "incarnations": sorted({g["inc"] for g in gens}),
            "post_restart_requests": sum(1 for g in gens
                                         if g["inc"] > 0),
            # fleet trace grouping (ISSUE 20): one logical-request row
            # per trace id, across incarnations and replicas
            "by_trace": group_by_trace(gens)[:top],
            "traced_requests": sum(1 for g in gens if g.get("tid")),
            "slowest": sorted(gens, key=lambda g: -g["e"])[:top]}


def render_gen(rep, file=sys.stdout):
    print(f"{rep['requests']} generation span(s), "
          f"{rep['tokens']} tokens "
          f"({rep['prefix_hit_requests']} prefix-cache hit(s), "
          f"{rep['prefix_hit_tokens']} prompt tokens served from cache)",
          file=file)
    print(f"speculative decoding: {rep['spec_accepted_tokens']} draft "
          f"tokens accepted across {rep['spec_accepted_requests']} "
          f"request(s) — {rep['tokens_per_step']} tokens/step",
          file=file)
    if rep.get("post_restart_requests"):
        print(f"engine resurrection: {rep['post_restart_requests']} "
              f"request(s) resolved after a supervised restart "
              f"(incarnations {rep['incarnations']})", file=file)
    print(f"\n{'phase':<10}{'p50(ms)':>10}{'p99(ms)':>10}"
          f"{'mean':>10}{'max':>10}", file=file)
    for label, _ in GEN_PHASES + (("e2e", "e"),):
        s = rep["phases_ms"][label]
        print(f"{label:<10}{s['p50']:>10.3f}{s['p99']:>10.3f}"
              f"{s['mean']:>10.3f}{s['max']:>10.3f}", file=file)
    if rep["slowest"]:
        print(f"\ntop {len(rep['slowest'])} slowest:", file=file)
        print(f"{'rid':>8} {'engine':<16}{'slot':>5}{'toks':>6}"
              f"{'pfx':>5}{'acc':>5}{'e2e(ms)':>10}{'ttft':>9}"
              f"{'tpot':>9}", file=file)
        for g in rep["slowest"]:
            print(f"{g['rid']:>8} {g['engine']:<16}{g['slot']:>5}"
                  f"{g['n']:>6}{g['pfx']:>5}{g['acc']:>5}"
                  f"{g['e']:>10.3f}"
                  f"{g['ttft']:>9.3f}{g['tpot']:>9.3f}", file=file)
    if rep.get("by_trace"):
        print(f"\nby trace id ({rep['traced_requests']} traced "
              f"span(s), one row per request across "
              f"incarnations/replicas):", file=file)
        print(f"{'trace':<18}{'spans':>6}{'toks':>6}{'e2e(ms)':>10}"
              f"{'ttft':>9}  engines (incarnations)", file=file)
        for r in rep["by_trace"]:
            engines = ",".join(r["engines"])
            incs = ",".join(str(i) for i in r["incarnations"])
            print(f"{r['tid']:<18}{r['spans']:>6}{r['n']:>6}"
                  f"{r['e']:>10.3f}{r['ttft']:>9.3f}  "
                  f"{engines} ({incs})", file=file)


def render(rep, file=sys.stdout):
    print(f"{rep['requests']} request span(s)", file=file)
    print(f"\n{'phase':<10}{'p50(ms)':>10}{'p99(ms)':>10}"
          f"{'mean':>10}{'max':>10}", file=file)
    for label, _ in PHASES + (("e2e", "e"),):
        s = rep["phases_ms"][label]
        print(f"{label:<10}{s['p50']:>10.3f}{s['p99']:>10.3f}"
              f"{s['mean']:>10.3f}{s['max']:>10.3f}", file=file)
    if rep["slowest"]:
        print(f"\ntop {len(rep['slowest'])} slowest:", file=file)
        print(f"{'rid':>8} {'engine':<16}{'lane':>5}{'bkt':>5}"
              f"{'e2e(ms)':>10}{'queue':>9}{'pad':>9}{'device':>9}"
              f"{'resolve':>9}", file=file)
        for r in rep["slowest"]:
            print(f"{r['rid']:>8} {r['engine']:<16}{r['lane']:>5}"
                  f"{r['bucket']:>5}{r['e']:>10.3f}{r['q']:>9.3f}"
                  f"{r['p']:>9.3f}{r['d']:>9.3f}{r['r']:>9.3f}",
                  file=file)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="chrome trace json "
                    "(export_chrome_tracing / curl /trace / bench --trace)")
    ap.add_argument("--top", type=int, default=10,
                    help="how many slowest requests to list")
    ap.add_argument("--engine", default=None,
                    help="only requests of this engine name")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of a table")
    args = ap.parse_args(argv)
    events = _load_events(args.trace)
    requests = parse_trace(args.trace, events=events)
    gens = parse_gen_trace(args.trace, events=events)
    if args.engine is not None:
        requests = [r for r in requests if r["engine"] == args.engine]
        gens = [g for g in gens if g["engine"] == args.engine]
    if not requests and not gens:
        print("no reqspan events found — was the trace exported from a "
              "process serving with FLAGS_serving_spans on?",
              file=sys.stderr)
        return 1
    out = {}
    if requests:
        out["serving"] = report(requests, top=args.top)
    if gens:
        out["generation"] = gen_report(gens, top=args.top)
    if args.json:
        # serving-only traces keep the original FLAT schema (pre-existing
        # consumers read report['phases_ms'] directly); the sectioned
        # wrapper only appears once generation spans exist in the trace
        payload = out["serving"] if not gens else out
        json.dump(payload, sys.stdout, indent=2)
        print()
    else:
        if requests:
            render(out["serving"])
        if requests and gens:
            print()
        if gens:
            render_gen(out["generation"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
