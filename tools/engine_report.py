#!/usr/bin/env python
"""Render a generation engine's scheduler X-ray as a human timeline.

    curl -s localhost:9100/steps > steps.json
    python tools/engine_report.py steps.json
    python tools/engine_report.py steps.json --engine gen0 --last 40
    python tools/engine_report.py flightrec-...-gen_engine_death.json

Input is either a `/steps` payload (profiler/step_log.steps_payload:
per-engine iteration records + decision-audit tail) or a flight-recorder
dump whose `extra` carries `step_log_tail`/`audit_tail` (engine death,
poison, allocator exhaustion). The report shows, per iteration: decode
slots in use (as a bar), scheduler decisions (admit/complete/expire/
poison/abort), queue depth + oldest-request age, page-pool occupancy,
prefix-cache hit tokens + copy-on-write splits (pfx/cow), host-tier
page traffic (dem/pro — ISSUE 18: pages demoted to host RAM vs pages
promoted back to HBM this iteration), tokens
delivered + speculative drafts accepted + prefill chunks run
(tok/acc/chk — ISSUE 14: tok > slots on a decode iteration is
speculation paying off, chk interleaved with decode wall is chunked
prefill protecting TPOT), the engine generation (`inc` — a supervised
restart bumps the incarnation counter, ISSUE 15, so a ring spanning a
death + resurrection reads as two generations with the
ENGINE_RESTART/REPLAY_ADMIT audit events between them), the engine's
mesh-slice width (`tp` — ISSUE 19: a tensor-parallel lane records its
degree every iteration so mixed-fleet rings are self-describing;
records predating the field read as single-chip), and
prefill-vs-decode wall, and the per-iteration goodput attribution
(ISSUE 20: idle/wall columns plus a per-incarnation "where did the
milliseconds go" rollup — admit / prefill / promote / decode /
bookkeep / idle tile each iteration's wall exactly), and the engine's
own timeline of the device (dev/dev_idle columns: device time of the
programs the iteration read, and device idle that closed at one of its
launches; a per-incarnation "device idle by span" rollup of
`dev_idle_by`, which says what the step thread was doing while the chip
waited) — then the audit
tail with reason codes (per request: ADMIT_PREFIX_HIT carries
prefix_tokens, COW_SPLIT the split pages), so "why did this request
wait/die" reads straight off the artifact. Records predating
ISSUE 14/15/20 parse unchanged: every field reads by name with a zero
default.

`--json` emits the parsed + summarized structure for scripting.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional


def load_payload(path: str) -> dict:
    """Normalize either input shape to {engine: {"records", "audit"}}."""
    with open(path) as f:
        raw = json.load(f)
    if "engines" in raw:  # /steps payload
        return {name: {"records": e.get("records", []),
                       "audit": e.get("audit", []),
                       "recorded_total": e.get("recorded_total"),
                       "ring_capacity": e.get("ring_capacity")}
                for name, e in raw["engines"].items()}
    extra = raw.get("extra", {})
    if "step_log_tail" in extra or "audit_tail" in extra:
        name = extra.get("engine", raw.get("reason", "engine"))
        return {name: {"records": extra.get("step_log_tail", []),
                       "audit": extra.get("audit_tail", []),
                       "recorded_total": None, "ring_capacity": None,
                       "dump_reason": raw.get("reason")}}
    raise SystemExit(
        f"{path}: neither a /steps payload (no 'engines' key) nor a "
        f"flight-recorder dump with step_log_tail/audit_tail")


def summarize(records: List[dict]) -> dict:
    """Aggregate decision totals + peaks over the retained window."""
    if not records:
        return {"iterations": 0}
    tot = {k: sum(r.get(k, 0) for r in records)
           for k in ("admitted", "completed", "expired", "poisoned",
                     "aborted", "freed", "prefix_tokens", "cow_splits",
                     "tokens", "spec_drafted", "spec_accepted",
                     "prefill_chunks", "tier_demotions",
                     "tier_promotions")}
    decode_steps = sum(1 for r in records if r.get("decode_ms", 0) > 0)
    # engine generations in the window (ISSUE 15): a supervised restart
    # bumps `incarnation`, so >1 distinct value means the ring spans an
    # engine death + resurrection (records predating the field read 0)
    incarnations = sorted({r.get("incarnation", 0) for r in records})
    # mesh-slice width (ISSUE 19): constant per incarnation; records
    # predating the field (or seed-era zeros) read as single-chip
    tp = max((r.get("tp", 0) for r in records), default=0) or 1
    return {
        "iterations": len(records),
        "decode_steps": decode_steps,
        "incarnations": incarnations,
        "restarts_in_window": max(0, len(incarnations) - 1),
        "tp": tp,
        **tot,
        # tokens delivered per decode step over the window. NOTE: the
        # numerator includes prefill FIRST tokens (the ring does not
        # record prefill completions separately), so short-request
        # traffic reads slightly above 1.0 even with speculation off —
        # spec_accepted_per_step below is the exact speculation signal
        # (accepted drafts are the only way a decode step delivers
        # more than one token per live slot)
        "tokens_per_step": round(tot["tokens"] / decode_steps, 3)
        if decode_steps else 0.0,
        "spec_accepted_per_step": round(
            tot["spec_accepted"] / decode_steps, 3)
        if decode_steps else 0.0,
        "peak_live": max(r.get("live", 0) for r in records),
        "peak_queue_depth": max(r.get("queue_depth", 0)
                                for r in records),
        "peak_oldest_age_ms": round(max(r.get("oldest_age_ms", 0.0)
                                        for r in records), 3),
        "peak_pages_in_use": max(r.get("pages_in_use", 0)
                                 for r in records),
        "min_free_pages": min(r.get("free_pages", 0) for r in records),
        "prefill_ms_total": round(sum(r.get("prefill_ms", 0.0)
                                      for r in records), 3),
        "decode_ms_total": round(sum(r.get("decode_ms", 0.0)
                                     for r in records), 3),
        "goodput": goodput(records),
        "device": device_idle(records),
    }


def device_idle(records: List[dict]) -> dict:
    """Per-incarnation device rollup from the engine's own timeline of the
    device: device time of the programs read, device idle, and that idle
    by the step thread's scope at the time (dev_idle_by). {} when no record
    carries the timeline (older records, or FLAGS_gen_step_log off)."""
    by_inc: dict = {}
    for r in records:
        if "dev_idle_ms" not in r:
            continue
        d = by_inc.setdefault(r.get("incarnation", 0),
                              {"dev_ms": 0.0, "idle_ms": 0.0, "wall_ms": 0.0,
                               "idle_by": {}})
        d["dev_ms"] += r.get("decode_dev_ms", 0.0) + r.get("prefill_dev_ms",
                                                           0.0)
        d["idle_ms"] += r["dev_idle_ms"]
        d["wall_ms"] += r.get("attr_wall_ms", 0.0) or 0.0
        for span, ms in (r.get("dev_idle_by") or {}).items():
            d["idle_by"][span] = d["idle_by"].get(span, 0.0) + ms
    for d in by_inc.values():
        for k in ("dev_ms", "idle_ms", "wall_ms"):
            d[k] = round(d[k], 3)
        d["idle_by"] = {k: round(v, 3) for k, v in sorted(
            d["idle_by"].items(), key=lambda kv: -kv[1])}
    return by_inc


# goodput-attribution buckets (ISSUE 20): label -> StepRecord field.
# The six tile each iteration's attr_wall_ms exactly (bookkeeping is
# the remainder of the rounded siblings, computed engine-side).
ATTR_BUCKETS = (("admit", "attr_admit_ms"), ("prefill", "prefill_ms"),
                ("promote", "attr_promote_ms"), ("decode", "decode_ms"),
                ("bookkeep", "attr_bookkeep_ms"),
                ("idle", "attr_idle_ms"))


def goodput(records: List[dict]) -> dict:
    """Per-incarnation 'where did the milliseconds go' rollup over the
    records carrying attribution (attr_wall_ms > 0; older-era records
    simply don't contribute). {} when no record has attribution."""
    by_inc: dict = {}
    for r in records:
        wall = r.get("attr_wall_ms", 0) or 0
        if wall <= 0:
            continue
        d = by_inc.setdefault(r.get("incarnation", 0),
                              {label: 0.0 for label, _ in ATTR_BUCKETS})
        d["wall_ms"] = d.get("wall_ms", 0.0) + wall
        for label, key in ATTR_BUCKETS:
            d[label] += r.get(key, 0.0) or 0.0
    for d in by_inc.values():
        for k in list(d):
            d[k] = round(d[k], 3)
    return {"by_incarnation": by_inc,
            "wall_ms": round(sum(d.get("wall_ms", 0.0)
                                 for d in by_inc.values()), 3)}\
        if by_inc else {}


def _bar(n: int, peak: int, width: int = 8) -> str:
    peak = max(peak, 1)
    fill = round(width * min(n, peak) / peak)
    return "#" * fill + "." * (width - fill)


def render(name: str, eng: dict, last: int = 0,
           file=None) -> None:
    out = file or sys.stdout
    records = eng["records"]
    if last > 0:
        records = records[-last:]
    summ = summarize(records)
    print(f"== engine {name} ==", file=out)
    if eng.get("dump_reason"):
        print(f"   (from flight dump: {eng['dump_reason']})", file=out)
    if not records:
        print("   no step records (FLAGS_gen_step_log off, or the "
              "engine never iterated)", file=out)
    else:
        peak_live = summ["peak_live"]
        lane = (f", tp={summ['tp']} mesh-slice lane"
                if summ.get("tp", 1) > 1 else "")
        print(f"   {summ['iterations']} iterations retained "
              f"({summ['decode_steps']} decode steps{lane}): "
              f"admitted {summ['admitted']}, completed "
              f"{summ['completed']}, expired {summ['expired']}, "
              f"poisoned {summ['poisoned']}, aborted "
              f"{summ['aborted']}", file=out)
        print(f"   peak live {peak_live}, peak queue "
              f"{summ['peak_queue_depth']} (oldest "
              f"{summ['peak_oldest_age_ms']}ms), peak pages "
              f"{summ['peak_pages_in_use']}, min free pages "
              f"{summ['min_free_pages']}", file=out)
        if summ.get("restarts_in_window"):
            print(f"   {summ['restarts_in_window']} engine "
                  f"restart(s) in window — incarnations "
                  f"{summ['incarnations']} (see ENGINE_RESTART / "
                  f"REPLAY_ADMIT audit events)", file=out)
        if summ.get("prefix_tokens") or summ.get("cow_splits"):
            print(f"   prefix cache: {summ['prefix_tokens']} prompt "
                  f"tokens served from cached pages, "
                  f"{summ['cow_splits']} copy-on-write splits", file=out)
        # cross-tier traffic (ISSUE 18): pages the prefix cache demoted
        # to host RAM vs pages promoted back to HBM in the window
        if summ.get("tier_demotions") or summ.get("tier_promotions"):
            print(f"   kv tier: {summ['tier_demotions']} pages demoted "
                  f"to host, {summ['tier_promotions']} promoted back",
                  file=out)
        # the speculative economics in one line: tokens delivered per
        # decode step (incl. prefill first tokens), the exact accepted-
        # drafts-per-step signal, the draft acceptance split, and any
        # prefill chunks run (ISSUE 14)
        print(f"   {summ['tokens']} tokens / {summ['decode_steps']} "
              f"decode steps = {summ['tokens_per_step']} tokens/step "
              f"(+{summ['spec_accepted_per_step']}/step from spec: "
              f"{summ['spec_accepted']}/{summ['spec_drafted']} drafts "
              f"accepted, {summ['prefill_chunks']} prefill chunks)",
              file=out)
        # goodput attribution (ISSUE 20): where did this replica's
        # milliseconds go, per incarnation — buckets tile the wall
        gp = summ.get("goodput") or {}
        for inc in sorted(gp.get("by_incarnation", {})):
            d = gp["by_incarnation"][inc]
            wall = max(d.get("wall_ms", 0.0), 1e-9)
            pct = " ".join(
                f"{label} {100.0 * d.get(label, 0.0) / wall:.1f}%"
                for label, _ in ATTR_BUCKETS)
            print(f"   goodput inc {inc}: wall "
                  f"{d.get('wall_ms', 0.0):.1f}ms — {pct}", file=out)
        # the device's own timeline: how long the chip sat idle, and what
        # the step thread was doing meanwhile
        for inc, d in sorted(summ.get("device", {}).items()):
            wall = max(d["wall_ms"], 1e-9)
            spans = ", ".join(
                f"{span} {100.0 * ms / max(d['idle_ms'], 1e-9):.1f}%"
                for span, ms in d["idle_by"].items()) or "-"
            print(f"   device inc {inc}: busy {d['dev_ms']:.1f}ms, idle "
                  f"{d['idle_ms']:.1f}ms ({100.0 * d['idle_ms'] / wall:.1f}%"
                  f" of wall) — device idle by span: {spans}", file=out)
        hdr = (f"   {'inc':>3} {'tp':>2} {'it':>6} {'step':>6} "
               f"{'slots':<10} "
               f"{'adm':>3} "
               f"{'done':>4} {'exp':>3} {'psn':>3} {'abt':>3} "
               f"{'queue':>5} {'age_ms':>8} {'pages':>5} {'free':>5} "
               f"{'pfx':>4} {'cow':>3} {'dem':>3} {'pro':>3} "
               f"{'tok':>4} {'acc':>4} "
               f"{'chk':>3} {'prefill':>8} {'decode':>8} "
               f"{'idle':>8} {'wall':>8} {'dev':>8} {'dev_idle':>8}")
        print(hdr, file=out)
        for r in records:
            dev = r.get("decode_dev_ms", 0.0) + r.get("prefill_dev_ms", 0.0)
            print(f"   {r.get('incarnation', 0):>3} "
                  f"{r.get('tp', 0) or 1:>2} "
                  f"{r.get('it', 0):>6} {r.get('step', 0):>6} "
                  f"[{_bar(r.get('live', 0), peak_live)}] "
                  f"{r.get('admitted', 0):>3} "
                  f"{r.get('completed', 0):>4} "
                  f"{r.get('expired', 0):>3} "
                  f"{r.get('poisoned', 0):>3} "
                  f"{r.get('aborted', 0):>3} "
                  f"{r.get('queue_depth', 0):>5} "
                  f"{r.get('oldest_age_ms', 0.0):>8.1f} "
                  f"{r.get('pages_in_use', 0):>5} "
                  f"{r.get('free_pages', 0):>5} "
                  f"{r.get('prefix_tokens', 0):>4} "
                  f"{r.get('cow_splits', 0):>3} "
                  f"{r.get('tier_demotions', 0):>3} "
                  f"{r.get('tier_promotions', 0):>3} "
                  f"{r.get('tokens', 0):>4} "
                  f"{r.get('spec_accepted', 0):>4} "
                  f"{r.get('prefill_chunks', 0):>3} "
                  f"{r.get('prefill_ms', 0.0):>7.1f}ms "
                  f"{r.get('decode_ms', 0.0):>7.1f}ms "
                  f"{r.get('attr_idle_ms', 0.0) or 0.0:>7.1f}ms "
                  f"{r.get('attr_wall_ms', 0.0) or 0.0:>7.1f}ms "
                  f"{dev:>7.1f}ms "
                  f"{r.get('dev_idle_ms', 0.0):>7.1f}ms",
                  file=out)
    audit = eng.get("audit", [])
    if last > 0:
        audit = audit[-last:]
    print(f"   -- decision audit ({len(audit)} events) --", file=out)
    for ev in audit:
        extra = {k: v for k, v in ev.items()
                 if k not in ("t", "engine", "reason", "rid")}
        detail = (" " + " ".join(f"{k}={v}" for k, v in
                                 sorted(extra.items()))) if extra else ""
        rid = ev.get("rid")
        print(f"   t={ev.get('t', 0):.3f} "
              f"{ev.get('reason', '?'):<18} "
              f"rid={rid if rid is not None else '-':<6}{detail}",
              file=out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="engine_report.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("path", help="/steps payload or flight-recorder dump")
    p.add_argument("--engine", default=None,
                   help="only this engine (default: all)")
    p.add_argument("--last", type=int, default=0,
                   help="only the last N records/events (default: all)")
    p.add_argument("--json", action="store_true",
                   help="emit parsed records + summary as JSON")
    args = p.parse_args(argv)

    engines = load_payload(args.path)
    if args.engine is not None:
        if args.engine not in engines:
            print(f"engine {args.engine!r} not in {sorted(engines)}",
                  file=sys.stderr)
            return 1
        engines = {args.engine: engines[args.engine]}
    if not engines:
        print("no engines in payload", file=sys.stderr)
        return 1

    if args.json:
        out = {}
        for name, eng in engines.items():
            recs = eng["records"][-args.last:] if args.last > 0 \
                else eng["records"]
            audit = eng["audit"][-args.last:] if args.last > 0 \
                else eng["audit"]
            out[name] = {"summary": summarize(recs), "records": recs,
                         "audit": audit}
        print(json.dumps(out, indent=2))
        return 0

    for name, eng in sorted(engines.items()):
        render(name, eng, last=args.last)
    return 0


if __name__ == "__main__":
    sys.exit(main())
