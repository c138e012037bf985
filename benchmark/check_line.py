"""The two checks a run makes on itself: that BENCHMARK.json is well formed,
and that the line about to be printed is the object the driver reads.

PR 22 was refused for a traced line of its four-chip cell that the driver
could not read. `run.py` calls `check_line` on every last line before it
prints it, and exits non-zero on a raise, so that fault now shows in the
builder's own first run of a cell.
"""
import math
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"correct", "attempted", "failed", "metrics", "device",
            "breakdown"}


class BadLine(ValueError):
    pass


class BadManifest(ValueError):
    pass


def metrics_of(manifest, cell, group):
    """The metrics of `group` (end_to_end or per_layer) that `cell`
    reports: those with no `workloads` key, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def cell_of(manifest, name):
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise BadManifest(f"no workload {name!r} in BENCHMARK.json")


def check_line(manifest, cell, trace, obj):
    """Raise BadLine unless `obj` is what the driver reads for `cell`."""
    w = cell_of(manifest, cell)
    missing = (TOP_KEYS - {"breakdown"}) - set(obj)
    extra = set(obj) - TOP_KEYS
    if missing or extra:
        raise BadLine(f"top-level keys: missing {sorted(missing)}, "
                      f"unknown {sorted(extra)}")
    if not isinstance(obj["correct"], bool):
        raise BadLine("correct is not a boolean")
    for k in ("attempted", "failed"):
        if not isinstance(obj[k], int) or isinstance(obj[k], bool) \
                or obj[k] < 0:
            raise BadLine(f"{k} is not a count: {obj[k]!r}")
    if obj["failed"] > obj["attempted"]:
        raise BadLine("failed > attempted")
    group = "per_layer" if trace else "end_to_end"
    for m in metrics_of(manifest, cell, group):
        got = obj["metrics"].get(m["name"])
        if not isinstance(got, dict):
            raise BadLine(f"metric {m['name']} is missing from the line")
        v = got.get("value")
        if isinstance(v, bool) or not isinstance(v, (int, float)) \
                or not math.isfinite(v):
            raise BadLine(f"metric {m['name']} has no finite value: {v!r}")
        if got.get("unit") != m["unit"]:
            raise BadLine(f"metric {m['name']} has unit {got.get('unit')!r}"
                          f", BENCHMARK.json says {m['unit']!r}")
    known = {m["name"] for g in ("end_to_end", "per_layer")
             for m in manifest[g]}
    for name in obj["metrics"]:
        if name not in known:
            raise BadLine(f"metric {name} is not in BENCHMARK.json")
    d = obj["device"]
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        if k not in d:
            raise BadLine(f"device.{k} is missing")
    if d["count"] != w["chips"]:
        raise BadLine(f"device.count {d['count']} is not the cell's chips "
                      f"{w['chips']}")
    if not isinstance(d["memory_peak_bytes"], int) \
            or d["memory_peak_bytes"] <= 0:
        raise BadLine(f"device.memory_peak_bytes {d['memory_peak_bytes']!r}"
                      f" is not a positive count")
    if trace:
        busy, window = d.get("busy_s"), d.get("window_s")
        for k, v in (("busy_s", busy), ("window_s", window)):
            if isinstance(v, bool) or not isinstance(v, (int, float)) \
                    or not math.isfinite(v):
                raise BadLine(f"device.{k} is not a number: {v!r}")
        if not 0 < busy <= window:
            raise BadLine(f"device.busy_s {busy} is not above 0 and at most "
                          f"window_s {window}")
    if "breakdown" in obj:
        b = obj["breakdown"]
        if set(b) - {"device_ops", "idle_gaps"}:
            raise BadLine("breakdown has keys besides device_ops, idle_gaps")
        for k, rows in b.items():
            if len(rows) > 10 or any(
                    len(r) != 2 or not isinstance(r[0], str)
                    or not isinstance(r[1], (int, float)) for r in rows):
                raise BadLine(f"breakdown.{k} is not at most 10 "
                              f"[name, seconds] pairs")


def check_manifest(manifest, root):
    """Raise BadManifest unless BENCHMARK.json keeps the rules a later PR
    is most likely to break by adding an entry. `root` is the checkout."""
    def need(ok, what):
        if not ok:
            raise BadManifest(what)

    e2e = {m["name"] for m in manifest["end_to_end"]}
    need("setup_s" in e2e, "end_to_end has no setup_s")
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in manifest[g]]
    need(len(names) == len(set(names)), "two metrics share a name")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        need(NAME.match(m["name"]), f"bad metric name {m['name']!r}")
        need(UNIT.match(m["unit"]), f"bad unit {m['unit']!r}")
        need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = [w["name"] for w in manifest["workloads"]]
    need(len(cells) == len(set(cells)), "two workloads share a name")
    four = 0
    for w in manifest["workloads"]:
        for k in ("name", "config", "traffic"):
            need(NAME.match(w[k]), f"bad {k} {w[k]!r}")
        need(w["chips"] in (1, 4), f"{w['name']}: chips is not 1 or 4")
        need(len(w["why"]) <= 200, f"{w['name']}: why is over 200 characters")
        four += w["chips"] == 4
        need(w["config"] in configs, f"{w['name']}: unknown config")
        for path in (configs[w["config"]]["file"],
                     f"benchmark/traffic/{w['traffic']}.json"):
            need(os.path.isfile(os.path.join(root, path)),
                 f"{w['name']}: no file {path}")
        ours = {m["name"] for m in metrics_of(manifest, w["name"],
                                              "end_to_end")}
        need(len(ours) >= 2, f"{w['name']}: reports setup_s alone")
        layer = metrics_of(manifest, w["name"], "per_layer")
        need(layer, f"{w['name']}: reports no per-layer metric")
        for m in layer:
            need(m["moves"] in ours, f"{m['name']} moves {m['moves']}, "
                 f"which {w['name']} does not report")
    need(four <= max(1, len(cells) // 4),
         f"{four} of {len(cells)} cells ask for four chips")
    for m in manifest["per_layer"]:
        need(m["moves"] in e2e, f"{m['name']} moves no end-to-end metric")
        path = f"benchmark/metrics/{m['name']}.py"
        need(os.path.isfile(os.path.join(root, path)), f"no file {path}")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for c in m.get("workloads", []):
            need(c in cells, f"{m['name']} lists unknown workload {c}")
    used = {w["config"] for w in manifest["workloads"]}
    need(used == set(configs), f"configs used by no cell: "
         f"{sorted(set(configs) - used)}")
