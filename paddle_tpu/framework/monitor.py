"""Runtime STAT counters (reference `paddle/fluid/platform/monitor.h:44`
StatRegistry/StatValue + the STAT_ADD/STAT_SUB/STAT_RESET macros in
`monitor.h:131`).

Same contract, Python-native: named monotonic/resettable int counters,
thread-safe, globally registered, dumped as one dict for metrics export.
Hot-path framework code (dataloader batches, flash-kernel dispatches,
executor runs) bumps these; they cost one dict lookup + int add.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict

__all__ = ["StatValue", "stat_add", "stat_sub", "stat_reset", "stat_get",
           "stat_set", "stat_gauge_add", "all_stats", "stat_time",
           "STAT_ADD", "STAT_SUB",
           "STAT_RESET", "StatHistogram", "histogram", "all_histograms",
           "registered_histograms", "reset_all_stats", "drain_deltas",
           "merge_deltas", "register_gauge", "gauge_kind", "is_gauge_name"]


# -- gauge-name registry ----------------------------------------------------
#
# The ONE place a stat name's gauge-ness is recorded (ISSUE 11 satellite:
# the exporter's suffix list and the relay's per-instance flag used to
# drift independently). Two kinds:
#
#   "level"  — an absolute level (live HBM bytes, MFU, pages in use):
#              rendered as a Prometheus gauge AND skipped by the
#              cross-process delta relay (summing levels across processes
#              corrupts both sides). `stat_set`/`stat_gauge_add` mark
#              their name "level" automatically.
#   "updown" — a counter that legitimately moves both ways (queue
#              depths): rendered as a Prometheus gauge but RELAYED —
#              stat_add/stat_sub deltas sum correctly across processes.
#              Registered explicitly by the owning module.
#
# The Prometheus exporter classifies via `gauge_kind(name)`; the relay
# skips exactly the "level" kind. A name in neither bucket is a plain
# monotone counter.

_gauge_kinds: Dict[str, str] = {}


def register_gauge(name: str, updown: bool = False) -> None:
    """Declare `name` a gauge for the Prometheus exporter. updown=True
    keeps it in the cross-process relay (bidirectional counter);
    updown=False (a pure level) also excludes it from the relay — though
    level gauges normally self-register through stat_set/gauge_add."""
    _gauge_kinds[name] = "updown" if updown else "level"


def _note_level_gauge(name: str) -> None:
    # stat_set/gauge_add call sites are by definition levels; an updown
    # registration wins (it was an explicit owner decision)
    if _gauge_kinds.get(name) != "updown":
        _gauge_kinds[name] = "level"


def gauge_kind(name: str):
    """"level" / "updown" / None for `name` — the single source of truth
    the exporter and the relay both read."""
    k = _gauge_kinds.get(name)
    if k is not None:
        return k
    s = _registry._stats.get(name)
    if s is not None and s.gauge:
        return "level"
    return None


def is_gauge_name(name: str) -> bool:
    """Should `name` render as a Prometheus gauge?"""
    return gauge_kind(name) is not None


class StatValue:
    """One named counter (reference monitor.h:44)."""

    __slots__ = ("name", "_v", "_lock", "gauge")

    def __init__(self, name: str):
        self.name = name
        self._v = 0
        self._lock = threading.Lock()
        self.gauge = False  # set() flips it: a level, not a running total

    def increase(self, n: int = 1) -> int:
        with self._lock:
            self._v += n
            return self._v

    def decrease(self, n: int = 1) -> int:
        return self.increase(-n)

    def reset(self) -> int:
        with self._lock:
            self._v = 0
            return 0

    def set(self, v: int) -> int:
        """Overwrite with an absolute level — gauge semantics (device
        telemetry: live HBM bytes, MFU) as opposed to the counters'
        monotone increase. Marks the stat as a gauge, which excludes it
        from the cross-process delta relay (summing levels across
        processes is meaningless)."""
        with self._lock:
            self._v = int(v)
            self.gauge = True
        _note_level_gauge(self.name)
        return self._v

    def gauge_add(self, n: int) -> int:
        """Atomically move a gauge LEVEL by a delta (resource-residency
        gauges: a predictor replica adds its quantized-weight bytes on
        load and subtracts them on collection). Gauge-marked like set(),
        so the relay never sums it across processes."""
        with self._lock:
            self._v += int(n)
            self.gauge = True
            v = self._v
        _note_level_gauge(self.name)
        return v

    def drain(self) -> int:
        """Atomically read-and-zero (the cross-process delta relay: a
        DataLoader worker ships everything accumulated since its last
        ship, exactly once)."""
        with self._lock:
            v = self._v
            self._v = 0
            return v

    def get(self) -> int:
        return self._v


class StatHistogram:
    """Streaming latency histogram: fixed log-spaced buckets, O(1) observe,
    approximate percentiles (error bounded by the ~7% bucket width).

    The serving engine records per-request latency here (p50/p99 without
    retaining per-request state — the same reason the reference exports
    bucketed latency metrics rather than raw samples)."""

    # 10% geometric spacing from 1us to ~1000s expressed in the caller's
    # unit (buckets are unit-agnostic ratios; callers pick ms or ns)
    _BASE = 1.10
    _MIN = 1e-3
    _NBUCKETS = 240

    __slots__ = ("name", "_counts", "_count", "_sum", "_min", "_max",
                 "_lock")

    def __init__(self, name: str):
        self.name = name
        self._counts = [0] * (self._NBUCKETS + 2)  # +underflow +overflow
        self._count = 0
        self._sum = 0.0
        self._min = float("inf")
        self._max = float("-inf")
        self._lock = threading.Lock()

    def _bucket(self, v: float) -> int:
        if v < self._MIN:
            return 0
        import math
        i = int(math.log(v / self._MIN) / math.log(self._BASE)) + 1
        return min(i, self._NBUCKETS + 1)

    def observe(self, value: float) -> None:
        i = self._bucket(value)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += value
            self._min = min(self._min, value)
            self._max = max(self._max, value)

    def _percentile_locked(self, p: float) -> float:
        if self._count == 0:
            return 0.0
        rank = max(1, int(round(p / 100.0 * self._count)))
        seen = 0
        for i, c in enumerate(self._counts):
            seen += c
            if seen >= rank:
                if i == 0:
                    return min(self._MIN, self._max)
                # geometric midpoint of the bucket, clamped to
                # observed extremes so p0/p100 stay honest
                lo = self._MIN * (self._BASE ** (i - 1))
                mid = lo * (self._BASE ** 0.5)
                return max(self._min, min(mid, self._max))
        return self._max

    def percentile(self, p: float) -> float:
        """Approximate p-th percentile (p in [0, 100])."""
        with self._lock:
            return self._percentile_locked(p)

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def buckets(self):
        """Cumulative histogram as `[(upper_bound, cumulative_count)]`,
        ending with `(inf, count)` — exactly the shape a Prometheus
        `_bucket{le=...}` series wants (log-spaced bounds map one-to-one
        onto `le` labels; see profiler/exporter.py)."""
        with self._lock:
            counts = list(self._counts)
        out = []
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            le = (self._MIN * self._BASE ** i if i <= self._NBUCKETS
                  else float("inf"))
            out.append((le, cum))
        return out

    def drain_raw(self):
        """Atomically snapshot-and-reset the raw state as a compact
        picklable blob `(sparse_counts, count, sum, min, max)` — the
        DataLoader worker side of the cross-process relay. Sparse: most
        of the 242 log buckets are empty for any one shipping window."""
        with self._lock:
            if self._count == 0:
                return None
            blob = ({i: c for i, c in enumerate(self._counts) if c},
                    self._count, self._sum, self._min, self._max)
            self._counts = [0] * (self._NBUCKETS + 2)
            self._count = 0
            self._sum = 0.0
            self._min = float("inf")
            self._max = float("-inf")
            return blob

    def merge_raw(self, sparse_counts, count, total, mn, mx) -> None:
        """Fold another histogram's raw state into this one (the parent
        side of the relay). Buckets are fixed and identical in every
        process, so the merge is exact — not a re-observation through
        snapshots, which would quantize twice."""
        with self._lock:
            for i, c in sparse_counts.items():
                self._counts[int(i)] += int(c)
            self._count += int(count)
            self._sum += float(total)
            self._min = min(self._min, float(mn))
            self._max = max(self._max, float(mx))

    def mean(self) -> float:
        return self._sum / self._count if self._count else 0.0

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (self._NBUCKETS + 2)
            self._count = 0
            self._sum = 0.0
            self._min = float("inf")
            self._max = float("-inf")

    def snapshot(self) -> Dict[str, float]:
        with self._lock:  # one lock: count/mean/percentiles stay coherent
            count = self._count
            return {"count": count,
                    "mean": round(self._sum / count, 4) if count else 0.0,
                    "p50": round(self._percentile_locked(50), 4),
                    "p99": round(self._percentile_locked(99), 4),
                    "max": round(self._max, 4) if count else 0.0}


class _Registry:
    def __init__(self):
        self._stats: Dict[str, StatValue] = {}
        self._hists: Dict[str, StatHistogram] = {}
        self._lock = threading.Lock()

    def get(self, name: str) -> StatValue:
        s = self._stats.get(name)
        if s is None:
            with self._lock:
                s = self._stats.setdefault(name, StatValue(name))
        return s

    def get_hist(self, name: str) -> StatHistogram:
        h = self._hists.get(name)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(name, StatHistogram(name))
        return h

    def snapshot(self) -> Dict[str, int]:
        # one consistent pass: the registry lock freezes the NAME SET so
        # a concurrent get-or-create can't resize the dict mid-iteration
        # (values are single atomic int reads and need no per-stat lock)
        with self._lock:
            items = sorted(self._stats.items())
        return {n: s.get() for n, s in items}

    def snapshot_hists(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            items = sorted(self._hists.items())
        return {n: h.snapshot() for n, h in items}

    def histograms(self) -> Dict[str, StatHistogram]:
        with self._lock:
            return dict(self._hists)

    def reset_all(self) -> None:
        with self._lock:
            stats = list(self._stats.values())
            hists = list(self._hists.values())
        for s in stats:
            s.reset()
        for h in hists:
            h.reset()


_registry = _Registry()


def stat_add(name: str, n: int = 1) -> int:
    return _registry.get(name).increase(n)


def stat_sub(name: str, n: int = 1) -> int:
    return _registry.get(name).decrease(n)


def stat_reset(name: str) -> int:
    return _registry.get(name).reset()


def stat_get(name: str) -> int:
    return _registry.get(name).get()


def stat_set(name: str, v: int) -> int:
    """Set an absolute gauge level (device telemetry samplers)."""
    return _registry.get(name).set(v)


def stat_gauge_add(name: str, n: int) -> int:
    """Atomically add a (possibly negative) delta to a gauge level —
    for residency gauges whose owners add on construction and subtract
    on teardown (quantized weights, KV pools)."""
    return _registry.get(name).gauge_add(n)


def drain_deltas():
    """Atomically drain every counter and histogram into one picklable
    delta blob (None when nothing was touched). The multiprocess
    DataLoader worker calls this per shipped batch so ANY stat bumped in
    the worker process — packing counters, user collate_fn counters,
    histograms — reaches the trainer's registry instead of dying with
    the fork's private copy. "level" gauges (anything touched via
    `stat_set`) stay process-local and are neither drained nor merged —
    summing a worker's level into the parent would corrupt both sides.
    The gauge registry is authoritative: a name registered "updown"
    relays as deltas even if some code path also flipped the
    per-instance gauge flag on it."""
    with _registry._lock:
        stats = list(_registry._stats.values())
        hists = list(_registry._hists.items())
    out_s = {}
    for s in stats:
        kind = _gauge_kinds.get(s.name)
        if kind == "level" or (kind is None and s.gauge):
            continue
        v = s.drain()
        if v:
            out_s[s.name] = v
    out_h = {}
    for n, h in hists:
        blob = h.drain_raw()
        if blob is not None:
            out_h[n] = blob
    if not out_s and not out_h:
        return None
    return {"stats": out_s, "hists": out_h}


def merge_deltas(delta) -> None:
    """Fold a `drain_deltas()` blob from another process into this
    registry (additive for counters, exact bucket-merge for
    histograms)."""
    if not delta:
        return
    for n, v in delta.get("stats", {}).items():
        _registry.get(n).increase(v)
    for n, blob in delta.get("hists", {}).items():
        _registry.get_hist(n).merge_raw(*blob)


def all_stats() -> Dict[str, int]:
    """Snapshot of every registered counter (reference
    StatRegistry::publish)."""
    return _registry.snapshot()


def reset_all_stats() -> None:
    """Zero every registered counter AND histogram. STAT counters are
    process-global (the serving-engine docstring's contract), so a bench
    or test that measures deltas from a warm process must reset first or
    it inherits counts from whatever ran before."""
    _registry.reset_all()


def histogram(name: str) -> StatHistogram:
    """Globally registered streaming histogram (get-or-create)."""
    return _registry.get_hist(name)


def all_histograms() -> Dict[str, Dict[str, float]]:
    """Snapshot {name: {count, mean, p50, p99, max}} of every histogram."""
    return _registry.snapshot_hists()


def registered_histograms() -> Dict[str, StatHistogram]:
    """The live histogram objects (the Prometheus exporter renders
    `buckets()`/`sum`/`count` directly rather than via snapshots)."""
    return _registry.histograms()


@contextlib.contextmanager
def stat_time(name: str):
    """Accumulate the wall time (ns) of the enclosed block into `name`.

    Note that with async dispatch this measures Python dispatch
    latency, not device compute; pair with an explicit sync when device
    time is wanted.
    """
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        stat_add(name, time.perf_counter_ns() - t0)


# macro-style aliases matching the reference spelling
STAT_ADD = stat_add
STAT_SUB = stat_sub
STAT_RESET = stat_reset
