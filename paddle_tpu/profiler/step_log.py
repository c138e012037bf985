"""Per-step scheduler timeline for the generation engine ("scheduler
X-ray", ISSUE 11).

PR 7's spans explain ONE request's latency; nothing explained the
*scheduler's* behavior between requests — which iteration admitted or
evicted whom, how deep the queue ran, how close the page pool was to
exhaustion. The step thread records one compact `StepRecord` per engine
iteration into a bounded per-engine ring (`FLAGS_gen_step_log_size`,
oldest overwritten — the same bounding discipline as the trace rings):

    it            iteration ordinal (monotone per engine)
    step          decode-step total AFTER the iteration (unchanged when
                  the iteration only admitted/expired)
    live          occupied decode slots after the iteration
    admitted / completed / expired / poisoned / aborted / freed
                  scheduler decisions taken THIS iteration (freed =
                  slots released; completed+expired+poisoned+aborted
                  partition the request outcomes, so the ring's sums
                  reconcile exactly with STAT_gen_completions /
                  STAT_gen_timeouts / STAT_gen_poisoned)
    queue_depth / oldest_age_ms
                  intake pressure after the iteration (FIFO → the head
                  is the oldest)
    pages_in_use / free_pages
                  page-pool occupancy after the iteration
    prefix_tokens / cow_splits
                  prompt tokens served from cached prefix pages and
                  copy-on-write page splits performed THIS iteration
                  (ISSUE 12 — the prefix-cache effectiveness signal,
                  per iteration)
    tokens / spec_drafted / spec_accepted / prefill_chunks
                  tokens delivered THIS iteration (prefill first
                  tokens + decode/verify), speculative draft tokens
                  proposed and accepted, and prefill chunks run
                  (ISSUE 14 — tokens > live on a decode iteration is
                  speculation paying off; prefill_chunks interleaved
                  with decode_ms > 0 is chunked prefill protecting
                  TPOT). Appended AFTER the ISSUE-12 fields so older
                  ring consumers — which read by name with defaults —
                  parse records from both eras unchanged
    prefill_ms / decode_ms
                  the time of the prefill programs this iteration ran
                  and of the decode step it READ — the "is one long
                  prompt spiking everyone's TPOT" signal. With one
                  decode step in flight ahead of the host (ISSUE 34) a
                  program's time runs from the later of (its launch,
                  the observed end of the program before it) to its own
                  observed end: its device time or more (short only by
                  the host's lag in seeing the program before it end,
                  which that program's time holds), and no stretch
                  counted twice. A record's tokens, device
                  counters and decode_ms belong to the same step
    tier_demotions / tier_promotions
                  prefix-cache pages demoted to / promoted back from
                  the host-RAM tier THIS iteration (ISSUE 18 — the
                  cross-tier traffic signal)
    attr_admit_ms / attr_promote_ms / attr_bookkeep_ms / attr_idle_ms /
    attr_wall_ms  per-iteration goodput attribution (ISSUE 20): with
                  prefill_ms and decode_ms these six buckets sum to
                  attr_wall_ms EXACTLY (bookkeeping is the remainder of
                  the rounded siblings), feeding the
                  STAT_gen_step_attr_* histogram family. Since ISSUE 34
                  the wall is the sum of the stretches of the step
                  thread's timeline charged to the iteration (every
                  stretch is charged once, so the records' walls sum to
                  the thread's elapsed time), and the three host
                  buckets hold only time during which NO program was in
                  flight: the time the chip waited for the host
    decode_wait_ms / prefill_wait_ms
                  the part of decode_ms / prefill_ms the step thread
                  spent blocked in the read-back (`np.asarray` of the
                  program's host outputs) — waiting for the chip; the
                  rest is launch and argument upload. SUB-SPLITS (ISSUE
                  25): the six buckets above are unchanged and these
                  two are never added to them
    admit_wait_ms sum, over the requests admitted THIS iteration, of
                  admitted − queued (GenSpan stamps): with `admitted`
                  the mean wait from submission to a slot and pages
    experts_hit / latent_rows
                  counted ON THE DEVICE by the latent-attention family's
                  decode program and read back with the step's tokens
                  (ISSUE 27; 0 for a family that counts nothing):
                  distinct experts that got at least one live token,
                  summed over the expert layers, and cached positions the
                  step attended, summed over the live slots
    ahead         1 where the iteration's decode step was launched while
                  the step before it was still unread (ISSUE 34): its
                  tokens went from the device's output straight into
                  this step's input, and the host's work ran under it
    decode_dev_ms / prefill_dev_ms / dev_idle_ms / dev_idle_by
                  the engine's own timeline of the device
                  (serving/device_clock.py): each timed program's `enq`
                  (its dispatch returned) and `done` (the earlier of a
                  watcher thread's stamp and the read-back's return), on
                  the clock of these records. A program occupies the
                  device from the later of its `enq` and the `done` before
                  to its own `done`, charged to the record that READ it
                  (decode and verify steps to decode_dev_ms, prefills to
                  prefill_dev_ms); where its `enq` is later than the
                  `done` before, the device sat idle in between, charged
                  to the record of the iteration that launched it, and
                  dev_idle_by cuts that idle by the step thread's
                  innermost `generation::` scope (bucket suffix dropped,
                  `none` outside any; with the trace ring off, by the
                  engine's host bucket at the launch, `attr_admit_ms` /
                  `attr_bookkeep_ms`); its rounded values sum to
                  dev_idle_ms. Over consecutive records the three times
                  tile the span from the first `enq` to the last `done`.
                  They read the device, not the host: decode_ms and the
                  attribution buckets keep their meaning

The fit loop has a record of its own (`FitRecord`, ISSUE 25): one per
train step into ONE process-wide `FitLog` ring of the same kind, read
through `fit_records()`:

    fit           ordinal of the `Model.fit` call in the process
    step          step ordinal inside its epoch
    t             perf_counter at the record
    input_wait_ms blocked taking the next batch from the feeder or the
                  loader (span `fit::input_wait`)
    prep_ms       batch split, tail padding, masks
    dispatch_ms   the `train_batch` call: argument preparation and the
                  launch (span `fit::train_step`). On the v5e the jitted
                  call returns only when the step BEFORE has finished (its
                  donated carry is that step's output), so this bucket
                  holds the wait for the chip on every step but the one
                  after a sync (PERF.md, PR 25: 183 of 190 ms)
    sync_ms       blocked in `float(loss)` on the log cadence: waiting
                  for the chip (span `fit::sync`)
    callback_ms   on_batch_begin + on_batch_end (span `fit::callbacks`)
    other_ms      the remainder of the ROUNDED siblings, so the six
                  buckets sum to wall_ms EXACTLY (the engine's rule)
    wall_ms       mark to mark: the end of the previous step's record
                  (or the epoch's start) to the end of this one

The ring is exported three ways: `/steps` JSON
(`steps_payload()` — per-engine records + audit-log tail, the input of
`tools/engine_report.py`), chrome-trace counter tracks
(`chrome_counter_events()` merged into `/trace` and
`export_chrome_tracing`, so the scheduler state renders as "C" series
under the request timeline), and two histograms — `engine_step_ms`
(decode-step wall) and `gen_queue_age_ms` (oldest queued request's age,
observed every iteration the queue is non-empty).

Recording is single-writer (the engine's step thread owns every
append); readers take GIL-consistent list copies like the tracer rings.
Everything is gated by `FLAGS_gen_step_log` (default on).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from ..framework import monitor
from ..framework.flags import flag
from . import RecordEvent
from ._engine_registry import EngineRegistry

__all__ = ["StepRecord", "StepLog", "FitRecord", "FitLog", "FitClock",
           "enabled", "register", "unregister", "steps_payload",
           "fit_log", "fit_records", "chrome_counter_events"]

_FIELDS = ("it", "step", "t", "live", "admitted", "completed", "expired",
           "poisoned", "aborted", "freed", "queue_depth", "oldest_age_ms",
           "pages_in_use", "free_pages", "prefix_tokens", "cow_splits",
           "prefill_ms", "decode_ms", "tokens", "spec_drafted",
           "spec_accepted", "prefill_chunks",
           # ISSUE 15: which engine GENERATION (supervised-restart
           # ordinal) recorded this iteration — appended after the
           # older fields so ring consumers reading by name with
           # defaults parse records from every era unchanged
           "incarnation",
           # ISSUE 18: prefix-cache pages demoted to / promoted from
           # the host tier THIS iteration (same era-compat appending)
           "tier_demotions", "tier_promotions",
           # ISSUE 19: the engine's tensor-parallel degree (mesh-slice
           # width; 1 = single-chip lane) — constant per incarnation,
           # recorded so mixed-fleet step rings are self-describing
           "tp",
           # ISSUE 20: per-iteration goodput attribution. Six buckets —
           # attr_admit_ms (scheduler work net of nested device calls),
           # prefill_ms (above), attr_promote_ms (tier re-upload),
           # decode_ms (above), attr_bookkeep_ms (host bookkeeping:
           # record/flush/slice — computed as the remainder of the
           # ROUNDED siblings, so the stored buckets sum EXACTLY to
           # attr_wall_ms), attr_idle_ms (cv waits) — tile the
           # iteration's wall. attr_wall_ms == 0 marks a record from
           # before this era
           "attr_admit_ms", "attr_promote_ms", "attr_bookkeep_ms",
           "attr_idle_ms", "attr_wall_ms",
           # ISSUE 25: launch against wait inside decode_ms / prefill_ms
           # (sub-splits: the six buckets and their exact sum are as
           # they were), and how long this iteration's admissions had
           # queued — appended, by the same era rule
           "decode_wait_ms", "prefill_wait_ms", "admit_wait_ms",
           # ISSUE 27: what a family's decode program counts on the
           # device and returns with the tokens (a family that counts
           # nothing leaves them 0) — appended, by the same era rule
           "experts_hit", "latent_rows",
           # ISSUE 34: 1 where the iteration's decode step was launched
           # while the step before it was still unread (its tokens went
           # from the device's output straight into this step's input)
           "ahead",
           # ISSUE 36: the hybrid family's device counters (live slots
           # whose state-space state the step read and wrote; attended
           # K/V positions summed over the live slots; 0 for the other
           # families), and the real prompt tokens of the iteration's
           # prefill programs, counted by the engine for every family —
           # appended, by the same era rule
           "state_slots", "kv_rows", "prefill_tokens",
           # the engine's own timeline of the device
           # (serving/device_clock.py): the device time of the decode /
           # verify programs and of the prefill programs the iteration
           # READ, and the device idle that closed at one of its launches
           # with that idle by the step thread's scope ({} by default) —
           # appended, by the same era rule
           "decode_dev_ms", "prefill_dev_ms", "dev_idle_ms", "dev_idle_by")

_FIT_FIELDS = ("fit", "step", "t", "input_wait_ms", "prep_ms",
               "dispatch_ms", "sync_ms", "callback_ms", "other_ms",
               "wall_ms")
_FIT_BUCKETS = _FIT_FIELDS[3:8]     # measured; other_ms is the remainder
_FIT_RING = 4096                    # records


def enabled() -> bool:
    return bool(flag("FLAGS_gen_step_log"))


class _Record:
    """Compact record: slots only, absent fields read 0."""

    __slots__ = ()

    def __init__(self, **kw):
        for f in self.__slots__:
            setattr(self, f, kw.get(f, 0))

    def to_dict(self) -> dict:
        return {f: getattr(self, f) for f in self.__slots__}


class StepRecord(_Record):
    """One engine iteration's scheduler state."""

    __slots__ = _FIELDS

    def __init__(self, **kw):
        super().__init__(**kw)
        if not self.dev_idle_by:
            self.dev_idle_by = {}


class FitRecord(_Record):
    """One train step of a fit loop: where its wall went on the host."""

    __slots__ = _FIT_FIELDS


class _Ring:
    """Bounded record ring, oldest overwritten. `snapshot()` is a
    GIL-consistent copy in the order of `key` (a record's own monotone
    counter): the writer can append between the copy and a read of its
    index, which would rotate the true oldest record to the newest
    position, so the rotation point is found in the copy itself."""

    def __init__(self, capacity: int):
        self.cap = max(1, int(capacity))
        self._buf: list = []
        self._idx = 0           # oldest slot once full
        self.recorded = 0       # total records ever appended

    def _append(self, rec) -> None:
        if len(self._buf) < self.cap:
            self._buf.append(rec)
        else:
            self._buf[self._idx] = rec
            self._idx = (self._idx + 1) % self.cap
        self.recorded += 1

    def _snapshot(self, key) -> list:
        buf = list(self._buf)   # one GIL-atomic copy — consistent
        if len(buf) < self.cap:
            return buf
        lo = min(range(len(buf)), key=lambda i: key(buf[i]))
        return buf[lo:] + buf[:lo] if lo else buf


_hists_lock = threading.Lock()
_hists = None
_attr_hists = None


def _step_hists():
    global _hists
    if _hists is None:
        with _hists_lock:
            if _hists is None:
                # literal names: the check_stats lint reads these
                _hists = (monitor.histogram("engine_step_ms"),
                          monitor.histogram("gen_queue_age_ms"))
    return _hists


def _step_attr_hists():
    global _attr_hists
    if _attr_hists is None:
        with _hists_lock:
            if _attr_hists is None:
                # literal names: the check_stats lint reads these
                _attr_hists = (
                    monitor.histogram("STAT_gen_step_attr_admit_ms"),
                    monitor.histogram("STAT_gen_step_attr_prefill_ms"),
                    monitor.histogram("STAT_gen_step_attr_promote_ms"),
                    monitor.histogram("STAT_gen_step_attr_decode_ms"),
                    monitor.histogram("STAT_gen_step_attr_bookkeep_ms"),
                    monitor.histogram("STAT_gen_step_attr_idle_ms"))
    return _attr_hists


class StepLog(_Ring):
    """One engine's bounded step ring. The owning step thread is the
    only writer; `snapshot()`/`tail()` are GIL-consistent copies."""

    def __init__(self, engine: str, capacity: Optional[int] = None):
        super().__init__(flag("FLAGS_gen_step_log_size")
                         if capacity is None else capacity)
        self.engine = engine
        register(self)

    def record(self, rec: StepRecord) -> None:
        """Append one iteration record (step thread only) and feed the
        step/queue-age histograms. One list append + two histogram
        observes — nothing here syncs the device."""
        step_h, age_h = _step_hists()
        if rec.decode_ms > 0:
            step_h.observe(rec.decode_ms)
        if rec.queue_depth:
            age_h.observe(max(0.0, rec.oldest_age_ms))
        if rec.attr_wall_ms > 0:
            # goodput attribution (ISSUE 20): one observe per bucket
            # per iteration — "where did this replica's ms go" as a
            # fleet-scrapeable histogram family
            for h, v in zip(_step_attr_hists(),
                            (rec.attr_admit_ms, rec.prefill_ms,
                             rec.attr_promote_ms, rec.decode_ms,
                             rec.attr_bookkeep_ms, rec.attr_idle_ms)):
                h.observe(max(0.0, v))
        self._append(rec)

    def snapshot(self) -> List[StepRecord]:
        return self._snapshot(lambda r: r.it)

    def tail(self, n: int) -> List[dict]:
        """Last `n` records as dicts, oldest-first (flight dumps,
        `/steps`)."""
        return [r.to_dict() for r in self.snapshot()[-max(0, int(n)):]]


# -- the fit loop's ring (ISSUE 25) ------------------------------------------

class FitLog(_Ring):
    """The process's bounded ring of train-step records. Fit loops on
    several threads may share it, so an append takes a lock (uncontended
    in the one-loop case: tens of nanoseconds against a step)."""

    def __init__(self, capacity: int = _FIT_RING):
        super().__init__(capacity)
        self._lock = threading.Lock()
        self._fits = 0

    def begin_fit(self) -> int:
        """Ordinal of a starting `fit()` call (1-based)."""
        with self._lock:
            self._fits += 1
            return self._fits

    def record(self, rec: FitRecord) -> None:
        with self._lock:
            self._append(rec)

    def snapshot(self) -> List[FitRecord]:
        return self._snapshot(lambda r: r.t)


fit_log = FitLog()


def fit_records() -> List[dict]:
    """The fit ring's retained records as dicts, oldest first."""
    return [r.to_dict() for r in fit_log.snapshot()]


class _FitSpan:
    """`with clock.span(bucket, name)`: the block's wall goes to
    `bucket`, under a RecordEvent `name` when one is given (the same
    stretch on the profiler's clock)."""

    __slots__ = ("_acc", "_bucket", "_ev", "_t0")

    def __init__(self, acc, bucket, name):
        self._acc, self._bucket = acc, bucket
        self._ev = None if name is None else RecordEvent(name)

    def __enter__(self):
        self._t0 = time.perf_counter()
        if self._ev is not None:
            self._ev.begin()

    def __exit__(self, *exc):
        if self._ev is not None:
            self._ev.end()
        self._acc[self._bucket] += time.perf_counter() - self._t0
        return False


class FitClock:
    """One fit loop's marks. `span()` charges a stretch to a bucket,
    `close(step)` writes the step's record: wall is mark to mark, every
    stored value is rounded first and `other_ms` is the remainder of the
    rounded siblings, so a record's buckets sum to its wall_ms exactly.
    `restart()` moves the mark without a record (an epoch's start)."""

    def __init__(self, log: FitLog = fit_log):
        self._log = log
        self.fit = log.begin_fit()
        self.restart()

    def restart(self) -> None:
        self._acc = dict.fromkeys(_FIT_BUCKETS, 0.0)
        self._mark = time.perf_counter()

    def span(self, bucket: str, name: Optional[str] = None) -> _FitSpan:
        return _FitSpan(self._acc, bucket, name)

    def close(self, step: int) -> None:
        now = time.perf_counter()
        parts = {k: round(v * 1000.0, 3) for k, v in self._acc.items()}
        wall = round((now - self._mark) * 1000.0, 3)
        self._log.record(FitRecord(
            fit=self.fit, step=step, t=now, wall_ms=wall,
            other_ms=round(wall - sum(parts.values()), 3), **parts))
        self._acc = dict.fromkeys(_FIT_BUCKETS, 0.0)
        self._mark = now


# -- registry (the `/steps` surface) ----------------------------------------

_logs = EngineRegistry()


def register(log: StepLog) -> None:
    _logs.register(log.engine, log)


def unregister(log: StepLog) -> None:
    _logs.unregister(log.engine, log)


def _live_logs() -> Dict[str, StepLog]:
    return _logs.live()


def steps_payload(last: int = 0, audit_tail: int = 256) -> dict:
    """The `/steps` JSON: per-engine iteration records (all retained, or
    the last `last`) + the engine's decision-audit tail + the two step
    histograms — everything `tools/engine_report.py` needs to render a
    human timeline."""
    from . import audit
    step_h, age_h = _step_hists()
    engines = {}
    for name, log in sorted(_live_logs().items()):
        recs = [r.to_dict() for r in log.snapshot()]
        if last > 0:
            recs = recs[-last:]
        engines[name] = {
            "records": recs,
            "recorded_total": log.recorded,
            "ring_capacity": log.cap,
            "audit": audit.tail_for(name, audit_tail),
        }
    return {"enabled": enabled(),
            "engines": engines,
            "histograms": {"engine_step_ms": step_h.snapshot(),
                           "gen_queue_age_ms": age_h.snapshot()}}


def chrome_counter_events(since: Optional[float] = None,
                          pid: Optional[int] = None) -> List[dict]:
    """Step-ring records as chrome-trace "C" counter events — one event
    per record carrying the scheduler's live/queue/pages series, so the
    timeline shows slot occupancy and pool pressure UNDER the request
    scopes. Merged into `/trace` and `export_chrome_tracing`."""
    import os
    pid = os.getpid() if pid is None else pid
    out = []
    for name, log in sorted(_live_logs().items()):
        for r in log.snapshot():
            if since is not None and r.t < since:
                continue
            out.append({"name": f"{name} scheduler", "ph": "C",
                        "pid": pid, "tid": 0, "ts": r.t * 1e6,
                        "args": {"live_slots": r.live,
                                 "queue_depth": r.queue_depth,
                                 "pages_in_use": r.pages_in_use,
                                 "free_pages": r.free_pages}})
    return out
