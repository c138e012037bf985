"""The generation engine's own timeline of the device: when each timed
program (decode, verify, prefill, tail prefill, prefill chunk) entered the
device's queue and when it ended, on the `time.perf_counter` clock of the
engine's spans and step records.

Two stamps a program:

    enq   the moment its dispatch call returned: it is in the device's queue
    done  the EARLIER of two readings, each an upper bound of its true end:
          the watcher's (one thread an engine blocks on an output only the
          host reads and stamps when that returns: close to the end when the
          step thread is busy elsewhere, late by the interpreter lock's
          hand-off) and the step thread's own read-back (exact when the step
          thread was already blocked in it)

Program n occupies the device over [max(enq_n, done_n-1), done_n]; where
enq_n is later than done_n-1 the device sat idle over [done_n-1, enq_n].
Programs the engine does not time (page zeroing, copy-on-write, host-tier
writes) are queued between two timed ones and fold into the later. Over
consecutive `close()` calls device time plus idle tiles the span from the
first `enq` to the last `done`: nothing is counted twice.

An idle stretch is cut by the step thread's innermost open `generation::`
scope, read from the thread's own trace ring (`profiler/tracer.py`, the
same clock), with a bracketed bucket suffix (`[b=256]`, `[m=96]`) dropped;
time under no scope goes to `none`. With the ring off (no profiler and
`FLAGS_flight_recorder` off) the whole stretch goes to the engine's host
bucket at the launch that closed it (`attr_admit_ms`, `attr_bookkeep_ms`,
`attr_promote_ms`).
"""
from __future__ import annotations

import queue
import re
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from ..profiler import RecordEvent, tracer

NO_SCOPE = "none"
SCOPE_PREFIX = "generation::"
_SUFFIX = re.compile(r"\[.*\]$")


class Launch:
    """One timed program from its dispatch to its end."""

    __slots__ = ("kind", "out", "enq", "phase", "watched", "read",
                 "idle_charged")

    def __init__(self, kind: str, out, enq: float, phase: str):
        self.kind = kind            # "decode" or "prefill"
        self.out = out              # what the watcher waits on, until then
        self.enq = enq
        self.phase = phase          # the engine's host bucket at the launch
        self.watched: Optional[float] = None    # the watcher's stamp
        self.read: Optional[float] = None       # the read-back's return
        self.idle_charged = False

    def done(self) -> Optional[float]:
        """The earlier of the readings taken so far, or None."""
        stamps = [t for t in (self.watched, self.read) if t is not None]
        return min(stamps) if stamps else None


def cut(lo: float, hi: float,
        scopes: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """[lo, hi] by the innermost of `scopes` (name, t0, t1 of ONE thread:
    nested or disjoint) open at each moment, as {name: seconds}, the
    bracketed suffix of a name dropped; time under none to NO_SCOPE."""
    inside = [(t0, t1, name) for name, t0, t1 in scopes
              if t0 < hi and t1 > lo]
    edges = sorted({lo, hi, *(t for t0, t1, _ in inside for t in (t0, t1)
                              if lo < t < hi)})
    out: Dict[str, float] = {}
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        # the innermost: the latest start, then the earliest end
        cover = [(t0, -t1, name) for t0, t1, name in inside
                 if t0 <= mid <= t1]
        label = _SUFFIX.sub("", max(cover)[2]) if cover else NO_SCOPE
        out[label] = out.get(label, 0.0) + (b - a)
    return out


class DeviceClock:
    """One engine's device timeline. `launched`, `close` and `dropped` are
    the step thread's; the watcher thread only stamps `Launch.watched`."""

    def __init__(self, name: str):
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._pending: deque = deque()  # launched, not yet charged whole
        self.first_enq: Optional[float] = None
        self.last_done: Optional[float] = None  # of the last program charged
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name=f"{name}-genwatch")
        self._thread.start()

    def launched(self, kind: str, out, phase: str) -> Launch:
        """A timed program's dispatch has just returned; `out` is an output
        that only the host reads (never a donated pool)."""
        t = Launch(kind, out, time.perf_counter(), phase)
        if self.first_enq is None:
            self.first_enq = t.enq
        self._pending.append(t)
        self._q.put(t)
        return t

    def dropped(self, t: Launch) -> None:
        """A program that will never be read (abort, death): no time of
        it is charged."""
        if t in self._pending:
            self._pending.remove(t)

    def close(self) -> Tuple[Dict[str, float], Dict[str, float]]:
        """What the programs launched or read since the last call add, in
        seconds: ({kind: device time of the programs READ since}, {scope:
        idle that closed at a launch since}). Programs are read in launch
        order, so the walk stops at the first one still unread."""
        dev = {"decode": 0.0, "prefill": 0.0}
        idle: Dict[str, float] = {}
        while self._pending:
            t = self._pending[0]
            prev = self.last_done
            if not t.idle_charged:
                t.idle_charged = True
                if prev is not None and t.enq > prev:
                    for k, s in self._idle_by(prev, t.enq, t.phase).items():
                        idle[k] = idle.get(k, 0.0) + s
            if t.read is None:
                break
            self._pending.popleft()
            done = t.done() if prev is None else max(t.done(), prev)
            dev[t.kind] += done - (t.enq if prev is None
                                   else max(t.enq, prev))
            self.last_done = done
        return dev, idle

    @staticmethod
    def _idle_by(lo: float, hi: float, phase: str) -> Dict[str, float]:
        if not tracer.recording():
            return {phase: hi - lo}
        return cut(lo, hi, tracer.own_scopes(lo, SCOPE_PREFIX))

    def stop(self) -> None:
        """The watcher ends once it has stamped what was queued before."""
        self._q.put(None)

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    def _watch(self):
        while True:
            t = self._q.get()
            if t is None:
                return
            out, t.out = t.out, None
            with RecordEvent("generation::await"):
                try:
                    out.block_until_ready()
                except Exception:  # noqa: BLE001 — a failed program: the
                    #                 step thread's read-back raises it
                    continue
                t.watched = time.perf_counter()
