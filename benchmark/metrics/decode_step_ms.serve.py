"""Median StepRecord.decode_ms over the window's decode iterations. Since
PR 34 (one decode step in flight ahead of the host) a step's decode_ms runs
on the host's clock from the LATER of its launch and the observed end of the
program before it, to its own observed end: with step n+1 queued behind step
n that is the device's step plus what the chip waited between the two, the
program's device time or more. After a stall of the step thread one record
reads short and the one before it long, so medians and sums hold where single
records need not. It is a host time, named for what it is; the device time
per program is a metric for the next benchmark issue (PERF.md, section 7)."""
import statistics


def read(rec):
    ms = [r["decode_ms"] for r in rec["steps"] if r["decode_ms"] > 0]
    return statistics.median(ms) if ms else None
