"""Median StepRecord.decode_ms over the window's decode iterations: the
host's clock around the decode program's launch and the tokens' read-back.
It is a host time, named for what it is; the device time per program needs
names inside the program (PERF.md, list for the tracing issue)."""
import statistics


def read(rec):
    ms = [r["decode_ms"] for r in rec["steps"] if r["decode_ms"] > 0]
    return statistics.median(ms) if ms else None
