"""The host's own work per engine iteration, in ms: the median, over the
window's StepRecords that ran a decode step, of attr_wall_ms - attr_idle_ms -
decode_wait_ms - prefill_wait_ms — everything the step thread did except
wait, for requests or for the chip. Since PR 34 the engine launches decode
step n+1 before it reads step n, so this work runs UNDER the step in flight:
hidden, not gone. The chip waits only for the host time with no program in
flight, attr_admit_ms + attr_bookkeep_ms; this metric reads all of the
host's work, hidden or not, and would hold the chip again if it grew past
the device's step. None where the window ran no decode step; NO_RECORD where
the records have no wait fields (before PR 25): the busy iteration whole
would read as the chip's time, not the host's."""
import statistics

from benchmark import program_records


def read(rec):
    if program_records.older_than(rec["steps"], "decode_wait_ms"):
        return program_records.NO_RECORD
    ms = [r["attr_wall_ms"] - r["attr_idle_ms"] - r["decode_wait_ms"]
          - r["prefill_wait_ms"] for r in rec["steps"] if r["decode_ms"] > 0]
    return statistics.median(ms) if ms else None
