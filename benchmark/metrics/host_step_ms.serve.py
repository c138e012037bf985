"""The host's serial work per engine iteration, in ms: the median, over the
window's StepRecords that ran a decode step, of attr_wall_ms - attr_idle_ms -
decode_wait_ms - prefill_wait_ms — everything the step thread did except
wait, for requests or for the chip. The chip waits for it, because step n+1
is launched only after step n's tokens are read (ROADMAP Queue 1 item 5).
None where the window ran no decode step; NO_RECORD where the records have
no wait fields (before PR 25): the busy iteration whole would read as the
chip's time, not the host's."""
import statistics

from benchmark import program_records


def read(rec):
    if program_records.older_than(rec["steps"], "decode_wait_ms"):
        return program_records.NO_RECORD
    ms = [r["attr_wall_ms"] - r["attr_idle_ms"] - r["decode_wait_ms"]
          - r["prefill_wait_ms"] for r in rec["steps"] if r["decode_ms"] > 0]
    return statistics.median(ms) if ms else None
