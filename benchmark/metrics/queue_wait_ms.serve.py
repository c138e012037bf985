"""Mean wait from submission to a slot and pages, in ms, over the window's
admissions: the sum of StepRecord.admit_wait_ms (per iteration, the sum of
admitted - queued over the requests admitted in it) over the sum of
`admitted`. Some 30 admissions a window: a mean, not a tail. None where the
window admitted nothing; NO_RECORD where the records have no admit_wait_ms
(before PR 25)."""
from benchmark import program_records


def read(rec):
    if program_records.older_than(rec["steps"], "admit_wait_ms"):
        return program_records.NO_RECORD
    admitted = sum(r["admitted"] for r in rec["steps"])
    if not admitted:
        return None
    return sum(r["admit_wait_ms"] for r in rec["steps"]) / admitted
