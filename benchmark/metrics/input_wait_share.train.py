"""Share of the fit loop's wall spent blocked taking the next batch from the
feeder (or the loader), in %: the sum of FitRecord.input_wait_ms over the sum
of wall_ms, numerator and denominator from the same records (the newest
fit's, from its second synced step on: benchmark/program_records.py). None
where those records hold no wall; NO_RECORD where the program keeps no fit
ring (before PR 25)."""
from benchmark import program_records


def share(recs):
    wall = sum(r["wall_ms"] for r in recs)
    if wall <= 0:
        return None
    return 100.0 * sum(r["input_wait_ms"] for r in recs) / wall


def read(rec):
    recs = program_records.fit_window()
    return program_records.NO_RECORD if recs is None else share(recs)
