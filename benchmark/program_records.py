"""What the four readers of PR 25 share: which of the program's own step
records a reader takes, and what it says of a program older than the record.

A reader whose records are there but hold nothing to read — an empty ring, a
fit too short to reach its second log cadence, a window without an admission
— returns None: `run.py` leaves the metric out and `check_line` refuses the
line, so a ring that a later change breaks or clears shows at once.

A program from BEFORE the record (no `step_log.fit_records`, a StepRecord
without the field) is another case. The driver lays BENCHMARK.json and this
directory over the parent's checkout for the traced runs, the parent of PR 25
is such a program, and `check_line` refuses that run too if a listed metric
is missing. For that case alone a reader returns NO_RECORD: finite, so the
line passes, and below any duration or share, so it is never taken for a
reading.
"""
NO_RECORD = -1.0


def fit_window():
    """The newest fit's records from its second synced step on (a record
    with `sync_ms` > 0: the loop forced the loss on its log cadence), so the
    steps before, with the workers' start and the feeder's first fill, are
    left out whatever the cadence is. [] where there is no such step, None
    where the program keeps no fit ring."""
    from paddle_tpu.profiler import step_log
    if not hasattr(step_log, "fit_records"):
        return None
    recs = step_log.fit_records()
    newest = max((r["fit"] for r in recs), default=0)
    recs = [r for r in recs if r["fit"] == newest]
    synced = [i for i, r in enumerate(recs) if r["sync_ms"] > 0]
    return recs[synced[1]:] if len(synced) > 1 else []


def older_than(steps, field):
    """True where the engine's step records come from a program that has no
    `field` yet."""
    return bool(steps) and field not in steps[0]
