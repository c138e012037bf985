"""What one train step costs the host when it does not wait for the chip, in
ms: the median of wall_ms - sync_ms over the records that FOLLOW a synced
record (`sync_ms` > 0), among the newest fit's from its second synced step on
(benchmark/program_records.py). The chip idles once this passes
step_device_ms.train.

Why those records alone: on the v5e the jitted train-step call returns only
when the step before has finished, so on every other step `dispatch_ms` holds
the wait for the chip and wall - sync reads the device's step (190.45 ms
against step_device_ms.train 188.37: PERF.md section 6, PR 25). After a sync
the chip has nothing queued, the call launches and returns, and the step's
wall is the host's own work: the feeder's hand-over, the batch split, the
eager argument programs, the launch, the callbacks. One record in `log_freq`
qualifies, some 26 a window.

None where no record follows a synced one; NO_RECORD where the program keeps
no fit ring (before PR 25)."""
import statistics

from benchmark import program_records


def host_ms(recs):
    ms = [r["wall_ms"] - r["sync_ms"] for before, r in zip(recs, recs[1:])
          if before["sync_ms"] > 0]
    return statistics.median(ms) if ms else None


def read(rec):
    recs = program_records.fit_window()
    return program_records.NO_RECORD if recs is None else host_ms(recs)
