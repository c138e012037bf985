"""Share of the step thread's window in which the device sat idle, in %:
the sum of StepRecord.dev_idle_ms over the sum of attr_wall_ms, over the
window's records. The engine's own timeline of the device
(paddle_tpu/serving/device_clock.py) counts a stretch idle from one timed
program's end to the enqueue of the next, where the next was enqueued
later; each record's dev_idle_by says what the step thread was doing then,
by its `generation::` scope. A profiler trace is its check: 1 - busy_s /
window_s over the same stretch of time. None where the window ran no
decode step; NO_RECORD where the records have no dev_idle_ms (a program
from before the engine kept that timeline)."""
from benchmark import program_records


def read(rec):
    steps = rec["steps"]
    if program_records.older_than(steps, "dev_idle_ms"):
        return program_records.NO_RECORD
    if not any(r["decode_ms"] > 0 for r in steps):
        return None
    wall = sum(r["attr_wall_ms"] for r in steps)
    return 100.0 * sum(r["dev_idle_ms"] for r in steps) / wall
