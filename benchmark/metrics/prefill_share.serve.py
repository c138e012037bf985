"""Share of the step thread's wall that went to prefill calls: the sum of
the window's StepRecord.prefill_ms over the sum of attr_wall_ms, in %."""


def read(rec):
    wall = sum(r["attr_wall_ms"] for r in rec["steps"])
    if wall <= 0:
        return None
    return 100.0 * sum(r["prefill_ms"] for r in rec["steps"]) / wall
