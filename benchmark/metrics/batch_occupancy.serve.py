"""Mean share of the decode slots that were live, over the window's engine
iterations that ran a decode step (StepRecord.live / max_slots), in %."""


def read(rec):
    live = [r["live"] for r in rec["steps"] if r["decode_ms"] > 0]
    if not live:
        return None
    return 100.0 * sum(live) / len(live) / rec["engine"]["max_slots"]
