"""Device time of one decode step, in ms: the median of
StepRecord.decode_dev_ms over the window's decode iterations. The engine
keeps its own timeline of the device (paddle_tpu/serving/device_clock.py):
a step occupies the device from the later of its enqueue (its dispatch
returned) and the end of the program before it, to its own end, the
earlier of a watcher thread's stamp and the step thread's read-back. So
where decode_ms (decode_step_ms.serve) holds the host's launch of a step
that found the chip idle, this holds only the chip's time, and the idle
goes to dev_idle_ms (device_idle_share.serve). A profiler trace is its
check: the mean `jit_gen_decode` run (tools/trace_report.py). None where
the window ran no decode step; NO_RECORD where the records have no
decode_dev_ms (a program from before the engine kept that timeline)."""
import statistics

from benchmark import program_records


def read(rec):
    if program_records.older_than(rec["steps"], "decode_dev_ms"):
        return program_records.NO_RECORD
    ms = [r["decode_dev_ms"] for r in rec["steps"] if r["decode_ms"] > 0]
    return statistics.median(ms) if ms else None
