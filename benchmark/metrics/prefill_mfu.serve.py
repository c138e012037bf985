"""Share of the chip's bfloat16 peak that the window's prefill calls reach,
in %: the model FLOPs of the real prompt tokens they ran
(benchmark/flops_hybrid.py: the blocks' matrices a token, causal attention's
and the chunked scan's own products, the head once a request; from
StepRecord.prefill_tokens and .admitted, nothing for a bucket's padding) over
the sum of the window's prefill_ms (host time of the prefill programs: later
of launch and the end of the program before, to their own end) times the
published peak. One request a call, so the matrices are read once a request:
low where prompts are short. None where the window ran no prefill; NO_RECORD
where the records have no `prefill_tokens` (a program from before PR 36)."""
from benchmark import flops, flops_hybrid, program_records


def read(rec):
    if program_records.older_than(rec["steps"], "prefill_tokens"):
        return program_records.NO_RECORD
    peak = flops.peaks(rec["device_kind"])["flops_bf16"]
    calls = [r for r in rec["steps"]
             if r["prefill_ms"] > 0 and r["prefill_tokens"] > 0]
    ms = sum(r["prefill_ms"] for r in calls)
    if ms <= 0:
        return None
    done = sum(flops_hybrid.prefill_flops(
        rec["model"], r["prefill_tokens"], max(1, r["admitted"]))
        for r in calls)
    return 100.0 * done / (ms / 1e3) / peak
