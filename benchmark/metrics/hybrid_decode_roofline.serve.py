"""How close a decode step of the hybrid state-space / attention model comes
to the least time the chip's memory allows, in %: the bytes the step has to
move (benchmark/flops_hybrid.py: every weight outside the embedding once,
the float32 mixer state of each live slot read AND written, one K and one V
row per attended position and layer — the last two from the program's own
`state_slots` / `kv_rows` counters of that very step) over the published
bandwidth, over the step's host time (decode_ms, as decode_roofline.serve
is); the median over the window's decode iterations. Bandwidth bounds it: 96
rows against 13 GB. From outside the program, so it leaves out nothing the
step does and cannot pass 100% unless bytes are over-counted. None where the
window ran no decode step; NO_RECORD where the records have no such counter
(a program from before PR 36)."""
import statistics

from benchmark import flops, flops_hybrid, program_records


def read(rec):
    if program_records.older_than(rec["steps"], "state_slots"):
        return program_records.NO_RECORD
    bw = flops.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    shares = [flops_hybrid.decode_step_bytes(
                  rec["model"], r["state_slots"], r["kv_rows"]) / bw
              / (r["decode_ms"] / 1e3)
              for r in rec["steps"] if r["decode_ms"] > 0]
    return 100.0 * statistics.median(shares) if shares else None
