"""How close a decode step of the latent-attention expert model comes to the
least time the chip's memory allows, in %: the bytes the step has to read
(benchmark/flops_moe_mla.py: every non-expert weight and the head once, the
three matrices of each expert the step's tokens hit, one latent row per
attended position and layer — the last two from the program's own
`experts_hit` / `latent_rows` counters of that very step) over the published
bandwidth, over the step's host time (decode_ms, as decode_roofline.serve
is); the median over the window's decode iterations. Bandwidth bounds it: 32
rows against 7.9 GB. From outside the program, so it leaves out nothing the
step does and cannot pass 100% unless bytes are over-counted. None where the
window ran no decode step; NO_RECORD where the records have no such counter
(a program from before PR 27)."""
import statistics

from benchmark import flops, flops_moe_mla, program_records


def read(rec):
    if program_records.older_than(rec["steps"], "experts_hit"):
        return program_records.NO_RECORD
    bw = flops.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    shares = [flops_moe_mla.decode_step_bytes(
                  rec["model"], r["experts_hit"], r["latent_rows"]) / bw
              / (r["decode_ms"] / 1e3)
              for r in rec["steps"] if r["decode_ms"] > 0]
    return 100.0 * statistics.median(shares) if shares else None
