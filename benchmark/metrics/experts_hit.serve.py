"""Share of the routed experts a decode step reads, in %: the mean, over the
window's decode iterations, of StepRecord.experts_hit (distinct experts that
got at least one live token, summed over the expert layers, counted on the
device by the decode program) over experts x expert layers. Lower is fewer
bytes a step: 32 tokens x 4 experts over 64 hit about 87% under even
routing. None where the window ran no decode step; NO_RECORD where the
records have no such counter (a program from before PR 27)."""
from benchmark import flops_moe_mla, program_records


def read(rec):
    if program_records.older_than(rec["steps"], "experts_hit"):
        return program_records.NO_RECORD
    m = rec["model"]
    hit = [r["experts_hit"] for r in rec["steps"] if r["decode_ms"] > 0]
    if not hit:
        return None
    return (100.0 * sum(hit) / len(hit)
            / (m["n_routed_experts"] * flops_moe_mla.expert_layers(m)))
