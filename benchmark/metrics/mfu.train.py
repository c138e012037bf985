"""End-to-end model FLOP/s utilisation, in %: the run's tokens per second
times the operations one token needs forward and backward (6 per matmul
weight plus attention, no recomputation; benchmark/flops.py) over the chips'
published bf16 peak. It is the whole loop's utilisation, input pipeline and
idle time included, not a kernel's roofline share. In a traced run the rate
is taken over the window's steps outside the traced ones."""
from benchmark import flops


def read(rec):
    peak = flops.peaks(rec["device_kind"])["flops_bf16"]
    per_token = flops.train_flops_per_token(rec["model"], rec["seq_len"])
    return 100.0 * rec["tokens_per_s"] * per_token / (rec["chips"] * peak)
