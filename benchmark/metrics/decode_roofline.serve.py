"""How close a decode step comes to the least time the chip's memory allows,
in %: the bytes one step has to read (every weight once, and the keys and
values of the pages in use, from shapes by benchmark/flops.py) over the
published bandwidth, over the step's host time (decode_ms); the median over
the window's decode iterations. Bandwidth bounds it: a step of 16 rows does
about 50 GFLOP, 0.25 ms at the compute peak, against 7.6 ms for the weights
alone. From outside the program, so it leaves out nothing the step does and
cannot pass 100% unless bytes are over-counted."""
import statistics

from benchmark import flops


def read(rec):
    m, page = rec["model"], rec["engine"]["page_size"]
    bw = flops.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    weights, per_token = flops.decoder_weight_bytes(m), \
        flops.kv_bytes_per_token(m)
    shares = [(weights + per_token * page * r["pages_in_use"]) / bw
              / (r["decode_ms"] / 1e3)
              for r in rec["steps"] if r["decode_ms"] > 0]
    return 100.0 * statistics.median(shares) if shares else None
