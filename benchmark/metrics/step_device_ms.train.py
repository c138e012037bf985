"""Device time of one train step: the traced sub-window's busy time (union
of operations, mean over the cell's chips) over the steps run in it."""


def read(rec):
    if not rec["traced"]:
        return None
    return rec["trace"]["busy_s"] / rec["traced"] * 1e3
