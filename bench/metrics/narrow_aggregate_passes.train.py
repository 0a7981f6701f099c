"""Layer passes an epoch, forward and backward, whose layer program ran the
transform-first order (the program's ``narrow_aggregate_passes`` counter:
GCN and SAGE layers that narrow). A reader of the train cells; a program
without the counter reads nothing."""


def read(r):
    c = r["counters"]
    if r["job"] != "train" or "narrow_aggregate_passes" not in c:
        return None
    return c["narrow_aggregate_passes"] / r["iters"]
