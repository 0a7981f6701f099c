"""Layer passes an epoch, forward and backward, of an attention layer (the
program's ``attention_passes`` counter: GAT). A reader of the train cells;
a program without the counter reads nothing."""


def read(r):
    c = r["counters"]
    if r["job"] != "train" or "attention_passes" not in c:
        return None
    return c["attention_passes"] / r["iters"]
