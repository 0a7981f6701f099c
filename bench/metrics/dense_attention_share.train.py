"""Share of an epoch's attention (layer, unit) passes, forward and backward,
that ran the dense path (the program's ``dense_attention_units`` over its
``attention_units`` counter: GAT). A reader of the train cells; a program
without the counters, or without attention layers, reads nothing."""


def read(r):
    c = r["counters"]
    if r["job"] != "train" or not c.get("attention_units"):
        return None
    return c.get("dense_attention_units", 0) / c["attention_units"]
