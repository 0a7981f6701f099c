"""Bytes staged host to device per pass in the window (the
pipeline's transfer stage, ``runtime/``)."""


def read(r):
    if r["job"] != "infer":
        return None
    return r["counters"].get("h2d_bytes", 0) / r["iters"]
