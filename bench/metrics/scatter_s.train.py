"""Seconds per epoch of the backward's host scatter of gradient rows into
their source partitions, on the compute thread: the program's ``scatter``
spans (``core/engine.py``), on the host clock."""


def read(r):
    ev = r.get("tracer_events")
    if r["job"] != "train" or not ev:
        return None
    total = sum(e["dur"] for e in ev if e["name"] == "scatter") * 1e-6
    return total / r["iters"] if total else None
