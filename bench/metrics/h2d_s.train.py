"""Seconds per epoch that the transfer thread spent staging units onto the
device: the program's ``h2d`` spans (``runtime/executor.py``), on the host
clock."""


def read(r):
    ev = r.get("tracer_events")
    if r["job"] != "train" or not ev:
        return None
    total = sum(e["dur"] for e in ev if e["name"] == "h2d") * 1e-6
    return total / r["iters"] if total else None
