"""Seconds per epoch of the engine's backward layers: the engine tracer's
``bwd_layer`` spans (``core/engine.py``), on the host clock."""


def read(r):
    ev = r.get("tracer_events")
    if r["job"] != "train" or not ev:
        return None
    total = sum(e["dur"] for e in ev if e["name"] == "bwd_layer") * 1e-6
    return total / r["iters"] if total else None
