"""Seconds per epoch of storage reads: the program's ``storage_read`` spans
(``core/storage.py``, one per read call on any thread), on the host clock.
Reads on several threads at once each count in full."""


def read(r):
    ev = r.get("tracer_events")
    if r["job"] != "train" or not ev:
        return None
    total = sum(e["dur"] for e in ev if e["name"] == "storage_read") * 1e-6
    return total / r["iters"] if total else None
