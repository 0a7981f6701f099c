"""Bytes read from the storage tier per pass in the window
(``core/storage.py``)."""


def read(r):
    if r["job"] != "infer":
        return None
    return r["counters"].get("storage_read_bytes", 0) / r["iters"]
