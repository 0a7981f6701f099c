"""Host-cache hits over hits and misses in the window (``core/cache.py``).
A reader of the train cells."""


def read(r):
    if r["job"] != "train":
        return None
    c = r["counters"]
    n = c.get("cache_hits", 0) + c.get("cache_misses", 0)
    return c.get("cache_hits", 0) / n if n else None
