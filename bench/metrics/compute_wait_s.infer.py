"""Seconds per pass that the compute loop waited on the
pipeline for its next unit's data (every ``compute_wait_*`` stall of
``runtime/executor.py``), on the host clock."""


def read(r):
    if r["job"] != "infer":
        return None
    c = r["counters"]
    return sum(v for k, v in c.items()
               if k.startswith("stall_compute_wait")) / r["iters"]
