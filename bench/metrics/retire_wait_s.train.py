"""Seconds per epoch that the backward's compute loop waited to hand a
unit's gradient rows to the D2H retire thread, whose queue was full: the
``retire_submit_bwd`` stall of ``runtime/executor.py``, on the host clock.
Nothing where the program does not retire the backward's scatter (no
``retired_scatter_units`` counter)."""


def read(r):
    c = r["counters"]
    if r["job"] != "train" or "retired_scatter_units" not in c:
        return None
    return c.get("stall_retire_submit_bwd", 0.0) / r["iters"]
