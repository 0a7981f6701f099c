"""Seconds per epoch of host gather: the program's ``gather``,
``regather``, ``snap_fetch``, ``loss_fetch`` and ``grad_fetch`` spans
(``runtime/executor.py``), less the ``storage_read`` spans inside them —
reads on the same thread, and reads the I/O thread served for the same
unit — on the host clock."""
import bisect

GATHER = ("gather", "regather", "snap_fetch", "loss_fetch", "grad_fetch")


def _union_within(intervals, a, b):
    """Length of the union of ``intervals`` clipped to ``[a, b)``."""
    out, end = 0.0, a
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, b)
        if e > s:
            out += e - s
            end = e
    return out


def read(r):
    ev = r.get("tracer_events")
    if r["job"] != "train" or not ev:
        return None
    by_tid, by_unit = {}, {}
    for e in ev:
        if e["name"] != "storage_read":
            continue
        iv = (e["ts"], e["ts"] + e["dur"])
        by_tid.setdefault(e["tid"], []).append(iv)
        args = e["args"] or {}
        if "stream" in args:
            by_unit.setdefault((args["stream"], args["seq"]), []).append(iv)
    # one thread's reads never overlap: sorted by start, sorted by end
    for reads in by_tid.values():
        reads.sort()
    starts = {t: [s for s, _ in v] for t, v in by_tid.items()}
    ends = {t: [e for _, e in v] for t, v in by_tid.items()}
    total = 0.0
    for e in ev:
        if e["name"] not in GATHER or e["ph"] != "X":
            continue
        a, b = e["ts"], e["ts"] + e["dur"]
        args = e["args"] or {}
        tid = e["tid"]
        mine = by_tid.get(tid, [])
        lo = bisect.bisect_right(ends.get(tid, []), a)
        hi = bisect.bisect_left(starts.get(tid, []), b)
        reads = mine[lo:hi] + by_unit.get((args.get("stream"),
                                           args.get("seq")), [])
        total += (b - a) - _union_within(reads, a, b)
    return total * 1e-6 / r["iters"] if total else None
