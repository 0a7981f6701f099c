#!/usr/bin/env python3
"""Charge every device-idle second of a traced window to the pipeline stage
that held it up, from the program's own spans on the profiler's clock.

An enabled program tracer mirrors each span into the JAX profiler, so the
``.xplane.pb`` holds them on the ``/host:CPU`` plane beside the device's
``XLA Ops``, with their arguments as event stats: integer ``stream`` (the
layer pass) and ``seq`` (the unit), ``layer`` and ``pass``. Host lines do
not tell threads apart, so a span's role comes from its name.

:func:`idle_by_stage` splits every idle interval of chip 0 inside a window:

- while the compute loop waits for unit (s, k) (``stall:compute_wait*``),
  the time goes to that unit's stage running then, first match in
  ``storage_read``, ``host_gather`` (``gather``, ``regather``,
  ``snap_fetch``, ``loss_fetch``, ``grad_fetch``: their reads are
  ``storage_read``), ``host_cache`` (``prefetch``, ``prefetch_bwd``,
  ``snap_prefetch``), ``h2d``; with none of them running, ``queued``;
- else to the compute loop's own span: ``d2h_wait``, ``drain``,
  ``write_submit`` or ``scatter``;
- else to ``host`` (dispatch and the loop's own Python).

Run on the chip, it measures one cell::

    python3 bench/stage_idle.py --workload <cell> --seed <n> --seconds <s>
        [--cost <windows>] [--keep <path>] [--nodes <n> --parts <p>]

It builds the cell as ``bench/run.py`` does, traces one window and prints
the ``[idle]`` line (seconds per label and per pass, the share of idle
with a label other than ``host``) and the ``[stages]`` line (the span
metrics of ``bench/metrics``). ``--cost`` then runs that many pairs of untraced
windows, one with the tracer off and one with it on but no profiler
session, in alternating order, and prints each window's seconds per
epoch. ``--keep`` copies the trace; ``--nodes``
and ``--parts`` cut the graph.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr

Interval = Tuple[float, float]

UNIT_STAGES = {
    "storage_read": "storage_read",
    "gather": "host_gather", "regather": "host_gather",
    "snap_fetch": "host_gather", "loss_fetch": "host_gather",
    "grad_fetch": "host_gather",
    "prefetch": "host_cache", "prefetch_bwd": "host_cache",
    "snap_prefetch": "host_cache",
    "h2d": "h2d",
}
UNIT_ORDER = ("storage_read", "host_gather", "host_cache", "h2d")
COMPUTE_SPANS = ("d2h_wait", "drain", "write_submit", "scatter")
WAIT_PREFIX = "stall:compute_wait"
LABELS = UNIT_ORDER + ("queued",) + COMPUTE_SPANS + ("host",)


def program_spans(pd) -> List[Tuple[str, float, float, dict]]:
    """``(name, start_ns, end_ns, stats)`` of the program's stage spans on
    the host plane: the unit stages, the compute loop's waits and its own
    spans."""
    keep = set(UNIT_STAGES) | set(COMPUTE_SPANS)
    out = []
    for plane in pd.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in keep or e.name.startswith(WAIT_PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def _length(ivs: Sequence[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def _subtract(ivs: Sequence[Interval], cut: Sequence[Interval]
              ) -> List[Interval]:
    """``ivs`` less ``cut``; both sorted and disjoint."""
    out: List[Interval] = []
    j = 0
    for a, b in ivs:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > a:
                out.append((a, cut[k][0]))
            a = max(a, cut[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


def _overlapping(spans, starts, ends, iv: Interval):
    """Spans of a sorted, disjoint list that overlap ``iv``."""
    return spans[bisect.bisect_right(ends, iv[0]):
                 bisect.bisect_left(starts, iv[1])]


def idle_by_stage(pd, window: Interval, chip: int = 0) -> Optional[Dict]:
    """Split the device-idle time of ``chip`` inside ``window`` (ns on the
    trace's clock) by the label of each instant (module docstring).
    Returns ``idle_s``, ``by_stage`` (seconds per label), ``by_pass``
    (seconds per label within each pass; ``none`` where no unit was
    named) and ``labelled_share`` (idle with a label other than ``host``
    over all idle), or None without a device plane."""
    chips = sorted((p for p in pd.planes
                    if p.name.startswith(tr.DEVICE_PREFIX)),
                   key=lambda p: p.name)
    if len(chips) <= chip:
        return None
    busy = tr.merge([c for _, *iv in tr._events(chips[chip], "XLA Ops")
                     if (c := tr._clip(tuple(iv), window))])
    gaps = _subtract([window], busy)
    waits, compute, units = [], [], {}
    for name, a, b, st in program_spans(pd):
        key = (st.get("stream"), st.get("seq"))
        if name.startswith(WAIT_PREFIX):
            waits.append((a, b, key, st.get("pass", "none")))
        elif name in UNIT_STAGES:
            units.setdefault(key, {}).setdefault(
                UNIT_STAGES[name], []).append((a, b))
        else:
            compute.append((a, b, name, st.get("pass", "none")))
    # the compute loop runs one span at a time; worker spans merge per label
    for labels in units.values():
        for label, ivs in labels.items():
            labels[label] = tr.merge(ivs)
    w_starts, w_ends = [w[0] for w in waits], [w[1] for w in waits]
    c_starts, c_ends = [c[0] for c in compute], [c[1] for c in compute]

    by_pass: Dict[str, Dict[str, float]] = {}

    def charge(label: str, pass_: str, ns: float) -> None:
        if ns > 0:
            d = by_pass.setdefault(pass_, {})
            d[label] = d.get(label, 0.0) + ns * 1e-9

    for gap in gaps:
        rest = [gap]
        for a, b, key, pass_ in _overlapping(waits, w_starts, w_ends, gap):
            wait = (max(a, gap[0]), min(b, gap[1]))
            left = [wait]
            rest = _subtract(rest, left)
            labels = units.get(key, {})
            for label in UNIT_ORDER:
                cover = [c for iv in labels.get(label, ())
                         if (c := tr._clip(iv, wait))]
                after = _subtract(left, cover)
                charge(label, pass_, _length(left) - _length(after))
                left = after
            charge("queued", pass_, _length(left))
        for label in COMPUTE_SPANS:
            for a, b, name, pass_ in _overlapping(compute, c_starts, c_ends,
                                                  gap):
                if name == label:
                    after = _subtract(rest, [(a, b)])
                    charge(label, pass_, _length(rest) - _length(after))
                    rest = after
        charge("host", "none", _length(rest))
    by_stage = {k: 0.0 for k in LABELS}
    for d in by_pass.values():
        for k, v in d.items():
            by_stage[k] += v
    idle_s = _length(gaps) * 1e-9
    return dict(idle_s=idle_s, by_stage=by_stage, by_pass=by_pass,
                labelled_share=(1.0 - by_stage["host"] / idle_s
                                if idle_s > 0 else None))


# ------------------------------------------------------------ chip command
def main(argv=None) -> int:
    import argparse
    import glob
    import json
    import os
    import shutil

    import harness
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cost", type=int, default=0,
                    help="pairs of untraced windows, tracer off and on")
    ap.add_argument("--keep", help="copy the traced window's .xplane.pb")
    ap.add_argument("--nodes", type=int)
    ap.add_argument("--parts", type=int)
    args = ap.parse_args(argv)

    bm = harness.benchmark()
    wl = next(w for w in bm["workloads"] if w["name"] == args.workload)
    config = harness.load_json("configs", wl["config"] + ".json")
    if args.nodes:
        config = {**config, "n_nodes": args.nodes}
    if args.parts:
        config = {**config, "n_parts": args.parts}
    traffic = harness.load_json("workloads", wl["traffic"] + ".json")
    harness.set_compile_cache()
    harness.use_program()
    from repro.obs import NULL_TRACER, Tracer

    tracer = Tracer(ring_events=1 << 20)
    cell = harness.Cell(args.workload, config, traffic)
    cell.load(args.seed)
    cell.open(tracer)
    cell.compile()
    cell.step()
    rec = run.run_window(cell, args.seconds, True, False)
    files = glob.glob(os.path.join(harness.TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if args.keep:
        shutil.copy(files[0], args.keep)
    pd = tr.load(files[0])
    win = tr.host_spans(pd, "bench_window")[0]
    dev = tr.reduce_profile(pd, win)
    split = idle_by_stage(pd, win)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    n = rec["iters"]
    if split is None:
        print(f"[idle] cell={args.workload} epochs={n} no device plane",
              flush=True)
    else:
        per_epoch = {k: v / n for k, v in split["by_stage"].items()}
        per_pass = {p: {k: v / n for k, v in d.items()}
                    for p, d in split["by_pass"].items()}
        print(f"[idle] cell={args.workload} epochs={n} "
              f"window_s={dev['window_s']} busy_s={dev['busy_s']} "
              f"idle_s={split['idle_s']} "
              f"labelled_share={split['labelled_share']} "
              f"by_stage_per_epoch={json.dumps(per_epoch)} "
              f"by_pass_per_epoch={json.dumps(per_pass)}", flush=True)
    stages = {}
    for name in ("storage_read_s.train", "host_gather_s.train",
                 "h2d_s.train", "scatter_s.train", "bwd_s.train",
                 "compute_wait_s.train"):
        stages[name] = harness.load_module("metrics", name + ".py").read(rec)
    c = rec["counters"]
    stages.update(storage_read_bytes=c.get("storage_read_bytes", 0) / n,
                  host_gather_bytes=c.get("host_gather_bytes", 0) / n,
                  h2d_bytes=c.get("h2d_bytes", 0) / n,
                  epoch_s=rec["window_s"] / n,
                  dropped_events=tracer.dropped)
    print(f"[stages] {json.dumps(stages)}", flush=True)
    for i in range(args.cost):
        # off-on, then on-off: a drift over the run cancels in the pairs
        for mode in ("off", "ring") if i % 2 == 0 else ("ring", "off"):
            cell.counters.tracer = (NULL_TRACER if mode == "off"
                                    else Tracer(ring_events=1 << 20))
            r = run.run_window(cell, args.seconds, False, False)
            print(f"[cost] pair={i} tracer={mode} epochs={r['iters']} "
                  f"epoch_s={r['window_s'] / r['iters']} "
                  f"spans={cell.counters.tracer.events_recorded}",
                  flush=True)
    cell.close()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
