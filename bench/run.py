#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, in order: build the cell (graph from ``bench/.cache`` or
generated, the program's partitioner and plan, data and weights from
``--seed``), compile every layer program, run one warm-up epoch or pass
(set-up ends here: ``setup_s``), then run whole epochs or passes until
``--seconds`` have passed (the window). After the window: the device's peak
memory, then the program's state is freed and the plain reference runs, and
the check compares what the window produced with it.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces the
window with the JAX profiler and reports its per-layer metrics. The last
lines of standard error are the compared numbers beside their limits; the
last line of standard output is the result as one JSON object. Off a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import harness  # noqa: E402
from harness import TRACE_DIR  # noqa: E402

ENVELOPES = {"epoch", "infer", "fwd_layer", "bwd_layer", "loss_layer"}


def _gap_labeller(events, sync_ts_us, window_start_ns):
    """Label a device idle gap with the program's span that covers most of
    it on the compute thread, mapped from the engine tracer's clock onto the
    profiler's through the instant taken as the window opened."""
    main = threading.get_ident()
    spans = []
    for ev in events:
        if ev["ph"] != "X" or ev["tid"] != main:
            continue
        a = window_start_ns + (ev["ts"] - sync_ts_us) * 1e3
        spans.append((a, a + ev["dur"] * 1e3, ev["name"]))

    def label(gap):
        best = {}
        for a, b, name in spans:
            ov = min(b, gap[1]) - max(a, gap[0])
            if ov > 0:
                best[name] = best.get(name, 0.0) + ov
        inner = {k: v for k, v in best.items() if k not in ENVELOPES}
        pick = inner or best
        return max(pick, key=pick.get) if pick else "host"

    return label


def run_window(cell, seconds: float, trace: bool, anon: bool):
    """Epochs or passes until ``seconds`` have passed; returns the record
    the metric readers read. ``anon`` samples the peak anonymous memory
    (only for cells that report it: the sampler reads ``smaps`` every
    50 ms)."""
    import jax

    harness.use_program()
    from repro.launch.compile_cache import CompileCounter

    c = cell.counters
    before = c.snapshot()
    tracer = c.tracer
    if trace:
        tracer.clear()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    rss = harness.AnonPeak().start() if anon else None
    iters, times = 0, []
    with CompileCounter() as cc:
        with jax.profiler.TraceAnnotation("bench_window"):
            t0 = time.perf_counter()
            tracer.instant("bench_sync")
            while True:
                with jax.profiler.TraceAnnotation("bench_iter"):
                    cell.step()
                iters += 1
                times.append(time.perf_counter() - t0)
                if times[-1] >= seconds:
                    break
            window_s = time.perf_counter() - t0
    host_peak = rss.stop() if rss else None
    sampler = (dict(samples=rss.samples, seconds=rss.sample_s) if rss
               else None)
    if trace:
        jax.profiler.stop_trace()
    after = c.snapshot()
    delta = {k: after[k] - before.get(k, 0) for k in after
             if isinstance(after[k], (int, float))}
    rec = dict(job=cell.job, iters=iters, window_s=window_s, ends=times,
               host_anon_peak_bytes=host_peak, anon_sampler=sampler,
               counters=delta,
               window_loads=cc.loads, window_compiles=cc.compiles,
               tracer_events=tracer.events() if trace else None)
    return rec


def reduce_trace(rec, cell, peaks):
    """Device numbers of the traced window, and the least seconds of the
    layer programs it ran."""
    import glob

    import flops
    import trace_reduce as tr

    files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return
    pd = tr.load(files[0])
    win = tr.host_spans(pd, "bench_window")
    events = rec["tracer_events"] or []
    sync = next((e["ts"] for e in events if e["name"] == "bench_sync"), None)
    label = (_gap_labeller(events, sync, win[0][0])
             if win and sync is not None else None)
    rec["device"] = tr.reduce_profile(pd, win[0] if win else None, label)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if peaks is None:
        return
    rec["least"] = {}
    for name, backward in (("jit_layer_apply", False),
                           ("jit_layer_vjp", True)):
        s, by_bytes, calls = flops.layer_costs(cell.model, cell.units,
                                               cell.dims, peaks, backward)
        rec["least"][name] = dict(seconds=s, bytes_bound=by_bytes,
                                  calls=calls)


def read_metrics(defs, rec):
    """``{name: {"value", "unit"}}`` of every declared metric whose reader
    found something to read."""
    out = {}
    for m in defs:
        value = harness.load_module("metrics", m["name"] + ".py").read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def check(got, ref, limits):
    """``(correct, {number: {value, limit}})`` over the numbers the cell's
    limits name."""
    nums = harness.compare(got, ref)
    checks = {k: {"value": nums[k], "limit": v} for k, v in limits.items()}
    return all(v["value"] <= v["limit"] for v in checks.values()), checks


def main(argv=None, rehearse=None) -> int:
    """``rehearse`` (tests only): a dict with ``config`` and ``traffic``
    overrides, ``limits``, and a ``workload`` for a cell that
    ``BENCHMARK.json`` does not declare; skips the look for a chip and
    returns the record instead of printing a result."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bm = harness.benchmark()
    wl = next((w for w in bm["workloads"] if w["name"] == args.workload),
              (rehearse or {}).get("workload"))
    if wl is None:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    if rehearse is None and (dev.platform != "tpu" or len(devs) < wl["chips"]):
        print(f"bench: needs {wl['chips']} TPU chip(s), found {device}",
              file=sys.stderr)
        return 2
    cache_dir = harness.set_compile_cache() if rehearse is None else None
    peaks_table = harness.load_json("peaks.json")
    peaks = peaks_table.get(dev.device_kind)
    if rehearse is None and peaks is None:
        print(f"bench: no peaks for device kind {dev.device_kind!r} in "
              "bench/peaks.json", file=sys.stderr)
        return 2
    config = harness.load_json("configs", wl["config"] + ".json")
    traffic = harness.load_json("workloads", wl["traffic"] + ".json")
    if rehearse is not None:
        config = {**config, **rehearse.get("config", {})}
        traffic = {**traffic, **rehearse.get("traffic", {})}
        limits = rehearse["limits"]
    else:
        limits = harness.load_json("limits", args.workload + ".json")["limits"]

    tracer = None
    if args.trace:
        harness.use_program()
        from repro.obs import Tracer
        tracer = Tracer(ring_events=1 << 20)
    # set-up in steps, each timed: process start to here, dataset graph,
    # partition and plan, data and weights, storage, programs, warm-up
    marks = [time.perf_counter()]
    cell = harness.Cell(args.workload, config, traffic)
    marks.append(time.perf_counter())
    cell.load(args.seed)
    marks.append(time.perf_counter())
    cell.open(tracer)
    marks.append(time.perf_counter())
    n_programs = cell.compile()
    marks.append(time.perf_counter())
    cell.step()                     # warm-up: loss, tree adds, the pipeline
    cell.reset_outputs()
    marks.append(time.perf_counter())
    setup_s = marks[-1] - T_START
    steps = ("start", "build", "data", "storage_init", "compile_or_load",
             "warmup")
    parts_s = dict(zip(steps, (b - a for a, b in zip([T_START] + marks,
                                                       marks))))
    parts_s.update(cell.build_s)
    print(f"[cell] {args.workload} config={wl['config']} job={cell.job} "
          f"nodes={cell.n} edges={cell.n_edges} dims={cell.dims} "
          f"parts={cell.plan.n_parts} buckets="
          f"{sorted({(u.r_pad, u.e_pad, u.d_pad) for u in cell.plan.units})} "
          f"cache_bytes={cell.cache_bytes} programs={n_programs} "
          f"setup_s={setup_s} setup_parts_s={parts_s}", flush=True)
    print(f"[host] storage_fs={harness.fs_type(harness.STORAGE_ROOT)} "
          f"mem_total_bytes={harness.proc_field('/proc/meminfo', 'MemTotal')} "
          f"cpus={os.cpu_count()} compile_cache={cache_dir}", flush=True)

    anon = any(m["name"] == "host_anon_peak_bytes" for m in
               harness.cell_metrics(bm, args.workload, "end_to_end"))
    rec = run_window(cell, args.seconds, bool(args.trace),
                     anon or rehearse is not None)
    rec["setup_s"] = setup_s
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use", 0)
    got = cell.outputs()
    cell.close()
    counters = rec["counters"]
    print(f"[window] iters={rec['iters']} window_s={rec['window_s']} "
          f"loads={rec['window_loads']} compiles={rec['window_compiles']} "
          f"storage_read_bytes={counters.get('storage_read_bytes')} "
          f"storage_write_bytes={counters.get('storage_write_bytes')} "
          f"h2d_bytes={counters.get('h2d_bytes')} "
          f"host_anon_peak_bytes={rec['host_anon_peak_bytes']} "
          f"anon_sampler={rec['anon_sampler']} "
          f"iter_ends_s={rec['ends']}", flush=True)

    t0 = time.perf_counter()
    ref = cell.reference()
    ok, checks = check(got, ref, limits)
    print(f"[check] reference_s={time.perf_counter() - t0}", flush=True)

    rec.update(model_flops=cell.model_flops(), peaks=peaks)
    if args.trace:
        reduce_trace(rec, cell, peaks)
        d = rec.get("device")
        if d:
            device["busy_s"], device["window_s"] = d["busy_s"], d["window_s"]
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(harness.cell_metrics(bm, args.workload, kind), rec)
    if rehearse is not None:
        rec.update(correct=ok, checks=checks, metrics=metrics)
        return rec
    result = {"correct": ok, "attempted": rec["iters"],
              "failed": 0 if ok else rec["iters"],
              "metrics": metrics, "device": device}
    d = rec.get("device")
    if args.trace and d:
        result["breakdown"] = {"device_ops": [list(x) for x in d["top_ops"]],
                               "idle_gaps": d["gaps"]}
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
