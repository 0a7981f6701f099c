"""Serial vs pipelined SSO engine: epoch wall-clock + stall/overlap breakdown.

The paper's headline mechanism (§5, Fig. 13) is hiding storage/host traffic
behind device compute. This benchmark runs the same workload through the
engine at pipeline depth 0 (strict serial) and depth N (async runtime:
prefetch → gather workers + aux grad fetch + write-behind), and reports
per-epoch wall time, the per-stage busy/stall accounting from Counters, the
overlapped fraction split into forward and backward passes, and the storage
read-op counts (the pipelined run batches per-unit prefetch reads into one
vectored submission, so it issues fewer ops for the same bytes). Loss
equality between the two runs is asserted — the pipeline must not change
the math.

Run:  PYTHONPATH=src python benchmarks/pipeline_overlap.py [--smoke] [--json]
CSV:  mode,ms_per_epoch,detail
JSON: --json [PATH] writes the full comparison (default
      BENCH_pipeline_overlap.json) for CI perf-trajectory artifacts.
"""
import argparse
import sys


def run_pair(wl, depth, epochs, cache_mb, mode, latency_us, gbps, workers,
             transfer=True, device_slots=2, trace=None):
    from benchmarks.common import run_engine_epoch

    out = {}
    for d in (0, depth):
        walls, mt, c, loss = run_engine_epoch(
            wl, mode, cache_mb << 20, epochs=epochs, pipeline_depth=d,
            storage_latency_us=latency_us, storage_gbps=gbps,
            per_epoch_walls=True, gather_workers=workers,
            transfer_stage=transfer, device_slots=device_slots,
            # only the pipelined run is worth a timeline
            trace=trace if d == depth else None,
        )
        # min-of-epochs: robust to noisy-neighbour CPU spikes on shared boxes
        out[d] = dict(
            wall=min(walls), mean_wall=sum(walls) / len(walls), loss=loss,
            counters=c, overlap=c.overlap_summary(sum(walls)),
        )
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--parts", type=int, default=12)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--gather-workers", type=int, default=1,
                    help="parallel host-gather workers in the pipelined run")
    ap.add_argument("--device-slots", type=int, default=2,
                    help="device-side staging slots for the transfer stage "
                         "(2 = double buffer, 1 = serialized H2D)")
    ap.add_argument("--no-transfer", action="store_true",
                    help="disable the async H2D/D2H device-transfer stage")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--cache-mb", type=int, default=8)
    ap.add_argument("--mode", default="regather",
                    choices=["regather", "snapshot"])
    ap.add_argument("--storage-latency-us", type=float, default=80.0,
                    help="emulated NVMe per-op latency (0 = raw page cache)")
    ap.add_argument("--storage-gbps", type=float, default=1.0,
                    help="emulated NVMe bandwidth (0 = raw page cache)")
    ap.add_argument("--raw", action="store_true",
                    help="no storage emulation (page-cached memmap; on a "
                         "CPU-only box there is little latency to hide)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload, asserts correctness + accounting")
    ap.add_argument("--json", nargs="?", const="BENCH_pipeline_overlap.json",
                    default=None, metavar="PATH",
                    help="also write the comparison as JSON (CI artifact)")
    ap.add_argument("--trace", nargs="?", const="TRACE_pipeline_overlap.json",
                    default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace_event timeline of "
                         "the pipelined run's timed epochs (CI artifact; "
                         "open in ui.perfetto.dev)")
    from benchmarks.common import add_obs_args, enable_compile_cache
    add_obs_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        # cache well below the activation working set so offloading (and
        # therefore the pipeline's storage traffic) genuinely engages
        args.nodes, args.parts, args.layers = 2000, 6, 2
        args.hidden, args.epochs, args.cache_mb = 32, 2, 1
    if args.raw:
        args.storage_latency_us = args.storage_gbps = 0.0

    from benchmarks.common import make_workload

    wl = make_workload(
        n_nodes=args.nodes, n_parts=args.parts, d_feat=args.hidden,
        d_hidden=args.hidden, n_layers=args.layers,
    )
    res = run_pair(wl, args.depth, args.epochs, args.cache_mb, args.mode,
                   args.storage_latency_us, args.storage_gbps,
                   args.gather_workers, transfer=not args.no_transfer,
                   device_slots=args.device_slots, trace=args.trace)
    ser, pipe = res[0], res[args.depth]
    if args.trace:
        print(f"trace,{args.trace},written")

    # the pipeline must not change the math
    assert ser["loss"] == pipe["loss"], (
        f"loss mismatch: serial {ser['loss']} vs pipelined {pipe['loss']}"
    )

    ov = pipe["overlap"]
    speedup = ser["wall"] / pipe["wall"] if pipe["wall"] > 0 else float("inf")
    ser_ops = ser["counters"].storage_read_ops
    pipe_ops = pipe["counters"].storage_read_ops
    print("mode,ms_per_epoch,detail")
    print(f"serial,{ser['wall'] * 1e3:.1f},"
          f"depth=0 mean={ser['mean_wall'] * 1e3:.1f}ms "
          f"read_ops={ser_ops}")
    print(
        f"pipelined,{pipe['wall'] * 1e3:.1f},"
        f"depth={args.depth} workers={args.gather_workers} "
        f"slots={args.device_slots} "
        f"xfer={'off' if args.no_transfer else 'on'} "
        f"mean={pipe['mean_wall'] * 1e3:.1f}ms "
        f"speedup={speedup:.2f}x "
        f"overlapped_frac={ov['overlapped_frac']:.3f} "
        f"fwd={ov['overlapped_frac_fwd']:.3f} "
        f"bwd={ov['overlapped_frac_bwd']:.3f} "
        f"xfer_frac={ov['overlapped_frac_xfer']:.3f} "
        f"busy_s={ov['busy_seconds']:.3f} "
        f"compute_wait_s={ov['compute_wait_seconds']:.3f} "
        f"read_ops={pipe_ops}"
    )
    c = pipe["counters"]
    for k, v in sorted(c.stage_busy_seconds.items()):
        print(f"stage_busy.{k},{v * 1e3:.1f},per-{args.epochs}-epochs")
    for k, v in sorted(c.stage_stall_seconds.items()):
        print(f"stage_stall.{k},{v * 1e3:.1f},per-{args.epochs}-epochs")
    plan = wl["plan"]
    ws = [plan.upcoming_parts(i, args.depth).size
          for i in range(len(plan.schedule))]
    print(f"prefetch_working_set,{sum(ws) / len(ws):.1f},"
          f"mean source partitions staged ahead at depth {args.depth}")

    config = dict(
        nodes=args.nodes, parts=args.parts, layers=args.layers,
        hidden=args.hidden, depth=args.depth,
        gather_workers=args.gather_workers, epochs=args.epochs,
        cache_mb=args.cache_mb, mode=args.mode,
        storage_latency_us=args.storage_latency_us,
        storage_gbps=args.storage_gbps,
        transfer_stage=not args.no_transfer,
        device_slots=args.device_slots,
    )
    headline = dict(
        wall_s=pipe["wall"], serial_wall_s=ser["wall"], speedup=speedup,
        overlapped_frac=ov["overlapped_frac"],
        overlapped_frac_fwd=ov["overlapped_frac_fwd"],
        overlapped_frac_bwd=ov["overlapped_frac_bwd"],
        overlapped_frac_xfer=ov["overlapped_frac_xfer"],
        read_ops=pipe_ops,
    )
    # the sentinel's marching orders: wall must not creep up, overlap must
    # not creep down (speedup is derived, read_ops is informational)
    watch = {"wall_s": "lower", "overlapped_frac": "higher"}

    if args.json:
        from benchmarks.common import write_bench_json

        payload = dict(
            config=config,
            serial=dict(
                wall_s=ser["wall"], mean_wall_s=ser["mean_wall"],
                storage_read_ops=ser_ops,
                storage_read_bytes=ser["counters"].storage_read_bytes,
            ),
            pipelined=dict(
                wall_s=pipe["wall"], mean_wall_s=pipe["mean_wall"],
                storage_read_ops=pipe_ops,
                storage_read_bytes=c.storage_read_bytes,
                overlap=ov,
                stage_busy_s=dict(sorted(c.stage_busy_seconds.items())),
                stage_stall_s=dict(sorted(c.stage_stall_seconds.items())),
            ),
            speedup=speedup,
            read_ops_ratio=(pipe_ops / ser_ops) if ser_ops else None,
        )
        write_bench_json(args.json, payload, "pipeline_overlap")
    if args.ledger:
        from benchmarks.common import ledger_append

        ledger_append(args.ledger, "pipeline_overlap", config, headline,
                      counters=c, watch=watch)

    ok = True
    if ov["overlapped_frac"] <= 0.0:
        print("WARN,0,no overlap achieved", file=sys.stderr)
        ok = not args.smoke and ok  # hard-fail only in smoke mode
    # warn-only: both depend on thread timing (a loaded 1-2 core runner can
    # serialize workers behind the main loop / race extra cache loads), so
    # they must not flake CI — the deterministic properties are asserted in
    # tests/test_runtime.py instead
    if ov["overlapped_frac_bwd"] <= 0.0:
        print("WARN,0,no backward overlap achieved", file=sys.stderr)
    if not args.no_transfer and ov["overlapped_frac_xfer"] <= 0.0:
        print("WARN,0,no H2D/D2H transfer overlap achieved", file=sys.stderr)
    if pipe_ops >= ser_ops:
        print(f"WARN,{pipe_ops},batched prefetch did not cut read ops "
              f"(serial={ser_ops})", file=sys.stderr)
    if args.smoke and ov["busy_seconds"] <= 0.0:
        print("FAIL,0,pipeline workers recorded no busy time",
              file=sys.stderr)
        ok = False
    if args.smoke and not args.no_transfer:
        busy = pipe["counters"].stage_busy_seconds
        if busy.get("h2d", 0.0) <= 0.0 or busy.get("d2h", 0.0) <= 0.0:
            print("FAIL,0,transfer stage recorded no H2D/D2H busy time",
                  file=sys.stderr)
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.path.insert(0, ".")  # allow `python benchmarks/pipeline_overlap.py`
    sys.exit(main())
