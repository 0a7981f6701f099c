"""Embedding-serving throughput vs host-cache budget on the emulated-NVMe
tier.

The serving-side companion of pipeline_overlap.py: storage-offloaded
inference (repro/infer/) produces the final-layer embedding table on an
EmulatedNVMeTier, then an EmbeddingServer answers zipf-skewed query traffic
at several dedicated-cache budgets. Reported per budget: queries/sec (and
rows/sec), row-granular cache hit-rate, and p50/p99 lookup latency — the
cache-budget → tail-latency trade-off a deployment sizes against.

Run:  PYTHONPATH=src python benchmarks/serving_throughput.py [--smoke] [--json]
CSV:  budget_kb,qps,detail
JSON: --json [PATH] writes the sweep (default BENCH_serving_throughput.json)
      for CI perf-trajectory artifacts.
"""
import argparse
import sys
import time


def run_sweep(args):
    import numpy as np

    from benchmarks.common import EmulatedNVMeTier, make_workload
    from repro.core import Counters, HostCache
    from repro.infer import EmbeddingServer, OffloadedInference, zipf_batches
    from repro.runtime import PipelineConfig

    wl = make_workload(
        n_nodes=args.nodes, n_parts=args.parts, d_feat=args.hidden,
        d_hidden=args.hidden, n_layers=args.layers,
    )
    plan = wl["plan"]
    c = Counters()
    import tempfile
    st_ = EmulatedNVMeTier(
        tempfile.mkdtemp(), counters=c,
        latency_us=args.storage_latency_us, gbps=args.storage_gbps,
    )
    inf = OffloadedInference(
        wl["spec"], plan, wl["dims"], st_,
        HostCache(args.infer_cache_mb << 20, st_, c), c,
        pipeline=PipelineConfig(depth=args.depth, trace=args.trace),
        store_dtype=np.float16 if args.fp16 else None,
    )
    inf.initialize(wl["X"])
    t0 = time.perf_counter()
    table = inf.run(wl["params"])
    t_infer = time.perf_counter() - t0
    inf.close()
    n = plan.n_nodes
    table_bytes = st_.shape(table)[0] * st_.shape(table)[1] \
        * st_.dtype(table).itemsize

    # pre-generate identical query traffic for every budget
    rng = np.random.default_rng(0)
    batches = zipf_batches(rng, n, args.batch, args.queries, args.zipf)

    results = []
    for budget_kb in args.budgets:
        # share the run's counters: lookup latency lands in the same
        # metrics registry and — when tracing — the same timeline
        srv = EmbeddingServer(st_, table, plan.ro, budget_kb << 10,
                              counters=c)
        for ids in batches[: args.warmup]:    # warm the cache + code paths
            srv.lookup(ids)
        srv.reset_stats()   # hit-rate/latency report steady state only
        t0 = time.perf_counter()
        for ids in batches[args.warmup:]:
            srv.lookup(ids)
        wall = time.perf_counter() - t0
        timed = len(batches) - args.warmup
        s = srv.stats()
        srv.close()
        results.append(dict(
            budget_kb=budget_kb,
            budget_frac_of_table=budget_kb * 1024 / table_bytes,
            qps=timed / wall if wall > 0 else float("inf"),
            rows_per_s=timed * args.batch / wall if wall > 0 else float("inf"),
            hit_rate=s["hit_rate"],
            p50_ms=s["p50_ms"],
            p99_ms=s["p99_ms"],
            mean_ms=s["mean_ms"],
            block_rows=s["block_rows"],
        ))
    if args.trace and c.tracer.enabled:
        # re-export: the engine's close() wrote only the inference part;
        # this picks up the serving lookup spans recorded since
        c.tracer.export_chrome_trace(args.trace)
    st_.close()
    return results, dict(
        table=table, table_bytes=table_bytes, infer_seconds=t_infer,
        n_nodes=n, dim=wl["dims"][-1],
    ), c


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--parts", type=int, default=12)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2,
                    help="inference pipeline lookahead")
    ap.add_argument("--infer-cache-mb", type=int, default=8)
    ap.add_argument("--budgets", type=lambda s: [int(x) for x in s.split(",")],
                    default=[64, 256, 1024],
                    help="comma-separated EmbeddingServer cache budgets, KiB")
    ap.add_argument("--queries", type=int, default=400,
                    help="lookup batches per budget (incl. warmup)")
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--fp16", action="store_true")
    ap.add_argument("--storage-latency-us", type=float, default=80.0,
                    help="emulated NVMe per-op latency")
    ap.add_argument("--storage-gbps", type=float, default=1.0,
                    help="emulated NVMe bandwidth")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload + sanity assertions")
    ap.add_argument("--json", nargs="?",
                    const="BENCH_serving_throughput.json", default=None,
                    metavar="PATH",
                    help="also write the sweep as JSON (CI artifact)")
    ap.add_argument("--trace", nargs="?",
                    const="TRACE_serving_throughput.json", default=None,
                    metavar="PATH",
                    help="write a Chrome/Perfetto trace_event timeline of "
                         "the inference build + serving sweep")
    from benchmarks.common import add_obs_args, enable_compile_cache
    add_obs_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.nodes, args.parts, args.layers = 2000, 6, 2
        args.hidden, args.queries, args.warmup = 32, 60, 10
        args.budgets = [16, 256]

    results, meta, c = run_sweep(args)

    print("budget_kb,qps,detail")
    for r in results:
        print(f"{r['budget_kb']},{r['qps']:.1f},"
              f"cache={r['budget_frac_of_table']:.2f}x-table "
              f"rows/s={r['rows_per_s']:.0f} hit={r['hit_rate']:.3f} "
              f"p50={r['p50_ms']:.3f}ms p99={r['p99_ms']:.3f}ms")
    print(f"table,{meta['table_bytes']},"
          f"{meta['n_nodes']}x{meta['dim']} built in "
          f"{meta['infer_seconds']:.2f}s (emulated NVMe)")

    config = dict(
        nodes=args.nodes, parts=args.parts, layers=args.layers,
        hidden=args.hidden, depth=args.depth,
        budgets_kb=args.budgets, queries=args.queries,
        warmup=args.warmup, batch=args.batch, zipf=args.zipf,
        fp16=args.fp16,
        storage_latency_us=args.storage_latency_us,
        storage_gbps=args.storage_gbps,
    )
    # flat per-budget headline keys so the sentinel tracks each budget's
    # qps / tail / hit-rate as its own series
    headline, watch = {}, {}
    for r in results:
        b = r["budget_kb"]
        headline[f"qps_b{b}"] = r["qps"]
        headline[f"p99_ms_b{b}"] = r["p99_ms"]
        headline[f"hit_rate_b{b}"] = r["hit_rate"]
        watch[f"qps_b{b}"] = "higher"
        watch[f"p99_ms_b{b}"] = "lower"
        watch[f"hit_rate_b{b}"] = "higher"

    if args.json:
        from benchmarks.common import write_bench_json

        write_bench_json(
            args.json, dict(config=config, table=meta, sweep=results),
            "serving_throughput",
        )
    if args.ledger:
        from benchmarks.common import ledger_append

        ledger_append(args.ledger, "serving_throughput", config, headline,
                      counters=c, watch=watch)
    if args.trace:
        print(f"trace,{args.trace},written")

    ok = True
    if len(results) < 2:
        print("FAIL,0,need >= 2 cache budgets for the sweep",
              file=sys.stderr)
        ok = False
    if args.smoke:
        hits = [r["hit_rate"] for r in results]
        if not all(0.0 <= h <= 1.0 for h in hits):
            print("FAIL,0,hit rates out of range", file=sys.stderr)
            ok = False
        if hits != sorted(hits):
            # larger budget must not serve a colder cache (same traffic)
            print(f"WARN,0,hit rate not monotone in budget: {hits}",
                  file=sys.stderr)
        if any(r["p50_ms"] > r["p99_ms"] for r in results):
            print("FAIL,0,p50 > p99", file=sys.stderr)
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.path.insert(0, ".")  # allow `python benchmarks/serving_throughput.py`
    sys.exit(main())
