# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

  table1_engines     Table 1: per-epoch time across engines
  table2_scaling     Table 2: Kronecker graph-size scaling
  table3_cache       Table 3/§8.3: cache-size sensitivity + GRD-G/GRD-GC
  table4_partitioner Table 4/Fig10/11/App.O: partitioner memory & quality
  io_volume          §5/App.H: measured vs analytic I/O volume
  fig9_memory        Fig 9: host memory usage
  fig12_models       Fig 12: model-type/#layer sensitivity
  fig13_bandwidth    Fig 13b/§8.9: SSD bandwidth sensitivity + write volume
  roofline           §Roofline from the dry-run artifacts
"""
import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    help="substring filter on the table/figure tags")
    from benchmarks.common import add_obs_args, enable_compile_cache
    add_obs_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks import (
        fig9_memory, fig12_models, fig13_bandwidth, io_volume, roofline,
        table1_engines, table2_scaling, table3_cache, table4_partitioner,
    )

    mods = [
        ("table1", table1_engines), ("table2", table2_scaling),
        ("table3", table3_cache), ("table4", table4_partitioner),
        ("io_volume", io_volume), ("fig9", fig9_memory),
        ("fig12", fig12_models), ("fig13", fig13_bandwidth),
        ("roofline", roofline),
    ]
    only = args.only
    print("name,us_per_call,derived")
    failures = 0
    timings = {}
    for tag, mod in mods:
        if only and only not in tag:
            continue
        t0 = time.perf_counter()
        try:
            mod.main()
            timings[f"{tag}_s"] = time.perf_counter() - t0
            print(f"# {tag} done in {timings[f'{tag}_s']:.1f}s", flush=True)
        except Exception as e:
            failures += 1
            traceback.print_exc()
            print(f"{tag}/FAILED,0,{type(e).__name__}: {e}")
    if args.ledger and timings:
        # one suite record: per-module wall time (failed modules excluded
        # — a crash should not ledger a bogus duration)
        from benchmarks.common import ledger_append

        ledger_append(
            args.ledger, "bench_suite",
            dict(only=only, modules=sorted(k[:-2] for k in timings)),
            timings, watch={k: "lower" for k in timings},
            extra=dict(failures=failures),
        )
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    sys.path.insert(0, ".")  # allow `python benchmarks/run.py`
    main()
