"""Gather/aggregate + scatter-grad hot-path microbenchmark: Pallas vs numpy.

Times the three dispatchable kernels on engine-shaped inputs (padded work
units: sorted dst, pow2-bucketed row counts) through both dispatch paths:

- gather_rows      — the device regather of the staged partition stack
- gather_aggregate — the fused gather + GCN layer-aggregate
- scatter_add      — the deterministic ∇A write-back (vs the improved
                     numpy reference: reduceat segments / slice fast path)

This artifact is the evidence behind the dispatch layer's ``"auto"`` rule:
on a CPU backend Pallas runs in interpret mode (a compiled per-grid-step
emulation) and loses to vectorized numpy on every shape, so ``"auto"``
resolves to the reference path there — ``fallback`` in the JSON records
that decision per shape. On a TPU the v5e compiler refuses these kernels
(see ``repro/kernels/dispatch.py``), so ``"auto"`` resolves to the
reference path there too.

Run:  PYTHONPATH=src python benchmarks/kernel_hotpath.py [--smoke] [--json]
CSV:  kernel,us_per_call,detail
JSON: --json [PATH] writes per-shape timings (default
      BENCH_kernel_hotpath.json) for CI perf-trajectory artifacts.
"""
import argparse
import sys
import time


def _time_call(fn, warmup=2, iters=10):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench_shapes(shapes, iters):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.kernels.dispatch import scatter_add_rows_ref
    from repro.kernels.gather_scatter import (
        gather_aggregate, gather_aggregate_ref, gather_rows,
        gather_rows_ref, scatter_add,
    )

    interpret = jax.default_backend() == "cpu"
    rng = np.random.default_rng(0)
    rows_out = []
    for n, E, nd, D in shapes:
        table = rng.standard_normal((n, D), dtype=np.float32)
        erows = rng.integers(0, n, E).astype(np.int32)
        dst = np.sort(rng.integers(0, nd, E)).astype(np.int32)
        w = rng.standard_normal(E, dtype=np.float32)
        gidx = rng.integers(0, n, nd).astype(np.int32)
        srows = np.sort(rng.permutation(n)[: min(nd, n)]).astype(np.int64)
        svals = rng.standard_normal((srows.size, D), dtype=np.float32)

        jt = jnp.asarray(table)
        je, jd, jw = jnp.asarray(erows), jnp.asarray(dst), jnp.asarray(w)
        jg = jnp.asarray(gidx)
        jb, jr, jv = jnp.asarray(table), jnp.asarray(
            srows.astype(np.int32)), jnp.asarray(svals)

        gather_p = jax.jit(
            lambda t, i: gather_rows(t, i, interpret=interpret))
        agg_p = jax.jit(
            lambda t, e, d, ww: gather_aggregate(
                t, e, d, ww, nd, interpret=interpret))
        scat_p = jax.jit(
            lambda b, r, v: scatter_add(b, r, v, interpret=interpret))

        entry = dict(shape=dict(n_rows=n, n_edges=E, n_dst=nd, d=D))

        t_ref = _time_call(lambda: gather_rows_ref(table, gidx),
                           iters=iters)
        t_pal = _time_call(
            lambda: jax.block_until_ready(gather_p(jt, jg)), iters=iters)
        entry["gather_rows"] = dict(
            ref_us=t_ref, pallas_us=t_pal,
            speedup=t_ref / t_pal if t_pal else None)

        t_ref = _time_call(
            lambda: gather_aggregate_ref(table, erows, dst, w, nd),
            iters=iters)
        t_pal = _time_call(
            lambda: jax.block_until_ready(agg_p(jt, je, jd, jw)),
            iters=iters)
        entry["gather_aggregate"] = dict(
            ref_us=t_ref, pallas_us=t_pal,
            speedup=t_ref / t_pal if t_pal else None)

        buf = table.copy()
        t_ref = _time_call(
            lambda: scatter_add_rows_ref(buf, srows, svals), iters=iters)
        t_pal = _time_call(
            lambda: jax.block_until_ready(scat_p(jb, jr, jv)),
            iters=iters)
        entry["scatter_add"] = dict(
            ref_us=t_ref, pallas_us=t_pal,
            speedup=t_ref / t_pal if t_pal else None)

        # the dispatch decision this artifact justifies: on an interpret
        # (CPU) backend every kernel should fall back to the reference
        entry["fallback"] = dict(
            interpret=interpret,
            pallas_wins={
                k: entry[k]["speedup"] is not None
                and entry[k]["speedup"] > 1.0
                for k in ("gather_rows", "gather_aggregate", "scatter_add")
            },
        )
        rows_out.append(entry)
    return rows_out, interpret


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 3 iters — CI correctness gate")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--json", nargs="?", const="BENCH_kernel_hotpath.json",
                    default=None, metavar="PATH",
                    help="also write per-shape timings as JSON (CI artifact)")
    from benchmarks.common import add_obs_args, enable_compile_cache
    add_obs_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    # engine-shaped: (n_rows of staged stack, edges, dst rows, feature dim).
    # Sized for interpret mode on CPU (per-grid-step emulation scales with
    # the edge count); on a real TPU backend pass bigger shapes explicitly.
    shapes = [
        (1024, 4096, 512, 64),
        (2048, 8192, 1024, 64),
        (1024, 4096, 512, 128),
    ]
    if args.smoke:
        shapes = [(256, 1024, 128, 32)]
        args.iters = 3

    import jax

    rows, interpret = bench_shapes(shapes, args.iters)

    print("kernel,us_per_call,detail")
    for e in rows:
        s = e["shape"]
        tag = f"n={s['n_rows']} E={s['n_edges']} nd={s['n_dst']} d={s['d']}"
        for k in ("gather_rows", "gather_aggregate", "scatter_add"):
            r = e[k]
            print(f"{k}.ref,{r['ref_us']:.1f},{tag}")
            print(f"{k}.pallas,{r['pallas_us']:.1f},"
                  f"{tag} speedup={r['speedup']:.3f}x")
        wins = e["fallback"]["pallas_wins"]
        print(f"dispatch,0,{tag} interpret={interpret} "
              f"pallas_wins={sum(wins.values())}/{len(wins)}")

    config = dict(
        backend=jax.default_backend(), interpret=interpret,
        iters=args.iters, smoke=args.smoke,
        shapes=[list(s) for s in shapes],
    )
    if args.json:
        from benchmarks.common import write_bench_json

        payload = dict(
            config=config,
            kernels=rows,
            note=(
                "interpret-mode Pallas on CPU is an emulation; the "
                "reference path winning here is the measured basis for "
                "dispatch mode 'auto' resolving to 'reference' on CPU"
                if interpret else
                "compiled Pallas timings on an accelerator backend"
            ),
        )
        write_bench_json(args.json, payload, "kernel_hotpath")
    if args.ledger:
        from benchmarks.common import ledger_append

        # per-kernel, per-shape series: both dispatch paths' call time must
        # not creep up (lower is better on every key)
        headline, watch = {}, {}
        for i, e in enumerate(rows):
            for k in ("gather_rows", "gather_aggregate", "scatter_add"):
                headline[f"{k}_ref_us_{i}"] = e[k]["ref_us"]
                headline[f"{k}_pallas_us_{i}"] = e[k]["pallas_us"]
                watch[f"{k}_ref_us_{i}"] = "lower"
                watch[f"{k}_pallas_us_{i}"] = "lower"
        ledger_append(args.ledger, "kernel_hotpath", config, headline,
                      watch=watch)

    # sanity: on CPU the dispatch layer must NOT be told pallas wins; on an
    # accelerator we only report (CI runs CPU-only)
    if interpret:
        for e in rows:
            if any(e["fallback"]["pallas_wins"].values()):
                print("WARN,0,interpret-mode pallas beat numpy "
                      "(unexpected on CPU)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ".")  # allow `python benchmarks/kernel_hotpath.py`
    sys.exit(main())
