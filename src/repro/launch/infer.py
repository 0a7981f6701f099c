"""Inference launcher: ``python -m repro.launch.infer --arch <id> [...]``.

The serving-side sibling of ``repro.launch.train --offload``: runs
storage-offloaded layer-wise inference (repro/infer/) for a GNN arch on the
workload ``repro.launch.train.gnn_workload`` builds from it (``--nodes``
cuts the node count), checks the pipelined engine against the serial one
(bit-identical embedding table) and the served lookups against a dense
whole-graph forward, then reports the EmbeddingServer's hit/latency stats.

Exit status 0 iff every check passes — CI uses this as the inference smoke.
"""
from __future__ import annotations

import argparse
import sys


def _infer_smoke(
    wl,
    depth: int,
    cache_bytes: int = None,
    serve_cache_kb: int = 256,
    queries: int = 8,
    batch: int = 64,
    fp16: bool = False,
    gather_workers: int = 1,
    trace: str = None,
    telemetry_port: int = None,
    ledger: str = None,
) -> dict:
    """Drive OffloadedInference (serial + pipelined) and the
    EmbeddingServer over a :class:`repro.launch.train.GNNWorkload`;
    returns the check/stat dict. ``cache_bytes`` defaults to a quarter of
    the layer-0 activations.

    ``telemetry_port`` serves live Prometheus metrics over the pipelined
    run's counters (the serve-side gauges included); ``ledger`` appends a
    ``run_kind="infer_smoke"`` record to that JSONL ledger."""
    import tempfile
    import time

    import jax
    import numpy as np

    from repro.core import Counters, HostCache, StorageTier
    from repro.infer import EmbeddingServer, OffloadedInference
    from repro.models.gnn.layers import full_graph_forward, full_graph_topo
    from repro.runtime import PipelineConfig

    plan, dims, spec, params, X = wl.plan, wl.dims, wl.spec, wl.params, wl.X
    n_nodes = wl.n_nodes
    if cache_bytes is None:
        cache_bytes = wl.act0_bytes // 4
    store_dtype = np.float16 if fp16 else None

    tables = {}
    stats = {}
    wall = 0.0
    c = None
    for d in sorted({0, depth}):
        c = Counters()
        root = tempfile.TemporaryDirectory(prefix="sso-infer-")
        st_ = StorageTier(root.name, counters=c)
        cache = HostCache(cache_bytes, st_, c)
        inf = OffloadedInference(
            spec, plan, dims, st_, cache, c,
            pipeline=PipelineConfig(
                depth=d, gather_workers=gather_workers,
                # trace the requested depth only (the other iteration is
                # the serial equivalence check)
                trace=trace if d == depth else None,
            ),
            store_dtype=store_dtype,
        )
        server = None
        if telemetry_port is not None and d == depth:
            from repro.obs.live import TelemetryServer
            server = TelemetryServer(c, port=telemetry_port).start()
        inf.initialize(X)
        t0 = time.perf_counter()
        name = inf.run(params)
        if d == depth:
            wall = time.perf_counter() - t0
        tables[d] = st_.read_rows(name, 0, n_nodes)
        inf.close()
        if d != depth:
            st_.close()
            root.cleanup()
            continue
        # serve the pipelined run's table and check against a dense forward
        # (sharing the run's counters, so lookups land in the same metrics
        # registry and — when tracing — the same timeline)
        srv = EmbeddingServer(st_, name, plan.ro, serve_cache_kb << 10,
                              counters=c)
        rg = plan.ro.graph
        topo = full_graph_topo(
            rg.indptr, rg.indices, rg.n_nodes, plan.edge_weight
        )
        ref = np.asarray(full_graph_forward(spec, params, X, topo))
        rng = np.random.default_rng(0)
        tol = 5e-2 if fp16 else 1e-3
        serve_ok = True
        for _ in range(queries):
            ids = rng.integers(0, n_nodes, batch)
            got = srv.lookup(ids).astype(np.float32)
            want = ref[plan.ro.inv_perm[ids]]
            if not np.allclose(got, want, rtol=tol, atol=tol):
                serve_ok = False
        stats = srv.stats()
        stats["serve_matches_dense"] = serve_ok
        srv.close()
        if trace and c.tracer.enabled:
            # re-export: the engine's close() wrote the inference timeline
            # before the serving lookups above recorded their spans
            c.tracer.export_chrome_trace(trace)
        if server is not None:
            server.stop()
        st_.close()
        root.cleanup()

    if ledger:
        from repro.obs.ledger import RunLedger, make_record
        RunLedger(ledger).append(make_record(
            "infer_smoke",
            dict(arch=wl.arch.name, n_nodes=n_nodes, depth=depth,
                 cache_bytes=cache_bytes,
                 serve_cache_kb=serve_cache_kb, queries=queries,
                 batch=batch, fp16=fp16, gather_workers=gather_workers),
            dict(wall_s=wall,
                 hit_rate=float(stats.get("hit_rate", 0.0)),
                 p99_ms=float(stats.get("p99_ms", 0.0))),
            counters=c, watch={"wall_s": "lower", "p99_ms": "lower"},
            backend=jax.default_backend(),
        ))

    pipeline_matches = bool(
        np.array_equal(tables[0], tables[max(tables)])
    )
    finite = all(bool(np.all(np.isfinite(
        t.astype(np.float32)))) for t in tables.values())
    return dict(
        finite=finite,
        pipeline_matches_serial=pipeline_matches,
        depth=depth,
        fp16=fp16,
        **stats,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="a GNN arch id (e.g. gcn-cora); the model and "
                         "widths come from its GNNArch")
    ap.add_argument("--nodes", type=int, default=None,
                    help="graph nodes (default: the dataset's)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="async pipeline lookahead (0 = serial engine)")
    ap.add_argument("--gather-workers", type=int, default=1)
    ap.add_argument("--cache-mb", type=int, default=None,
                    help="host-cache budget for the inference engine "
                         "(default: a quarter of the layer-0 activations)")
    ap.add_argument("--serve-cache-kb", type=int, default=256,
                    help="dedicated host-cache budget for the "
                         "EmbeddingServer")
    ap.add_argument("--queries", type=int, default=8,
                    help="lookup batches to issue against the server")
    ap.add_argument("--batch", type=int, default=64,
                    help="node ids per lookup batch")
    ap.add_argument("--fp16", action="store_true",
                    help="store activations/embeddings in float16 on "
                         "storage (compute stays float32)")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="write a Chrome/Perfetto trace_event timeline of "
                         "the inference + serving run (ui.perfetto.dev)")
    ap.add_argument("--telemetry-port", type=int, default=None,
                    metavar="PORT",
                    help="serve live Prometheus metrics (GET /metrics) for "
                         "the duration of the run (0 = ephemeral)")
    ap.add_argument("--ledger", nargs="?", const="RUNS/ledger.jsonl",
                    default=None, metavar="PATH",
                    help="append a run record to this JSONL ledger "
                         "(repro.obs.ledger)")
    args = ap.parse_args()
    if args.trace:
        import logging
        logging.basicConfig(level=logging.INFO,
                            format="%(name)s %(message)s")

    from repro.configs import REGISTRY
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import gnn_workload

    arch = REGISTRY[args.arch]
    if arch.family != "gnn":
        print(f"{args.arch}: inference requires a GNN arch "
              f"(family={arch.family})")
        sys.exit(2)
    enable_compile_cache()
    wl = gnn_workload(arch.gnn, args.nodes)
    r = _infer_smoke(
        wl, args.pipeline_depth,
        cache_bytes=(args.cache_mb << 20
                     if args.cache_mb is not None else None),
        serve_cache_kb=args.serve_cache_kb, queries=args.queries,
        batch=args.batch, fp16=args.fp16,
        gather_workers=args.gather_workers, trace=args.trace,
        telemetry_port=args.telemetry_port, ledger=args.ledger,
    )
    print(f"{args.arch} infer smoke: {r}")
    if args.trace:
        print(f"trace written to {args.trace}")
    ok = (
        r.get("finite")
        and r.get("pipeline_matches_serial", True)
        and r.get("serve_matches_dense", True)
    )
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
