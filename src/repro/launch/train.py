"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

``--offload`` trains a GNN arch through the storage-offloading engine
(``SSOEngine`` -> ``ForwardRunner`` -> pipeline runtime -> storage). The
workload comes from the arch: model id, hidden width and depth from its
``GNNArch``, feature width and classes from the dataset it names
(``configs/base.py`` ``GNN_DATASETS``), on a Kronecker graph generated from
a seed at the dataset's average degree. ``--nodes`` cuts the node count,
the only dimension that may be cut; the host cache defaults to a quarter of
the layer-0 activations, so cache, regather and bypass all engage. The run
is made at pipeline depth 0 (the serial engine) and at ``--pipeline-depth``,
and exits 1 unless the loss is finite and the two losses are bitwise equal.

``--smoke`` runs the reduced full-graph config in memory; without either,
the cell is lowered on the production mesh by ``repro.launch.dryrun``.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Dict, List, Optional, Sequence


@dataclasses.dataclass
class GNNWorkload:
    """One SSO training job: graph, plan, model and data, in plan order."""

    arch: object          # GNNArch
    graph: object         # CSRGraph (self loops added)
    plan: object          # PartitionPlan
    spec: object          # GNNSpec
    dims: List[int]       # [d_feat, d_hidden, ..., d_out]
    params: list
    X: object             # (n, d_feat) float32 features, plan order
    Y: object             # (n,) int32 labels, plan order

    @property
    def n_nodes(self) -> int:
        return self.plan.n_nodes

    @property
    def act0_bytes(self) -> int:
        """Layer-0 (input feature) activations on storage."""
        return self.n_nodes * self.dims[0] * 4

    @property
    def act_bytes(self) -> int:
        """Every layer's activations — the working set of an epoch."""
        return self.n_nodes * sum(self.dims) * 4


def gnn_workload(arch, n_nodes: Optional[int] = None, n_parts: int = 16,
                 seed: int = 0) -> GNNWorkload:
    """Build the SSO workload of a ``GNNArch`` at its dataset's widths,
    with ``n_nodes`` (default: the dataset's) nodes."""
    import jax

    from repro.configs.base import GNN_DATASETS
    from repro.configs.builders import gnn_dims
    from repro.core import build_plan
    from repro.graph import (
        gcn_norm_coeffs, kronecker_graph, switching_aware_partition,
    )
    from repro.graph.csr import add_self_loops
    from repro.graph.synthetic import random_features, random_labels
    from repro.models.gnn.layers import get_gnn

    ds = GNN_DATASETS[arch.dataset]
    n = int(n_nodes or ds["n_nodes"])
    avg_degree = max(1, round(ds["n_edges"] / ds["n_nodes"]))
    g = add_self_loops(kronecker_graph(n, avg_degree, seed=seed))
    res = switching_aware_partition(g, n_parts, max_iters=8, seed=seed)
    plan = build_plan(g, res.parts, n_parts, edge_weight=gcn_norm_coeffs(g))
    dims = gnn_dims(arch, ds["d_feat"], ds["classes"])
    spec = get_gnn(arch.model)
    params = spec.init(jax.random.PRNGKey(seed), dims[0], arch.d_hidden,
                       dims[-1], arch.n_layers)
    X = random_features(g.n_nodes, dims[0], seed)[plan.ro.perm]
    Y = random_labels(g.n_nodes, dims[-1], seed)[plan.ro.perm]
    return GNNWorkload(arch, g, plan, spec, dims, params, X, Y)


def offload_train(
    wl: GNNWorkload,
    depths: Sequence[int] = (0, 2),
    epochs: int = 1,
    warmup: int = 0,
    cache_bytes: Optional[int] = None,
    gather_workers: int = 1,
    transfer_stage: bool = True,
    device_slots: int = 2,
    trace: Optional[str] = None,
    telemetry_port: Optional[int] = None,
    ledger: Optional[str] = None,
) -> Dict:
    """Run ``warmup + epochs`` SSO epochs (loss and gradients at
    ``wl.params``) at each pipeline depth.

    The layer programs are compiled in parallel first
    (``SSOEngine.compile_programs``). Returns ``pipeline_matches_serial``
    (every epoch's loss bitwise equal across depths) and, per depth under
    ``runs``: the epoch losses, the last epoch's gradients, the timed
    epochs' seconds, compiles before and in the timed epochs, storage bytes
    read, the cache pins and pool buffers left after close, and the
    final-layer activations ``logits``.
    ``trace``, ``telemetry_port`` and ``ledger`` apply to the last depth.
    ``cache_bytes`` defaults to a quarter of the layer-0 activations."""
    import gc
    import tempfile

    import jax
    import numpy as np

    from repro.core import Counters, HostCache, SSOEngine, StorageTier
    from repro.launch.compile_cache import CompileCounter
    from repro.runtime import PipelineConfig

    if cache_bytes is None:
        cache_bytes = wl.act0_bytes // 4
    depths = sorted(set(depths))
    last = depths[-1]
    L = len(wl.dims) - 1
    runs = {}
    c = None
    for d in depths:
        c = Counters()
        run = dict(losses=[], epoch_s=[])
        with tempfile.TemporaryDirectory(prefix="sso-train-") as root:
            st_ = StorageTier(root, counters=c)
            cache = HostCache(cache_bytes, st_, c)
            eng = SSOEngine(
                wl.spec, wl.plan, wl.dims, st_, cache, c,
                pipeline=PipelineConfig(
                    depth=d, gather_workers=gather_workers,
                    transfer_stage=transfer_stage, device_slots=device_slots,
                    trace=trace if d == last else None,
                ),
            )
            server = None
            if telemetry_port is not None and d == last:
                from repro.obs.live import TelemetryServer
                server = TelemetryServer(c, port=telemetry_port).start()
            try:
                eng.initialize(wl.X)
                with CompileCounter() as cw:
                    t0 = time.perf_counter()
                    eng.compile_programs(wl.params)
                    run["precompile_s"] = time.perf_counter() - t0
                    for _ in range(warmup):
                        loss, grads = eng.run_epoch(wl.params, wl.Y)
                        run["losses"].append(loss)
                with CompileCounter() as ct:
                    for _ in range(epochs):
                        t0 = time.perf_counter()
                        loss, grads = eng.run_epoch(wl.params, wl.Y)
                        jax.block_until_ready(grads)
                        run["epoch_s"].append(time.perf_counter() - t0)
                        run["losses"].append(loss)
                run["logits"] = st_.read_rows(f"act{L}", 0, wl.n_nodes)
            finally:
                if server is not None:
                    server.stop()
                eng.close()
                st_.close()
            # zero-copy staged buffers return to the pool when their device
            # arrays die; collect any cycle still holding one
            gc.collect()
            run.update(
                grads=grads,
                warmup_compiles=cw.compiles,
                warmup_compile_s=cw.compile_s,
                warmup_cache_hits=cw.persistent_hits,
                compiles=ct.loads, compile_s=ct.compile_s,
                storage_read_bytes=c.storage_read_bytes,
                cache_evictions=c.cache_evictions,
                cache_pins=cache.total_pins,
                pool_outstanding=eng._rt.pool.outstanding,
                pool_deferred=eng._rt.pool.deferred_pending,
            )
        runs[d] = run
        finite = all(np.isfinite(l) for l in run["losses"]) and all(
            bool(np.all(np.isfinite(g))) for g in jax.tree.leaves(grads)
        )
        if not finite:
            return dict(finite=False, loss=run["losses"][-1], depth=d,
                        runs=runs)
    if ledger:
        from repro.obs.ledger import RunLedger, make_record
        RunLedger(ledger).append(make_record(
            "train_offload_smoke",
            dict(arch=wl.arch.name, n_nodes=wl.n_nodes, depth=last,
                 gather_workers=gather_workers,
                 transfer_stage=transfer_stage, device_slots=device_slots),
            dict(wall_s=float(np.mean(runs[last]["epoch_s"])),
                 loss=float(runs[last]["losses"][-1])),
            counters=c, watch={"wall_s": "lower"},
            backend=jax.default_backend(),
        ))
    first = runs[depths[0]]["losses"]
    return dict(
        finite=True,
        loss=runs[last]["losses"][-1],
        serial_loss=first[-1],
        pipeline_matches_serial=all(
            runs[d]["losses"] == first for d in depths
        ),
        depth=last,
        runs=runs,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="run the reduced config end-to-end on CPU")
    ap.add_argument("--offload", action="store_true",
                    help="train a GNN arch through the storage-offloading "
                         "engine (SSO pipeline runtime)")
    ap.add_argument("--nodes", type=int, default=None,
                    help="--offload graph nodes (default: the dataset's)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="async pipeline lookahead for --offload "
                         "(0 = serial engine)")
    ap.add_argument("--gather-workers", type=int, default=1,
                    help="parallel host-gather workers for --offload")
    ap.add_argument("--device-slots", type=int, default=2,
                    help="device staging slots for the transfer stage")
    ap.add_argument("--no-transfer-stage", action="store_true",
                    help="disable the async H2D/D2H device-transfer stage")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="write a Chrome/Perfetto trace_event timeline of "
                         "the --offload run (open in ui.perfetto.dev)")
    ap.add_argument("--telemetry-port", type=int, default=None,
                    metavar="PORT",
                    help="serve live Prometheus metrics (GET /metrics) for "
                         "the duration of the --offload run (0 = ephemeral)")
    ap.add_argument("--ledger", nargs="?", const="RUNS/ledger.jsonl",
                    default=None, metavar="PATH",
                    help="append a run record to this JSONL ledger "
                         "(repro.obs.ledger)")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    if args.trace:
        import logging
        logging.basicConfig(level=logging.INFO,
                            format="%(name)s %(message)s")

    from repro.configs import REGISTRY
    from repro.launch.compile_cache import enable_compile_cache

    if args.list:
        for name in REGISTRY:
            arch = REGISTRY[name]
            shapes = ", ".join(
                s + (" [skip]" if c.skip else "")
                for s, c in arch.cells.items()
            )
            print(f"{name:24s} [{arch.family}] {shapes}")
        return

    arch = REGISTRY[args.arch]
    if args.offload:
        if arch.family != "gnn":
            print(f"{args.arch}: --offload requires a GNN arch "
                  f"(family={arch.family})")
            sys.exit(2)
        enable_compile_cache()
        wl = gnn_workload(arch.gnn, args.nodes)
        r = offload_train(
            wl, depths=(0, args.pipeline_depth),
            gather_workers=args.gather_workers,
            transfer_stage=not args.no_transfer_stage,
            device_slots=args.device_slots, trace=args.trace,
            telemetry_port=args.telemetry_port, ledger=args.ledger,
        )
        summary = {k: v for k, v in r.items() if k != "runs"}
        print(f"{args.arch} offload: nodes={wl.n_nodes} dims={wl.dims} "
              f"{summary}")
        if args.trace:
            print(f"trace written to {args.trace}")
        ok = r.get("finite") and r.get("pipeline_matches_serial", True)
        sys.exit(0 if ok else 1)
    if args.smoke:
        enable_compile_cache()
        r = arch.smoke()
        print(f"{args.arch} smoke: {r}")
        sys.exit(0 if r.get("finite") else 1)

    # full config: verify the cell lowers on the production mesh (the
    # dry-run forces the CPU backend itself, so the child never takes a chip)
    print(
        f"{args.arch}: full-config execution requires the TPU mesh; "
        f"running dry-run lowering instead (use --smoke for CPU execution)."
    )
    import subprocess

    shape = args.shape or arch.runnable_shapes()[0]
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", args.arch, "--shape", shape, "--mesh", "single",
    ]
    sys.exit(subprocess.call(cmd))


if __name__ == "__main__":
    main()
