"""Mixed train+serve soak under injected storage faults.

The fault-tolerance acceptance benchmark: a pipelined training run
(depth >= 1, sharded gathers, async H2D) on a :class:`~repro.core.faults.
FaultyTier` — seeded transient read/write errors, a scheduled torn write,
a scheduled latency spike, random returned-buffer corruption — with a
concurrent embedding-serving thread hammering the SAME tier, checked
against a fault-free serial run. Because every injected fault is
*transient* (retried reads/writes, CRC-verified re-reads), the loss
trajectory and final params must be BIT-IDENTICAL to the clean run, with
the recovery work visible in ``io.retries`` / ``io.faults_injected``.
The serve lane validates every lookup against the known table contents,
so a corruption that slipped past the CRC layer would fail loudly.

Run:  PYTHONPATH=src python benchmarks/fault_soak.py [--smoke] [--json]
JSON: --json [PATH] writes the soak report (default BENCH_fault_soak.json)
      for CI fault-tolerance artifacts. Exits non-zero if the faulted run
      diverges from the clean run or any serve lookup came back wrong.
"""
import argparse
import sys
import threading
import time


def _sgd(grads, params, lr):
    import jax

    return jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)


def _tree_bytes(tree):
    import jax
    import numpy as np

    return [np.asarray(leaf).tobytes()
            for leaf in jax.tree_util.tree_leaves(tree)]


def _run_training(wl, st_, c, epochs, depth, gather_workers, lr):
    """Train ``epochs`` full-graph epochs with plain SGD; returns
    ``(losses, final_params)``. Deterministic given the workload."""
    from repro.core import HostCache, SSOEngine
    from repro.runtime import PipelineConfig

    cache = HostCache(8 << 20, st_, c)
    eng = SSOEngine(
        wl["spec"], wl["plan"], wl["dims"], st_, cache, c, mode="regather",
        pipeline=PipelineConfig(
            depth=depth, gather_workers=gather_workers, transfer_stage=True,
        ),
    )
    losses = []
    try:
        eng.initialize(wl["X"])
        params = wl["params"]
        for _ in range(epochs):
            loss, grads = eng.run_epoch(params, wl["Y"])
            params = _sgd(grads, params, lr)
            losses.append(float(loss))
    finally:
        eng.close()
    return losses, params


def _serve_loop(srv, batches, expected, stop, out):
    """Background serving lane: replay zipf batches (cycling) until told to
    stop, validating every lookup against the ground-truth table."""
    import numpy as np

    i = 0
    while not stop.is_set():
        ids = batches[i % len(batches)]
        i += 1
        try:
            got = srv.lookup(ids)
            if not np.array_equal(got, expected[ids]):
                out["errors"].append(f"batch {i}: wrong rows returned")
        except Exception as e:  # any raise here fails the soak
            out["errors"].append(f"batch {i}: {type(e).__name__}: {e}")
        out["lookups"] += 1
        out["rows"] += int(ids.size)
        if out["errors"]:
            return


def run_soak(args):
    import tempfile

    import numpy as np

    from benchmarks.common import make_workload
    from repro.core import Counters, StorageTier
    from repro.core.faults import FaultPolicy, FaultyTier
    from repro.core.storage import RetryPolicy
    from repro.infer import EmbeddingServer, zipf_batches

    wl = make_workload(
        n_nodes=args.nodes, n_parts=args.parts, d_feat=args.hidden,
        d_hidden=args.hidden, n_layers=args.layers,
    )
    plan = wl["plan"]
    n = plan.n_nodes

    # ---- clean serial baseline ------------------------------------------
    c0 = Counters()
    st0 = StorageTier(tempfile.mkdtemp(), counters=c0)
    losses_clean, params_clean = _run_training(
        wl, st0, c0, args.epochs, depth=0, gather_workers=1, lr=args.lr,
    )
    st0.close()

    # ---- faulted pipelined run + concurrent serving ---------------------
    policy = FaultPolicy(
        seed=args.seed,
        read_error_rate=args.read_error_rate,
        write_error_rate=args.write_error_rate,
        read_corrupt_rate=args.read_corrupt_rate,
        torn_write_rate=args.torn_write_rate,
        latency_spike_rate=args.latency_spike_rate,
        latency_spike_s=0.002,
    )
    # guarantee the acceptance mix regardless of the random rates: at least
    # one torn write and one latency spike (indices are attempt-indexed;
    # initialize() issues many ops, so small indices always fire)
    policy.schedule("write", 3, "torn")
    policy.schedule("read", 2, "latency")
    c1 = Counters()
    st1 = FaultyTier(
        tempfile.mkdtemp(), policy=policy, counters=c1,
        verify_reads=True, retry=RetryPolicy(),
    )

    # ground-truth embedding table for the serve lane: row for ORIGINAL id
    # i is a deterministic function of i, stored in reordered row space
    rng = np.random.default_rng(args.seed)
    emb = (np.arange(n, dtype=np.float32)[:, None]
           + np.linspace(0.0, 1.0, args.hidden, dtype=np.float32)[None, :])
    st1.alloc("emb", (n, args.hidden), np.float32)
    st1.write_rows("emb", 0, emb[plan.ro.perm])

    srv = EmbeddingServer(st1, "emb", plan.ro, args.serve_cache_kb << 10,
                          counters=c1)
    batches = zipf_batches(rng, n, args.serve_batch, args.serve_batches,
                           args.zipf)
    serve_out = {"lookups": 0, "rows": 0, "errors": []}
    stop = threading.Event()
    t = threading.Thread(
        target=_serve_loop, args=(srv, batches, emb, stop, serve_out),
        name="soak-serve", daemon=True,
    )
    # live observability over the soak's counters: periodic one-line status
    # (a wedged lane shows up in seconds, not at soak end) and an optional
    # scrapeable /metrics endpoint
    sampler = server = None
    if args.status_interval > 0:
        from repro.obs.live import LiveSampler
        sampler = LiveSampler(c1, log_every_s=args.status_interval).start()
    if args.telemetry_port is not None:
        from repro.obs.live import TelemetryServer
        server = TelemetryServer(c1, port=args.telemetry_port).start()
    t0 = time.perf_counter()
    t.start()
    try:
        losses_faulty, params_faulty = _run_training(
            wl, st1, c1, args.epochs, depth=args.depth,
            gather_workers=args.gather_workers, lr=args.lr,
        )
    finally:
        stop.set()
        t.join(timeout=30)
        srv.close()
        if sampler is not None:
            sampler.stop()
        if server is not None:
            server.stop()
    wall = time.perf_counter() - t0
    st1.close()

    identical = (
        losses_clean == losses_faulty
        and _tree_bytes(params_clean) == _tree_bytes(params_faulty)
    )

    def _metric(counters, name):
        inst = counters.metrics.get(name)
        return float(inst.value) if inst is not None else 0.0

    kinds = sorted({f for _, _, f in policy.injected})
    return dict(
        losses_clean=losses_clean,
        losses_faulty=losses_faulty,
        identical=bool(identical),
        faults_injected=int(policy.n_injected),
        fault_kinds=kinds,
        io_retries=_metric(c1, "io.retries"),
        io_faults_injected=_metric(c1, "io.faults_injected"),
        io_deadline_misses=_metric(c1, "io.deadline_misses"),
        io_corruption_rereads=_metric(c1, "io.corruption_rereads"),
        serve_lookups=serve_out["lookups"],
        serve_rows=serve_out["rows"],
        serve_errors=serve_out["errors"],
        sampler_ticks=sampler.ticks if sampler is not None else 0,
        wall_s=wall,
    ), c1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=12000)
    ap.add_argument("--parts", type=int, default=12)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--depth", type=int, default=2,
                    help="pipeline lookahead for the faulted run")
    ap.add_argument("--gather-workers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--read-error-rate", type=float, default=0.01)
    ap.add_argument("--write-error-rate", type=float, default=0.01)
    ap.add_argument("--read-corrupt-rate", type=float, default=0.005)
    ap.add_argument("--torn-write-rate", type=float, default=0.002)
    ap.add_argument("--latency-spike-rate", type=float, default=0.002)
    ap.add_argument("--serve-cache-kb", type=int, default=256)
    ap.add_argument("--serve-batch", type=int, default=64)
    ap.add_argument("--serve-batches", type=int, default=50)
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--smoke", action="store_true",
                    help="small graph / short soak for CI")
    ap.add_argument("--json", nargs="?", const="BENCH_fault_soak.json",
                    default=None, metavar="PATH",
                    help="write the soak report as JSON")
    ap.add_argument("--status-interval", type=float, default=0.0,
                    metavar="SEC",
                    help="log a one-line live status every SEC seconds "
                         "during the soak (repro.obs.live sampler; 0 = off)")
    ap.add_argument("--telemetry-port", type=int, default=None,
                    metavar="PORT",
                    help="serve live Prometheus metrics on this port for "
                         "the duration of the soak (0 = ephemeral)")
    from benchmarks.common import add_obs_args, enable_compile_cache
    add_obs_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.nodes, args.parts, args.hidden = 3000, 6, 32
        args.layers, args.epochs = 2, 2
        args.serve_batches = 20
    if args.status_interval > 0:
        import logging
        logging.basicConfig(
            level=logging.INFO,
            format="%(asctime)s %(name)s %(message)s",
        )

    soak, c1 = run_soak(args)

    print(f"clean   losses: {soak['losses_clean']}")
    print(f"faulted losses: {soak['losses_faulty']}")
    print(
        f"identical={soak['identical']} "
        f"faults={soak['faults_injected']} ({','.join(soak['fault_kinds'])}) "
        f"retries={soak['io_retries']:.0f} "
        f"rereads={soak['io_corruption_rereads']:.0f} "
        f"serve={soak['serve_lookups']} lookups/"
        f"{soak['serve_rows']} rows "
        f"errors={len(soak['serve_errors'])} wall={soak['wall_s']:.2f}s"
    )

    config = dict(
        nodes=args.nodes, parts=args.parts, layers=args.layers,
        hidden=args.hidden, epochs=args.epochs, depth=args.depth,
        gather_workers=args.gather_workers, seed=args.seed,
        read_error_rate=args.read_error_rate,
        write_error_rate=args.write_error_rate,
        read_corrupt_rate=args.read_corrupt_rate,
        torn_write_rate=args.torn_write_rate,
        latency_spike_rate=args.latency_spike_rate,
        smoke=args.smoke,
    )
    if args.json:
        from benchmarks.common import write_bench_json

        write_bench_json(args.json, dict(config=config, soak=soak),
                         "fault_soak")
    if args.ledger:
        from benchmarks.common import ledger_append

        ledger_append(
            args.ledger, "fault_soak", config,
            dict(wall_s=soak["wall_s"],
                 faults_injected=soak["faults_injected"],
                 io_retries=soak["io_retries"],
                 serve_lookups=soak["serve_lookups"]),
            counters=c1, watch={"wall_s": "lower"},
        )

    if soak["serve_errors"]:
        print("FAIL: serve lane returned wrong/failed lookups:",
              *soak["serve_errors"][:5], sep="\n  ")
        return 1
    if not soak["identical"]:
        print("FAIL: faulted run diverged from the fault-free run")
        return 1
    if soak["faults_injected"] < 3:
        print("FAIL: soak injected too few faults to be meaningful")
        return 1
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ".")  # allow `python benchmarks/fault_soak.py`
    sys.exit(main())
