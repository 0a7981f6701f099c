"""Shared benchmark setup (graph + engine construction, timing) plus the
plumbing every BENCH producer goes through: the compile-cache placement its
``main()`` calls first (``enable_compile_cache``), provenance
stamping for ``BENCH_*.json`` artifacts, the ``--ledger`` append path into
``RUNS/ledger.jsonl`` (repro.obs.ledger)."""
from __future__ import annotations

import json
import tempfile
import time
from typing import Dict, Optional

import jax
import numpy as np

from repro.core import (
    Counters, HostCache, SSOEngine, StorageTier, build_plan, modeled_time,
)
from repro.core.costmodel import PAPER_WORKSTATION, gnn_epoch_flops
from repro.graph import (
    gcn_norm_coeffs, kronecker_graph, switching_aware_partition,
)
from repro.graph.csr import add_self_loops
from repro.graph.synthetic import random_features, random_labels
from repro.launch.compile_cache import enable_compile_cache  # noqa: F401
from repro.models.gnn.layers import get_gnn


def make_workload(
    n_nodes: int = 20000, avg_deg: int = 10, n_parts: int = 16,
    d_feat: int = 64, d_hidden: int = 64, n_layers: int = 3,
    n_classes: int = 10, seed: int = 0, model: str = "gcn",
):
    g = add_self_loops(kronecker_graph(n_nodes, avg_deg, seed=seed))
    res = switching_aware_partition(g, n_parts, max_iters=20, seed=seed)
    ew = gcn_norm_coeffs(g)
    plan = build_plan(g, res.parts, n_parts, edge_weight=ew)
    X = random_features(g.n_nodes, d_feat, seed)
    Y = random_labels(g.n_nodes, n_classes, seed)
    dims = [d_feat] + [d_hidden] * (n_layers - 1) + [n_classes]
    spec = get_gnn(model)
    params = spec.init(
        jax.random.PRNGKey(seed), d_feat, d_hidden, n_classes, n_layers
    )
    return dict(
        g=g, plan=plan, ew=ew, spec=spec, params=params, dims=dims,
        X=X[plan.ro.perm], Y=Y[plan.ro.perm], parts=res.parts,
    )


class EmulatedNVMeTier(StorageTier):
    """StorageTier with emulated device latency/bandwidth.

    The container's memmap tier is page-cached host memory — reads cost a
    memcpy, not an NVMe round trip — so storage-overlap studies (paper
    Fig. 13) would measure nothing. This tier sleeps per ranged op
    (``latency_us`` fixed + bytes/``gbps``); ``time.sleep`` releases the GIL
    and burns no CPU, exactly like a host thread blocked on a real NVMe
    completion, so the pipeline can genuinely hide it."""

    def __init__(self, root, counters=None, latency_us: float = 0.0,
                 gbps: float = 0.0, **kw):
        super().__init__(root, counters=counters, **kw)
        self.latency_s = latency_us * 1e-6
        self.bytes_per_s = gbps * 1e9

    def _delay(self, nbytes: int) -> None:
        d = self.latency_s
        if self.bytes_per_s > 0:
            d += nbytes / self.bytes_per_s
        if d > 0:
            time.sleep(d)

    # delays hang off the raw single-attempt ops, UNDER the tier's retry
    # layer — a retried op pays the device time again, like real hardware
    def _write_rows_once(self, name, row0, arr):
        self._delay(arr.nbytes)
        super()._write_rows_once(name, row0, arr)

    def _read_rows_once(self, name, row0, row1):
        out = super()._read_rows_once(name, row0, row1)
        self._delay(out.nbytes)
        return out

    def _read_rows_batched_once(self, requests):
        # a vectored submission pays the fixed per-op latency ONCE for the
        # whole batch (plus the bandwidth term for the total bytes) — the
        # win the pipeline's batched prefetch is after
        outs = super()._read_rows_batched_once(requests)
        if outs:
            self._delay(sum(o.nbytes for o in outs))
        return outs


def run_engine_epoch(
    wl: Dict, mode: str, cache_bytes: int, epochs: int = 1,
    overlap: bool = False, pipeline_depth: int = 0,
    storage_latency_us: float = 0.0, storage_gbps: float = 0.0,
    per_epoch_walls: bool = False, gather_workers: int = 1,
    transfer_stage: bool = True, device_slots: int = 2,
    trace: Optional[str] = None, zero_copy_h2d: bool = True,
):
    """Returns (wall_s_per_epoch, modeled_s_per_epoch, counters).

    ``pipeline_depth`` > 0 runs the async runtime (repro/runtime/);
    ``overlap`` is the legacy knob for depth=1. Nonzero
    ``storage_latency_us``/``storage_gbps`` emulate an NVMe tier.
    ``gather_workers`` shards the pipelined host gather;
    ``transfer_stage``/``device_slots`` control the async H2D/D2H stage.
    ``zero_copy_h2d`` selects the pinned-buffer aliasing H2D path.
    ``trace`` writes a Chrome/Perfetto timeline of the timed epochs (the
    warmup epoch's reset clears the trace ring, so the export shows steady
    state only)."""
    from repro.runtime import PipelineConfig

    c = Counters()
    if storage_latency_us > 0 or storage_gbps > 0:
        st_ = EmulatedNVMeTier(
            tempfile.mkdtemp(), counters=c,
            latency_us=storage_latency_us, gbps=storage_gbps,
        )
    else:
        st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    cache = HostCache(cache_bytes, st_, c)
    depth = pipeline_depth if pipeline_depth > 0 else (1 if overlap else 0)
    eng = SSOEngine(
        wl["spec"], wl["plan"], wl["dims"], st_, cache, c, mode=mode,
        pipeline=PipelineConfig(
            depth=depth, gather_workers=gather_workers,
            transfer_stage=transfer_stage, device_slots=device_slots,
            trace=trace, zero_copy_h2d=zero_copy_h2d,
        ),
    )
    eng.initialize(wl["X"])
    # warmup epoch compiles the jitted layer fns
    eng.run_epoch(wl["params"], wl["Y"])
    c.reset()
    walls = []
    for _ in range(epochs):
        t0 = time.perf_counter()
        loss, _ = eng.run_epoch(wl["params"], wl["Y"])
        walls.append(time.perf_counter() - t0)
    wall = sum(walls) / len(walls)
    # real vertex+edge FLOPs so the modeled t_compute term is non-zero
    flops = gnn_epoch_flops(wl["g"].n_nodes, wl["g"].n_edges, wl["dims"])
    mt = modeled_time(c, PAPER_WORKSTATION, flops=flops)
    eng.close()
    st_.close()
    if per_epoch_walls:
        return walls, mt, c, loss
    return wall, mt, c, loss


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}")


# --------------------------------------------------------------------------
# observability plumbing (shared by every BENCH producer)

#: schema of the stamped BENCH_*.json artifact envelope (NOT the ledger's
#: record schema — that is repro.obs.ledger.LEDGER_SCHEMA_VERSION)
BENCH_SCHEMA_VERSION = 1


def add_obs_args(ap):
    """Attach the shared observability flags to a bench's argparser."""
    ap.add_argument(
        "--ledger", nargs="?", const="RUNS/ledger.jsonl", default=None,
        metavar="PATH",
        help="append a schema-versioned run record to this JSONL ledger "
             "(default RUNS/ledger.jsonl) for the perf-regression sentinel",
    )
    return ap


def stamp_payload(payload: Dict, run_kind: str) -> Dict:
    """Stamp a BENCH_*.json payload with provenance: schema version,
    run kind, config fingerprint, git rev, wall-clock write time. The
    fingerprint hashes the payload's ``config`` section with the SAME
    function the ledger uses, so an artifact and its ledger record can be
    joined by fingerprint."""
    from repro.obs.ledger import config_fingerprint, git_revision

    out = dict(payload)
    out["schema_version"] = BENCH_SCHEMA_VERSION
    out["run_kind"] = str(run_kind)
    out["fingerprint"] = config_fingerprint(out.get("config", {}))
    rev = git_revision()
    if rev:
        out["git_rev"] = rev
    out["written_at"] = time.time()
    return out


def write_bench_json(path: str, payload: Dict, run_kind: str) -> Dict:
    """Stamp + write a bench artifact; prints the producers' uniform
    ``json,<path>,written`` CSV line."""
    payload = stamp_payload(payload, run_kind)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=str)
    print(f"json,{path},written")
    return payload


def ledger_append(path: str, run_kind: str, config: Dict, headline: Dict,
                  *, counters=None, watch=None, extra=None) -> Dict:
    """Build + append one run record to the JSONL ledger. The backend
    string is resolved here (the obs layer is stdlib-only and must not
    import jax)."""
    from repro.obs.ledger import RunLedger, make_record

    rec = make_record(
        run_kind, config, headline, counters=counters, watch=watch,
        backend=jax.default_backend(), extra=extra,
    )
    RunLedger(path).append(rec)
    print(f"ledger,{path},appended run_kind={run_kind} "
          f"fingerprint={rec['fingerprint']}")
    return rec

