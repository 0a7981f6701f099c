#!/usr/bin/env python3
"""Bring-up smoke: the SSO training path on one TPU chip at gcn-igbm-3l width.

    python chip_smoke.py              # needs a TPU; exits 2 on any other platform
    python chip_smoke.py --rehearse   # the same phases at a tiny size, any backend

All phases run in this one process (a chip belongs to one process):

1. device: platform, kind, count, jax/libtpu versions, resolved kernel mode;
2. build from ``--seed``: a Kronecker graph at IGBM's average degree
   (262,144 nodes, 16 partitions), ``gcn-igbm-3l`` widths 1024-256-256-19,
   regather mode, a 256 MiB host cache (a quarter of the 1 GiB layer-0
   activations, so cache, regather and bypass all engage);
3. train through ``repro.launch.train.offload_train`` at pipeline depth 0
   and 2: one warm-up epoch, then 2 timed epochs;
4. checks (any failure exits 1): loss finite, depth 2 == depth 0 bitwise,
   SSO loss and gradients against whole-graph ``jax.value_and_grad``,
   storage bytes read > 0, no compiles in the timed epochs, cache pins and
   pool buffers returned, peak device memory;
5. one ``OffloadedInference`` pass over the same params, bit-identical to
   the training forward's final layer.

The last line is ``{"ok": true, "device": {...}}`` only when every check
passed on a TPU; ``--rehearse`` ends with ``"rehearsal_ok"`` instead and never
prints ``"ok"``. Nothing is read from untracked paths: graph, features,
labels and params come from ``--seed``, and the storage tier lives in a
temporary directory removed at exit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "gcn-igbm-3l"
# nodes/parts: the trained graph; ref_*: the whole-graph reference check,
# whose dense (edges x 1024) messages must fit the device at full width
SIZES = {
    "chip": dict(nodes=262_144, parts=16, cache_bytes=256 << 20,
                 ref_nodes=2048, ref_parts=8),
    "rehearse": dict(nodes=1024, parts=8, cache_bytes=1 << 20,
                     ref_nodes=512, ref_parts=4),
}
# SSO vs whole-graph, both under "highest" matmul precision: only float
# reassociation separates them (partition-wise segment sums and the
# per-partition dW accumulation), the bound tests/test_engine_equivalence.py
# holds the engine to
LOSS_RTOL = 1e-4
GRAD_MAX_REL = 5e-4


def _versions():
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "none"
    return out


def _max_rel_err(a_tree, b_tree):
    import jax
    import numpy as np

    return max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
              / (np.max(np.abs(np.asarray(a))) + 1e-12))
        for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree))
    )


def _reference_check(arch, size, seed):
    """SSO loss/grads vs whole-graph ``jax.value_and_grad`` on one small
    graph at full width, both under ``highest`` matmul precision."""
    import jax
    import jax.numpy as jnp

    from repro.launch.train import gnn_workload, offload_train
    from repro.models.gnn.layers import full_graph_loss, full_graph_topo

    wl = gnn_workload(arch, size["ref_nodes"], size["ref_parts"], seed)
    rg = wl.plan.ro.graph
    with jax.default_matmul_precision("highest"):
        r = offload_train(wl, depths=(2,))
        topo = full_graph_topo(rg.indptr, rg.indices, rg.n_nodes,
                               wl.plan.edge_weight)
        loss, grads = jax.value_and_grad(
            lambda p: full_graph_loss(wl.spec, p, jnp.asarray(wl.X), topo,
                                      jnp.asarray(wl.Y))
        )(wl.params)
        loss = float(loss)
    run = r["runs"][2]
    loss_err = abs(run["losses"][-1] - loss) / max(1.0, abs(loss))
    grad_err = _max_rel_err(grads, run["grads"])
    return wl, loss, run["losses"][-1], loss_err, grad_err


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase at a tiny size on any backend "
                         "(never reports ok)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (found {device}); refusing to run off "
              "the chip (use --rehearse)", file=sys.stderr)
        return 2

    import numpy as np

    from repro.configs import REGISTRY
    from repro.core import Counters, HostCache, StorageTier
    from repro.infer import OffloadedInference
    from repro.launch.compile_cache import CompileCounter, enable_compile_cache
    from repro.launch.train import gnn_workload, offload_train
    from repro.runtime import PipelineConfig

    cache_dir = enable_compile_cache()
    size = SIZES["rehearse" if args.rehearse else "chip"]
    arch = REGISTRY[ARCH].gnn
    checks = {}

    def check(name, ok, detail):
        checks[name] = bool(ok)
        print(f"[check] {name}: {'PASS' if ok else 'FAIL'} ({detail})",
              flush=True)

    # 1. device
    print(f"[device] {json.dumps(device)} versions={_versions()} "
          f"compile_cache={cache_dir}", flush=True)

    # 2. build
    t0 = time.perf_counter()
    wl = gnn_workload(arch, size["nodes"], size["parts"], args.seed)
    units = wl.plan.units
    cache_bytes = size["cache_bytes"]
    print(f"[build] {ARCH} dims={wl.dims} nodes={wl.n_nodes} "
          f"edges={wl.graph.n_edges} parts={wl.plan.n_parts} "
          f"max_r_pad={max(u.r_pad for u in units)} "
          f"max_e_pad={max(u.e_pad for u in units)} "
          f"cache_bytes={cache_bytes} act0_bytes={wl.act0_bytes} "
          f"working_set_bytes={wl.act_bytes} "
          f"host_prep_s={time.perf_counter() - t0:.3f}", flush=True)

    # 3. train at depth 0 and 2
    r = offload_train(wl, depths=(0, 2), epochs=2, warmup=1,
                      cache_bytes=cache_bytes)
    for d, run in r["runs"].items():
        print(f"[train] depth={d} epoch_s={run['epoch_s']} "
              f"losses={run['losses']} "
              f"precompile_s={run['precompile_s']:.3f} "
              f"warmup_compiles={run['warmup_compiles']} "
              f"warmup_compile_s={run['warmup_compile_s']:.3f} "
              f"warmup_cache_hits={run['warmup_cache_hits']} "
              f"timed_compiles={run['compiles']} "
              f"storage_read_bytes={run['storage_read_bytes']} "
              f"cache_evictions={run['cache_evictions']}", flush=True)

    # 4. checks
    runs = r["runs"]
    check("loss_finite", r["finite"], f"loss={r['loss']!r}")
    if not r["finite"]:
        return 1
    same_grads = all(
        np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(runs[0]["grads"]),
            jax.tree.leaves(runs[2]["grads"]))
    )
    check("depth2_equals_depth0_bitwise",
          r["pipeline_matches_serial"] and same_grads,
          f"losses {runs[0]['losses']} vs {runs[2]['losses']}, "
          f"grads equal={same_grads}")
    check("cache_below_working_set", cache_bytes < wl.act0_bytes,
          f"{cache_bytes} < {wl.act0_bytes}")
    check("storage_bytes_read",
          all(run["storage_read_bytes"] > 0 for run in runs.values()),
          str([run["storage_read_bytes"] for run in runs.values()]))
    check("no_compiles_in_timed_epochs",
          all(run["compiles"] == 0 for run in runs.values()),
          str([run["compiles"] for run in runs.values()]))
    check("pins_and_pool_returned",
          all(run["cache_pins"] == 0 and run["pool_outstanding"] == 0
              and run["pool_deferred"] == 0 for run in runs.values()),
          str([(run["cache_pins"], run["pool_outstanding"],
                run["pool_deferred"]) for run in runs.values()]))
    t0 = time.perf_counter()
    ref_wl, ref_loss, sso_loss, loss_err, grad_err = _reference_check(
        arch, size, args.seed)
    check("sso_matches_whole_graph",
          loss_err <= LOSS_RTOL and grad_err <= GRAD_MAX_REL,
          f"nodes={ref_wl.n_nodes} dims={ref_wl.dims} precision=highest "
          f"loss {sso_loss!r} vs {ref_loss!r} rel={loss_err:.3e} "
          f"(tol {LOSS_RTOL}), grad max_rel={grad_err:.3e} "
          f"(tol {GRAD_MAX_REL}), {time.perf_counter() - t0:.3f}s")

    # 5. inference over the same params
    c = Counters()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="sso-infer-") as root, \
            CompileCounter() as cc:
        st_ = StorageTier(root, counters=c)
        inf = OffloadedInference(
            wl.spec, wl.plan, wl.dims, st_, HostCache(cache_bytes, st_, c), c,
            pipeline=PipelineConfig(depth=2),
        )
        try:
            inf.initialize(wl.X)
            emb = st_.read_rows(inf.run(wl.params), 0, wl.n_nodes)
        finally:
            inf.close()
            st_.close()
    print(f"[infer] depth=2 seconds={time.perf_counter() - t0:.3f} "
          f"compiles={cc.compiles} cache_loads={cc.persistent_hits} "
          f"storage_read_bytes={c.storage_read_bytes}", flush=True)
    check("inference_bit_identical_to_training_forward",
          np.array_equal(emb, runs[2]["logits"]),
          f"shape={emb.shape} max_abs_diff="
          f"{float(np.max(np.abs(emb - runs[2]['logits']))):.3e}")

    stats = dev.memory_stats()
    if stats is None:
        check("peak_device_memory", args.rehearse,
              f"memory_stats() not reported on {dev.platform}")
    else:
        peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
        check("peak_device_memory", peak is not None,
              f"peak_bytes_in_use={peak} bytes_limit={limit}")

    passed = all(checks.values())
    if args.rehearse:
        print(json.dumps({"rehearsal_ok": passed, "device": device}))
        return 0 if passed else 1
    if not passed:
        print(f"chip_smoke: failed checks "
              f"{[k for k, v in checks.items() if not v]}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
