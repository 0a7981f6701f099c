"""One benchmark cell, built from its files and driven through the program.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; both
are data, found by name:

- ``bench/configs/<config>.json``: the model, widths, dataset shape, node
  count, partitions, the dataset graph's seed, and ``rehearse``: the
  ``n_nodes`` and ``n_parts`` of the CPU rehearsals in ``bench/tests``
  (a chip run ignores it);
- ``bench/models/<model>.py``: the model's plain reference. ``init``,
  ``model_flops``, ``forward``, ``backward``, ``fwd_cost`` and ``bwd_cost``
  are each told the layer's position as ``activate``, false on the output
  layer alone;
- ``bench/workloads/<traffic>.json``: the job (``train``: SSO epochs;
  ``infer``: offloaded inference passes), engine mode, host-cache budget as
  a fraction of named activations, pipeline depth;
- ``bench/limits/<cell>.json``: the cell's configuration and traffic, and
  the limit of each number the output check compares;
- ``bench/metrics/<metric>.py``: one reader per metric.

The graph comes from ``bench/graphgen.py`` and the run's ``--seed`` makes
features, labels and weights; the program (``src/repro``) partitions, plans
and runs the job through ``SSOEngine`` or ``OffloadedInference``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import threading
import time
from typing import Dict

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")
STORAGE_ROOT = os.path.join(CACHE, "storage")
TRACE_DIR = os.path.join(CACHE, "trace")

# a parameter leaf whose reference gradient norm is under this share of the
# median leaf's is left out of the gradient comparison: it is nought to
# rounding, and its relative gap measures round-off alone
GRAD_FLOOR = 1e-3


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bm: dict, cell: str, kind: str):
    """The metrics of ``kind`` (``end_to_end`` or ``per_layer``) that
    ``cell`` reports, as declared in ``BENCHMARK.json``."""
    return [m for m in bm[kind] if cell in m.get("workloads", [cell])]


def use_program():
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def set_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    keeping every program, so a cell's later runs compile nothing."""
    import jax

    path = os.path.join(CACHE, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def fs_type(path: str) -> str:
    """Filesystem type of the mount that holds ``path``."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            parts = line.split()
            mnt, typ = parts[1], parts[2]
            if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                    and len(mnt) > len(best):
                best, kind = mnt, typ
    return kind


def proc_field(path: str, key: str) -> int:
    """A ``Key: <n> kB`` field of a /proc file, in bytes."""
    with open(path) as f:
        for line in f:
            if line.startswith(key + ":"):
                return int(line.split()[1]) * 1024
    return 0


_ANON = re.compile(rb"^Anonymous:\s+(\d+) kB", re.M)


def anon_bytes() -> int:
    """Anonymous resident bytes of this process: ``RssAnon`` where the
    kernel reports it, else the sum of ``Anonymous`` over ``smaps_rollup``
    or ``smaps`` (gVisor reports no ``RssAnon``)."""
    n = proc_field("/proc/self/status", "RssAnon")
    if n:
        return n
    for name in ("/proc/self/smaps_rollup", "/proc/self/smaps"):
        try:
            with open(name, "rb") as f:
                return 1024 * sum(int(v) for v in _ANON.findall(f.read()))
        except OSError:
            continue
    return 0


class AnonPeak:
    """Peak anonymous resident memory of this process (``anon_bytes``),
    sampled every 50 ms between ``start()`` and ``stop()``. Anonymous
    memory only: the storage tier's memory-mapped file pages would
    otherwise count. ``sample_s`` is the host time the samples took."""

    def __init__(self, period_s: float = 0.05):
        self.period_s = period_s
        self.peak = 0
        self.samples, self.sample_s = 0, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-rss")

    def _sample(self) -> None:
        t0 = time.perf_counter()
        self.peak = max(self.peak, anon_bytes())
        self.samples += 1
        self.sample_s += time.perf_counter() - t0

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            self._sample()

    def start(self) -> "AnonPeak":
        self._sample()
        self._thread.start()
        return self

    def stop(self) -> int:
        self._stop.set()
        self._thread.join()
        self._sample()
        return self.peak


def make_data(model_mod, n: int, dims, seed: int):
    """Features ``(n, dims[0])`` float32, labels ``(n,)`` int32 and weights
    from ``seed``, on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        kx, ky, kp = jax.random.split(key, 3)
        x = jax.random.normal(kx, (n, dims[0]), jnp.float32) * 0.1
        y = jax.random.randint(ky, (n,), 0, dims[-1], jnp.int32)
        L = len(dims) - 1
        keys = jax.random.split(kp, L)
        params = [model_mod.init(keys[l], dims[l], dims[l + 1], l < L - 1)
                  for l in range(L)]
        return x, y, params

    seed = int(seed)    # may exceed 32 bits: folded in as two words
    return gen(np.uint32(seed & 0xFFFFFFFF),
               np.uint32((seed >> 32) & 0xFFFFFFFF))


def cached_partition(cfg: dict, partition) -> np.ndarray:
    """The partition vector of the configuration's graph, computed by
    ``partition()`` once per checkout and then loaded from ``bench/.cache``:
    it depends on the graph, ``n_parts`` and ``graph_seed`` alone."""
    path = os.path.join(
        CACHE, f"parts-kron-n{cfg['n_nodes']}-d{cfg['avg_degree']}"
        f"-s{cfg['graph_seed']}-p{cfg['n_parts']}.npy")
    if os.path.exists(path):
        return np.load(path)
    parts = np.asarray(partition())
    os.makedirs(CACHE, exist_ok=True)
    tmp = path + ".part.npy"
    np.save(tmp, parts)
    os.replace(tmp, path)
    return parts


class Cell:
    """A cell's dataset, plan and job. The plan depends on the configuration
    alone, so every seed runs the same shapes."""

    def __init__(self, name: str, config: dict, traffic: dict):
        use_program()
        from repro.core import build_plan
        from repro.graph import gcn_norm_coeffs, switching_aware_partition
        from repro.graph.csr import CSRGraph
        from repro.models.gnn.layers import get_gnn

        from graphgen import cell_graph

        self.name, self.cfg, self.traffic = name, config, traffic
        self.job = traffic["job"]
        self.model = load_module("models", config["model"] + ".py")
        self.dims = ([config["d_feat"]]
                     + [config["d_hidden"]] * (config["n_layers"] - 1)
                     + [config["classes"]])
        t0 = time.perf_counter()
        self.indptr, self.indices = cell_graph(config, CACHE)
        n = self.n = self.indptr.shape[0] - 1
        self.n_edges = int(self.indices.shape[0])
        g = CSRGraph(self.indptr, self.indices, n)
        t1 = time.perf_counter()
        parts = cached_partition(
            config, lambda: switching_aware_partition(
                g, config["n_parts"], max_iters=8,
                seed=config["graph_seed"]).parts)
        t2 = time.perf_counter()
        self.plan = build_plan(g, parts, config["n_parts"],
                               edge_weight=gcn_norm_coeffs(g))
        self.build_s = dict(graph=t1 - t0, partition=t2 - t1,
                            plan=time.perf_counter() - t2)
        self.spec = get_gnn(config["model"])
        self.units = [(u.n_dst, u.n_req, u.n_edges) for u in self.plan.units]
        act = {"act0": n * self.dims[0] * 4, "all": n * sum(self.dims) * 4}
        self.cache_bytes = int(traffic["cache_fraction"]
                               * act[traffic["cache_of"]])
        self.data = None
        self._open = None

    def model_flops(self) -> float:
        """Model FLOPs of one epoch (forward and backward) or pass, on real
        nodes and edges; regather recompute is not counted."""
        from flops import gnn_model_flops

        return gnn_model_flops(self.model, self.dims, self.n, self.n_edges,
                               train=self.job == "train")

    # ----------------------------------------------------------------- data
    def load(self, seed: int) -> None:
        """Features, labels and weights of ``seed``; the program gets them
        in its plan's node order."""
        x, y, params = make_data(self.model, self.n, self.dims, seed)
        perm = self.plan.ro.perm
        x, y = np.asarray(x), np.asarray(y)
        self.data = dict(x=x, y=y, params=params, xp=x[perm], yp=y[perm])

    # ------------------------------------------------------------------ job
    def open(self, tracer=None) -> None:
        """Storage, host cache and the job's engine, fed the loaded data."""
        from repro.core import Counters, HostCache, SSOEngine, StorageTier
        from repro.infer import OffloadedInference
        from repro.runtime import PipelineConfig

        shutil.rmtree(STORAGE_ROOT, ignore_errors=True)
        c = Counters()
        if tracer is not None:
            c.tracer = tracer
        st = StorageTier(STORAGE_ROOT, counters=c)
        cache = HostCache(self.cache_bytes, st, c)
        pipe = PipelineConfig(depth=self.traffic["pipeline_depth"])
        if self.job == "train":
            eng = SSOEngine(self.spec, self.plan, self.dims, st, cache, c,
                            mode=self.traffic["mode"], pipeline=pipe)
        else:
            eng = OffloadedInference(self.spec, self.plan, self.dims, st,
                                     cache, c, pipeline=pipe, keep_input=True)
        self._open = dict(counters=c, storage=st, engine=eng, outs=[])
        eng.initialize(self.data["xp"])

    @property
    def counters(self):
        return self._open["counters"]

    def compile(self) -> int:
        """Compile every layer program of the plan's shape buckets in
        parallel before the warm-up."""
        import jax

        from repro.runtime.forward import compile_parallel, layer_apply

        eng, params = self._open["engine"], self.data["params"]
        if self.job == "train":
            return eng.compile_programs(params)
        L = len(self.dims) - 1
        jobs = []
        for l in range(L):
            for u in eng.runner.shape_buckets():
                ga = jax.ShapeDtypeStruct((u.r_pad, self.dims[l]), np.float32)
                jobs.append((layer_apply, (params[l], ga, u.topo),
                             dict(apply=self.spec.apply_layer,
                                  activate=l < L - 1)))
        return compile_parallel(jobs)

    def step(self) -> None:
        """One SSO epoch, or one inference pass over every node; both
        return with every result on the host or on storage."""
        eng = self._open["engine"]
        if self.job == "train":
            loss, grads = eng.run_epoch(self.data["params"], self.data["yp"])
            self._open["outs"].append((loss, grads))
        else:
            self._open["outs"].append(eng.run(self.data["params"]))

    def outputs(self) -> dict:
        """What the window produced, in the dataset's node order."""
        st, L = self._open["storage"], len(self.dims) - 1
        name = f"act{L}" if self.job == "train" else self._open["outs"][-1]
        out_plan = st.read_rows(name, 0, self.n)
        out = np.empty_like(out_plan)
        out[self.plan.ro.perm] = out_plan
        res = {"out": out}
        if self.job == "train":
            res["losses"] = [loss for loss, _ in self._open["outs"]]
            res["grads"] = self._open["outs"][-1][1]
        return res

    def reset_outputs(self) -> None:
        self._open["outs"].clear()

    def close(self) -> None:
        if self._open is None:
            return
        try:
            self._open["engine"].close()
        finally:
            self._open["storage"].close()
            self._open = None
            gc.collect()

    # ---------------------------------------------------------------- check
    def reference(self, control=None, node_w=None) -> dict:
        """The plain reference on this seed's data, or a control."""
        from reference import run

        d = self.data
        res = run(self.model, self.indptr, self.indices, d["x"], d["y"],
                  d["params"], control, train=self.job == "train",
                  node_w=node_w)
        if "loss" in res:
            res["losses"] = [res["loss"]]
        return res


# ----------------------------------------------------------- the comparison
def _norm(a) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()))


def compare(got: dict, ref: dict) -> Dict[str, float]:
    """The numbers the output check compares:

    - ``out``: the final-layer output of every node, ``|got - ref| / |ref|``;
    - ``out_row``: the worst node's gap, ``|got_i - ref_i|`` over the larger
      of ``|ref_i|`` and the median node's norm;
    - training also ``loss``: the worst epoch's relative loss gap, and
      ``grad``: the worst parameter leaf's ``|got - ref|`` over the larger of
      its reference norm and the median leaf's, leaving out leaves whose
      reference gradient is under ``GRAD_FLOOR`` of the median leaf's.
    """
    g, r = np.asarray(got["out"], np.float64), np.asarray(ref["out"], np.float64)
    nums = {"out": _norm(g - r) / max(_norm(r), 1e-30)}
    rows_d = np.linalg.norm(g - r, axis=1)
    rows_r = np.linalg.norm(r, axis=1)
    nums["out_row"] = float(np.max(rows_d / np.maximum(rows_r,
                                                       np.median(rows_r))))
    if "loss" in ref:
        nums["loss"] = max(abs(l - ref["loss"]) for l in got["losses"]) \
            / abs(ref["loss"])
        import jax

        gl, rl = jax.tree.leaves(got["grads"]), jax.tree.leaves(ref["grads"])
        if len(gl) != len(rl):
            return {k: float("inf") for k in ("out", "out_row", "loss", "grad")}
        norms = [_norm(x) for x in rl]
        med = float(np.median(norms))
        nums["grad"] = max(
            _norm(np.asarray(a, np.float64) - np.asarray(b, np.float64))
            / max(nb, med)
            for a, b, nb in zip(gl, rl, norms) if nb >= GRAD_FLOOR * med)
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in nums.items()}
