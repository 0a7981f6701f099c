"""GAT (Veličković et al. 2018, §3.3) against the benchmark's plain reference.

The program's ``gat_apply`` and the SSO engine are compared with
``bench/models/gat.py`` (forward and backward by hand, edge work in
``EdgeOp``'s chunks) and ``bench/reference.py``, both loaded by path: the
benchmark's own reference is the only one. Weights come from the module's
``init``, in the program's parameter tree layout, on seeded random data.

Tolerances: on the CPU the program computes in float32 like the reference,
and the two differ only in the order of their sums (edge chunks against
partition units, division after the sum against per-edge coefficients), so
relative gaps are float32 reassociation, 1e-7 to 1e-6. Each limit below
sits a decade or more above that, and a bfloat16 reference (rounding at
2**-8) lands orders of magnitude above it.
"""
import importlib.util
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Counters, HostCache, SSOEngine, StorageTier, build_plan
from repro.core.engine import layer_vjp
from repro.graph import kronecker_graph, switching_aware_partition
from repro.graph.csr import add_self_loops
from repro.models.gnn.layers import full_graph_topo, gat_apply, get_gnn

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts).replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference.py")
gat = _load("models", "gat.py")

# one layer: the program's layer against the module's, float32 both
LAYER_TOL = 1e-5
# one SSO epoch against the whole-graph reference: loss, each gradient leaf
# (norm of the gap over the leaf's norm), final outputs (the same, over
# every node)
EPOCH_TOL = {"loss": 1e-5, "grad": 1e-4, "out": 1e-4}


def _rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def _graph(n_nodes, degree, seed):
    return add_self_loops(kronecker_graph(n_nodes, degree, seed=seed))


# the three kinds of layer in the published model, at small widths:
# concatenated heads (602 -> 4x256), concatenated with the skip
# (1024 -> 4x256 + input), averaged output heads (1024 -> 6x41)
LAYERS = {"concat": (24, 32, True), "skip": (32, 32, True),
          "average": (32, 10, False)}


@pytest.mark.parametrize("kind", LAYERS)
def test_layer_matches_reference(kind):
    d_in, d_out, activate = LAYERS[kind]
    g = _graph(96, 6, seed=3)
    # chunks of 256 edges: several chunks, the last one padded
    op = ref.EdgeOp(g.indptr, g.indices, gat.edge_weight, chunk=256)
    topo = full_graph_topo(g.indptr, g.indices, g.n_nodes)
    rng = np.random.default_rng(d_in + d_out)
    h = jnp.asarray(rng.standard_normal((g.n_nodes, d_in), np.float32))
    d_out_arr = jnp.asarray(
        rng.standard_normal((g.n_nodes, d_out), np.float32))
    p = gat.init(jax.random.key(d_in * d_out), d_in, d_out, activate)
    with jax.default_matmul_precision("highest"):
        z, want = gat.forward(p, h, op, activate)
        got = gat_apply(p, h, topo, activate=activate)
        want_dp, want_dh = gat.backward(p, h, z, d_out_arr, op, activate)
        got_dp, got_dh = layer_vjp(p, h, topo, d_out_arr, apply=gat_apply,
                                   activate=activate)
    assert got.shape == (g.n_nodes, d_out)
    assert _rel(got, want) < LAYER_TOL
    assert _rel(got_dh, want_dh) < LAYER_TOL
    for k in p:
        assert _rel(got_dp[k], want_dp[k]) < LAYER_TOL, k
    if kind == "skip":
        # the same heads with activate=False: no skip and no ELU
        with jax.default_matmul_precision("highest"):
            plain = gat_apply(p, h, topo, activate=False)
        assert _rel(jax.nn.elu(plain + h), got) < LAYER_TOL
        assert _rel(jax.nn.elu(plain), got) > 100 * LAYER_TOL


def test_program_init_is_the_published_layout():
    """``GNNSpec.init`` at the benchmark configuration's widths: hidden
    layers of 4 concatenated heads of 256, the output layer of 6 averaged
    heads of the class count (§3.3), as the reference's ``heads`` gives
    them, and the tree of the reference's ``init``."""
    with open(os.path.join(BENCH, "configs", "gat-reddit.json")) as f:
        cfg = json.load(f)
    dims = ([cfg["d_feat"]] + [cfg["d_hidden"]] * (cfg["n_layers"] - 1)
            + [cfg["classes"]])
    assert dims == [602, 1024, 1024, 41]
    layout = [gat.heads(dims[l + 1], l < 2) for l in range(3)]
    assert layout == [(4, 256), (4, 256), (6, 41)]
    params = get_gnn("gat").init(jax.random.key(0), cfg["d_feat"],
                                 cfg["d_hidden"], cfg["classes"],
                                 cfg["n_layers"])
    shapes = [(p["w"].shape, p["b"].shape) for p in params]
    assert shapes == [((dims[l], h, dh), (dims[l + 1],)) for l, (h, dh)
                      in enumerate(layout)]
    for l, p in enumerate(params):
        q = gat.init(jax.random.key(0), dims[l], dims[l + 1], l < 2)
        assert jax.tree.structure(p) == jax.tree.structure(q)
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
            assert a.shape == b.shape


@pytest.fixture(scope="module")
def epoch():
    """One SSO regather epoch of the published widths (602-1024-1024-41) on
    a 256-node Kronecker graph in 4 parts, and the reference on the same
    graph, data and weights."""
    g = _graph(256, 16, seed=0)
    parts = switching_aware_partition(g, 4, max_iters=8, seed=0).parts
    plan = build_plan(g, parts, 4)
    rg = plan.ro.graph
    dims = [602, 1024, 1024, 41]
    rng = np.random.default_rng(0)
    x = (0.1 * rng.standard_normal((rg.n_nodes, dims[0]))).astype(np.float32)
    y = rng.integers(0, dims[-1], rg.n_nodes).astype(np.int32)
    keys = jax.random.split(jax.random.key(1), 3)
    params = [gat.init(keys[l], dims[l], dims[l + 1], l < 2)
              for l in range(3)]
    c = Counters()
    st = StorageTier(tempfile.mkdtemp(), counters=c)
    cache = HostCache(1 << 20, st, c)
    eng = SSOEngine(get_gnn("gat"), plan, dims, st, cache, c,
                    mode="regather")
    eng.initialize(x)
    loss, grads = eng.run_epoch(params, y)
    got = {"loss": loss, "grads": grads,
           "out": st.read_rows("act3", 0, rg.n_nodes)}
    eng.close()
    st.close()
    args = (gat, rg.indptr, rg.indices, x, y, params)
    return got, ref.run(*args), ref.run(*args, control="bf16"), c


def _gaps(got, want):
    return {"loss": abs(got["loss"] - want["loss"]) / abs(want["loss"]),
            "grad": max(_rel(a, b) for a, b in
                        zip(jax.tree.leaves(got["grads"]),
                            jax.tree.leaves(want["grads"]))),
            "out": _rel(got["out"], want["out"])}


def test_sso_epoch_matches_reference(epoch):
    got, want, _, c = epoch
    assert len(jax.tree.leaves(got["grads"])) == 12
    gaps = _gaps(got, want)
    assert all(gaps[k] < EPOCH_TOL[k] for k in EPOCH_TOL), gaps
    # three layers, each once forward and once backward
    assert c.attention_passes == 6
    assert c.narrow_aggregate_passes == 0


def test_bf16_control_is_rejected(epoch):
    _, want, bf16, _ = epoch
    gaps = _gaps(bf16, want)
    assert any(gaps[k] > EPOCH_TOL[k] for k in EPOCH_TOL), gaps
