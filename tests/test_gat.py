"""GAT (Veličković et al. 2018, §3.3) against the benchmark's plain reference.

The program's ``gat_apply`` and the SSO engine are compared with
``bench/models/gat.py`` (forward and backward by hand, edge work in
``EdgeOp``'s chunks) and ``bench/reference.py``, both loaded by path: the
benchmark's own reference is the only one. Weights come from the module's
``init``, in the program's parameter tree layout, on seeded random data.

Tolerances: on the CPU the program computes in float32 like the reference,
and the two differ only in the order of their sums (edge chunks against
partition units, division after the sum against per-edge coefficients), so
relative gaps are float32 reassociation, 1e-7 to 1e-6, as are those
between the layer's dense and edge formulations. Each limit below
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
from repro.models.gnn import layers
from repro.models.gnn.layers import (
    LocalTopo, full_graph_topo, gat_apply, get_gnn,
)

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


def _unit_topo():
    """A small padded unit: 8 dst rows over 16 gathered rows, 29 real edges
    (one listed twice) padded to 64, and a last dst row with no real edge
    (as the padded dst rows of a plan's bucket)."""
    rng = np.random.default_rng(5)
    dst = np.concatenate([np.arange(7), rng.integers(0, 7, 21)])
    src = np.concatenate([np.arange(7), rng.integers(0, 16, 21)])
    # the duplicate edge
    dst, src = np.append(dst, dst[-1]), np.append(src, src[-1])
    n_real, n_pad = dst.size, 64
    pad = lambda a, dt: np.concatenate(
        [a, np.zeros(n_pad - n_real)]).astype(dt)
    return LocalTopo(
        src=jnp.asarray(pad(src, np.int32)),
        dst=jnp.asarray(pad(dst, np.int32)),
        n_dst=8,
        edge_weight=jnp.asarray(pad(np.ones(n_real), np.float32)),
        edge_mask=jnp.asarray(pad(np.ones(n_real), np.float32)),
        in_deg=jnp.ones(8, jnp.float32),
        dst_self=jnp.arange(8, dtype=jnp.int32),
    )


@pytest.mark.parametrize("kind", ["skip", "average"])
def test_dense_path_matches_edge_path(kind):
    """The dense and the edge formulations of one GAT layer give the same
    outputs and the same ``jax.vjp`` gradients, for the parameters and for
    the gathered rows, to float32 reassociation: on a 1024 -> 4x256 layer
    with the skip (at 32 -> 4x8) and a 6-head averaging one (32 -> 6x10)."""
    d_in, d_out, activate = LAYERS[kind]
    topo = _unit_topo()
    assert int(topo.edge_mask.sum()) == 29
    rng = np.random.default_rng(d_out)
    ga = jnp.asarray(rng.standard_normal((16, d_in), np.float32))
    p = gat.init(jax.random.key(d_out), d_in, d_out, activate)
    ct = jnp.asarray(rng.standard_normal((8, d_out), np.float32))
    got = {}
    for name, attend in [("dense", layers._attend_dense),
                         ("edges", layers._attend_edges)]:
        f = lambda q, a, attend=attend: layers._gat(q, a, topo, activate,
                                                    attend)
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(f, p, ga)
            got[name] = (out, *vjp(ct))
    # the same path gat_apply takes for this unit
    assert layers.gat_dense(8, 16, 64, p["w"].shape[2])
    for a, b in zip(jax.tree.leaves(got["dense"]),
                    jax.tree.leaves(got["edges"])):
        assert a.dtype == b.dtype == jnp.float32
        assert np.all(np.isfinite(np.asarray(a)))
        assert _rel(a, b) < LAYER_TOL
    # the dst row without edges gets the bias (and the skip) alone
    out = got["dense"][0]
    want = p["b"] + (ga[7] if kind == "skip" else 0.0)
    np.testing.assert_allclose(
        out[7], jax.nn.elu(want) if activate else want, rtol=1e-6)


def _softmax_unit(rng, n, E, n_pad=0):
    """A unit of ``n`` rows, each its own dst, ``E`` real in-edges (sorted
    dst) and ``n_pad`` padding edges as the plan pads them: mask 0, at row
    0."""
    dst = np.sort(rng.integers(0, n, E))
    src = rng.integers(0, n, E)
    pad = lambda a, dt: np.concatenate([a, np.zeros(n_pad)]).astype(dt)
    topo = LocalTopo(
        src=jnp.asarray(pad(src, np.int32)),
        dst=jnp.asarray(pad(dst, np.int32)),
        n_dst=n,
        edge_weight=jnp.asarray(pad(np.ones(E), np.float32)),
        edge_mask=jnp.asarray(pad(np.ones(E), np.float32)),
        in_deg=jnp.ones(n, jnp.float32),
        dst_self=jnp.arange(n, dtype=jnp.int32),
    )
    return topo, src, dst


def _edge_softmax_oracle(h, e_src, e_dst, src, dst, n):
    """Each head's softmax over a dst row's in-edges of
    ``leaky_relu(e_src[src] + e_dst[dst])``, weighting ``h[src]``: float64
    numpy, one dst row at a time."""
    h, e_src, e_dst = (np.asarray(a, np.float64) for a in (h, e_src, e_dst))
    out = np.zeros((n,) + h.shape[1:])
    for v in range(n):
        ins = np.flatnonzero(dst == v)
        if ins.size == 0:
            continue
        s = e_src[src[ins]] + e_dst[v]
        s = np.where(s > 0, s, 0.2 * s)
        a = np.exp(s - s.max(axis=0))
        a /= a.sum(axis=0)
        out[v] = np.einsum("eh,ehd->hd", a, h[src[ins]])
    return out


@pytest.mark.parametrize("n,E,H", [(200, 1500, 1), (300, 2500, 4),
                                   (128, 600, 8)])
def test_edge_softmax_matches_float64(n, E, H):
    """The edge path's neighbourhood softmax and weighted sum
    (``_attend_edges``) at 1, 4 and 8 heads, padding edges included, against
    a float64 numpy softmax per dst row."""
    rng = np.random.default_rng(n + H)
    topo, src, dst = _softmax_unit(rng, n, E, n_pad=64)
    h = jnp.asarray(rng.standard_normal((n, H, 8)), jnp.float32)
    e_src = jnp.asarray(rng.standard_normal((n, H)), jnp.float32)
    e_dst = jnp.asarray(rng.standard_normal((n, H)), jnp.float32)
    got = layers._attend_edges(h, e_src, e_dst, topo)
    want = _edge_softmax_oracle(h, e_src, e_dst, src, dst, n)
    assert got.shape == (n, H, 8)
    assert _rel(got, want) < LAYER_TOL


def test_edge_softmax_rows_sum_to_one():
    """Weighting rows of ones, the edge path's weighted sum is each dst
    row's sum of coefficients: one where the row has in-edges, zero where
    it has none (padding edges weigh nothing)."""
    rng = np.random.default_rng(7)
    n, E, H = 100, 150, 2
    topo, _, dst = _softmax_unit(rng, n, E, n_pad=32)
    e_src = jnp.asarray(3 * rng.standard_normal((n, H)), jnp.float32)
    e_dst = jnp.asarray(3 * rng.standard_normal((n, H)), jnp.float32)
    sums = np.asarray(layers._attend_edges(
        jnp.ones((n, H, 1), jnp.float32), e_src, e_dst, topo))[:, :, 0]
    touched = np.bincount(dst, minlength=n) > 0
    assert (~touched).any()
    np.testing.assert_allclose(sums[touched], 1.0, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(sums[~touched], 0.0)


# the padded (r_pad, e_pad, d_pad) buckets of the reddit plan (16,384 nodes,
# 16 parts) and the largest of the gcn-igbm-3l plan (65,536 nodes, 8 parts)
REDDIT_BUCKETS = [(16_384, 524_288, 1_024), (16_384, 524_288, 2_048),
                  (16_384, 1_048_576, 2_048), (16_384, 1_048_576, 1_024)]
IGBM_UNIT = (65_536, 1_048_576, 16_384)


@pytest.mark.parametrize("d_head", [256, 41])
@pytest.mark.parametrize("unit,dense", [(u, True) for u in REDDIT_BUCKETS]
                         + [(IGBM_UNIT, False)])
def test_path_rule_by_unit_shape(unit, dense, d_head):
    """Every reddit bucket takes the dense path at the hidden layers' heads
    of 256 and the output layer's of 41; the sparse igbm unit keeps the
    edge path. Shapes only: nothing compiles."""
    r_pad, e_pad, d_pad = unit
    assert layers.gat_dense(d_pad, r_pad, e_pad, d_head) is dense


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


def _sso_epoch(g, n_parts, dims):
    """One SSO regather epoch of a 3-layer GAT of widths ``dims`` on ``g``
    in ``n_parts`` parts, and the reference's inputs: the graph in the
    plan's order, data and weights."""
    parts = switching_aware_partition(g, n_parts, max_iters=8, seed=0).parts
    plan = build_plan(g, parts, n_parts)
    rg = plan.ro.graph
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
    return got, (gat, rg.indptr, rg.indices, x, y, params), c


@pytest.fixture(scope="module")
def epoch():
    """One SSO regather epoch of the published widths (602-1024-1024-41) on
    a 256-node Kronecker graph in 4 parts, and the reference on the same
    graph, data and weights."""
    got, args, c = _sso_epoch(_graph(256, 16, seed=0), 4,
                              [602, 1024, 1024, 41])
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
    # three layers, each once forward and once backward, over 4 units that
    # are dense enough for the dense path
    assert c.attention_passes == 6
    assert c.attention_units == c.dense_attention_units == 24
    assert c.narrow_aggregate_passes == 0


def test_sparse_units_take_the_edge_path():
    """A graph of one edge a node besides its self loop: each unit's
    attention matrix would outgrow its messages, so both units of every
    pass run the edge path, and the epoch still matches the reference."""
    got, args, c = _sso_epoch(_graph(1024, 1, seed=0), 2, [24, 32, 32, 10])
    gaps = _gaps(got, ref.run(*args))
    assert all(gaps[k] < EPOCH_TOL[k] for k in EPOCH_TOL), gaps
    assert c.attention_units == 12
    assert c.dense_attention_units == 0


def test_bf16_control_is_rejected(epoch):
    _, want, bf16, _ = epoch
    gaps = _gaps(bf16, want)
    assert any(gaps[k] > EPOCH_TOL[k] for k in EPOCH_TOL), gaps
