"""THE core correctness property (paper Appendix W): the SSO engine —
regather or snapshot — produces gradients equal to whole-graph autodiff up
to float reassociation, for every model, for any partitioning.

Marked slow (multi-second oracle runs per model); the CI fast job skips it
— the cheap pipelined-vs-serial equivalence checks live in
tests/test_runtime.py."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    Counters, HostCache, SSOEngine, StorageTier, build_plan,
)
from repro.graph import (
    gcn_norm_coeffs, kronecker_graph, switching_aware_partition,
)
from repro.graph.csr import add_self_loops
from repro.graph.synthetic import random_features, random_labels
from repro.models.gnn.layers import (
    full_graph_loss, full_graph_topo, get_gnn,
)

pytestmark = pytest.mark.slow


def _setup(n_nodes=1200, n_parts=6, d_in=24, seed=0):
    g = add_self_loops(kronecker_graph(n_nodes, 7, seed=seed))
    res = switching_aware_partition(g, n_parts, max_iters=10, seed=seed)
    ew = gcn_norm_coeffs(g)
    plan = build_plan(g, res.parts, n_parts, edge_weight=ew)
    X = random_features(g.n_nodes, d_in, seed)
    Y = random_labels(g.n_nodes, 10, seed)
    return g, plan, X[plan.ro.perm], Y[plan.ro.perm]


def _oracle(spec, params, plan, Xr, Yr):
    rg = plan.ro.graph
    topo = full_graph_topo(rg.indptr, rg.indices, rg.n_nodes, plan.edge_weight)
    return jax.value_and_grad(
        lambda p: full_graph_loss(spec, p, jnp.asarray(Xr), topo, jnp.asarray(Yr))
    )(params)


def _engine_run(spec, params, plan, Xr, Yr, dims, mode, budget_kb=65536):
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    cache = HostCache(budget_kb << 10, st_, c)
    eng = SSOEngine(spec, plan, dims, st_, cache, c, mode=mode)
    eng.initialize(Xr)
    loss, grads = eng.run_epoch(params, Yr)
    st_.close()
    return loss, grads, c


def _max_rel_err(a_tree, b_tree):
    errs = [
        float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-12))
        for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree))
    ]
    return max(errs)


MODELS = ["gcn", "sage", "gat", "gin", "pna", "graphcast"]


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("mode", ["regather", "snapshot"])
def test_engine_matches_oracle(model, mode):
    g, plan, Xr, Yr = _setup()
    spec = get_gnn(model)
    dims = [24, 32, 10]
    params = spec.init(jax.random.PRNGKey(0), 24, 32, 10, 2)
    oracle_loss, oracle_grads = _oracle(spec, params, plan, Xr, Yr)
    loss, grads, _ = _engine_run(spec, params, plan, Xr, Yr, dims, mode)
    assert abs(loss - float(oracle_loss)) < 1e-4 * max(1.0, abs(float(oracle_loss)))
    assert _max_rel_err(oracle_grads, grads) < 5e-4


def test_engine_matches_oracle_deep():
    """5-layer GCN (the paper's deep setting)."""
    g, plan, Xr, Yr = _setup()
    spec = get_gnn("gcn")
    dims = [24, 32, 32, 32, 10]
    params = spec.init(jax.random.PRNGKey(1), 24, 32, 10, 4)
    oracle_loss, oracle_grads = _oracle(spec, params, plan, Xr, Yr)
    loss, grads, _ = _engine_run(spec, params, plan, Xr, Yr, dims, "regather")
    assert _max_rel_err(oracle_grads, grads) < 5e-4


def test_tight_cache_still_correct():
    """Cache thrashing (layer eviction + grad spill) must not change math."""
    g, plan, Xr, Yr = _setup()
    spec = get_gnn("gcn")
    dims = [24, 32, 10]
    params = spec.init(jax.random.PRNGKey(2), 24, 32, 10, 2)
    _, oracle_grads = _oracle(spec, params, plan, Xr, Yr)
    # budget below one layer's activations (1200 nodes x 24 x 4B ~ 115KB)
    # so layer eviction + grad spill genuinely engage
    loss, grads, c = _engine_run(
        spec, params, plan, Xr, Yr, dims, "regather", budget_kb=96
    )
    assert _max_rel_err(oracle_grads, grads) < 5e-4
    assert c.cache_evictions > 0  # it really did thrash


@given(
    n_parts=st.sampled_from([2, 4, 8]),
    seed=st.integers(0, 4),
)
@settings(max_examples=6, deadline=None)
def test_engine_partition_invariance(n_parts, seed):
    """Property: grads are independent of the partitioning (hypothesis)."""
    g, plan, Xr, Yr = _setup(n_nodes=600, n_parts=n_parts, seed=seed)
    spec = get_gnn("gcn")
    params = spec.init(jax.random.PRNGKey(seed), 24, 16, 10, 2)
    _, oracle = _oracle(spec, params, plan, Xr, Yr)
    _, grads, _ = _engine_run(spec, params, plan, Xr, Yr, [24, 16, 10], "regather")
    assert _max_rel_err(oracle, grads) < 1e-3


def test_io_volume_regather_beats_snapshot_when_cache_holds_one_layer():
    """Paper §5: with host memory ~ one layer (D), regather avoids the αD
    snapshot traffic. Compare engine byte counters."""
    g, plan, Xr, Yr = _setup(n_nodes=2000, n_parts=8, d_in=64)
    spec = get_gnn("gcn")
    dims = [64, 64, 10]
    params = spec.init(jax.random.PRNGKey(0), 64, 64, 10, 2)
    D = g.n_nodes * 64 * 4
    budget = int(2.2 * D)  # holds ~2 layers, not alpha*D snapshots
    res = {}
    for mode in ["regather", "snapshot"]:
        c = Counters()
        st_ = StorageTier(tempfile.mkdtemp(), counters=c)
        cache = HostCache(budget, st_, c)
        eng = SSOEngine(spec, plan, dims, st_, cache, c, mode=mode)
        eng.initialize(Xr)
        c.reset()
        eng.run_epoch(params, Yr)
        res[mode] = c.storage_read_bytes + c.storage_write_bytes
        st_.close()
    assert res["regather"] < res["snapshot"]


def test_microbatch_matches_oracle(tiny_graph):
    from repro.core.microbatch import microbatch_grads
    from repro.graph.csr import gcn_norm_coeffs as norm

    g = tiny_graph
    ew = norm(g)
    spec = get_gnn("gcn")
    params = spec.init(jax.random.PRNGKey(0), 16, 24, 8, 2)
    X = random_features(g.n_nodes, 16, 0)
    Y = random_labels(g.n_nodes, 8, 0)
    topo = full_graph_topo(g.indptr, g.indices, g.n_nodes, ew)
    ol, og = jax.value_and_grad(
        lambda p: full_graph_loss(spec, p, jnp.asarray(X), topo, jnp.asarray(Y))
    )(params)
    l, gr, stats = microbatch_grads(spec, params, g, X, Y, 4, edge_weight=ew)
    assert abs(l - float(ol)) < 1e-4
    assert _max_rel_err(og, gr) < 1e-4
    assert stats["peak_input_nodes"] > g.n_nodes * 0.5  # neighbor explosion
