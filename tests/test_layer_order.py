"""Transform-first order of narrowing GCN and GraphSAGE layers.

``gcn_apply`` and ``sage_apply`` run the dense transform before the edge
aggregation when a layer narrows (``d_out < d_in``) and aggregate first
otherwise. The aggregate-first formulation is kept here as the oracle: at
``highest`` matmul precision the layer's output and its ``jax.vjp``
cotangents match it to float32 reassociation, layers that do not narrow
reproduce it bit for bit, and padded edges (mask 0, GCN weight 0, pointing
at slot 0 as ``core/plan.py`` pads them) add nothing in either order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.gnn.layers import LocalTopo, get_gnn, transform_first

N_SRC, N_DST, E_REAL, E_PAD = 48, 24, 160, 256
WIDTHS = [(602, 128), (128, 41), (1024, 256), (256, 256), (64, 128)]


def _seg(x, topo):
    return jax.ops.segment_sum(x, topo.dst, num_segments=topo.n_dst)


def _gcn_aggregate_first(params, ga, topo, activate=True):
    msg = ga[topo.src] * topo.edge_weight[:, None]
    h = _seg(msg, topo) @ params["lin"]["w"] + params["lin"]["b"]
    return jax.nn.relu(h) if activate else h


def _sage_aggregate_first(params, ga, topo, activate=True):
    msg = ga[topo.src] * topo.edge_mask[:, None]
    agg = _seg(msg, topo) / topo.in_deg[:, None]
    x_self = ga[topo.dst_self]
    h = (x_self @ params["self"]["w"] + params["self"]["b"]
         + (agg @ params["nbr"]["w"] + params["nbr"]["b"]))
    return jax.nn.relu(h) if activate else h


ORACLE = {"gcn": _gcn_aggregate_first, "sage": _sage_aggregate_first}


def _topo(rng, n_edges):
    """A work unit's topology: sorted dst, ``n_edges`` real edges, padded
    to ``E_PAD`` the way the plan pads (src and dst 0, weight and mask 0)."""
    src = np.zeros(E_PAD, np.int32)
    dst = np.zeros(E_PAD, np.int32)
    ew = np.zeros(E_PAD, np.float32)
    mask = np.zeros(E_PAD, np.float32)
    src[:n_edges] = rng.integers(0, N_SRC, n_edges)
    dst[:n_edges] = np.sort(rng.integers(0, N_DST, n_edges))
    ew[:n_edges] = rng.uniform(0.1, 1.0, n_edges)
    mask[:n_edges] = 1.0
    deg = np.maximum(np.bincount(dst[:n_edges], minlength=N_DST), 1)
    return LocalTopo(
        src=jnp.asarray(src), dst=jnp.asarray(dst), n_dst=N_DST,
        edge_weight=jnp.asarray(ew), edge_mask=jnp.asarray(mask),
        in_deg=jnp.asarray(deg.astype(np.float32)),
        dst_self=jnp.asarray(rng.permutation(N_SRC)[:N_DST].astype(np.int32)),
    )


def _vjp_fn(apply):
    """Jitted ``(params, ga, topo, d_out) -> (out, (dparams, dga))``."""
    @jax.jit
    def f(params, ga, topo, d_out):
        out, vjp = jax.vjp(lambda p, a: apply(p, a, topo, activate=True),
                           params, ga)
        return out, vjp(d_out)
    return f


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


@pytest.mark.parametrize("d_in,d_out", WIDTHS)
@pytest.mark.parametrize("model", ["gcn", "sage"])
def test_layer_order_matches_aggregate_first(model, d_in, d_out):
    rng = np.random.default_rng(d_in * 1000 + d_out)
    spec = get_gnn(model)
    params = spec.init_layer(jax.random.PRNGKey(d_in + d_out), d_in, d_out)
    ga = jnp.asarray(rng.standard_normal((N_SRC, d_in), dtype=np.float32))
    topo = _topo(rng, E_REAL)
    d_out_arr = jnp.asarray(
        rng.standard_normal((N_DST, d_out), dtype=np.float32))
    layer = _vjp_fn(spec.apply_layer)
    oracle = _vjp_fn(ORACLE[model])

    # same mathematics: forward and every cotangent (dGA, each dW and db)
    with jax.default_matmul_precision("highest"):
        out, (dp, dga) = layer(params, ga, topo, d_out_arr)
        ref_out, (ref_dp, ref_dga) = oracle(params, ga, topo, d_out_arr)
    assert _rel(out, ref_out) < 1e-5
    assert _rel(dga, ref_dga) < 1e-5
    for got, want in zip(jax.tree.leaves(dp), jax.tree.leaves(ref_dp)):
        assert _rel(got, want) < 1e-5

    # layers that do not narrow run today's program: the same bits
    if not transform_first(d_in, d_out):
        got = layer(params, ga, topo, d_out_arr)
        want = oracle(params, ga, topo, d_out_arr)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # padded edges add nothing: the same real edges, unpadded
    real = LocalTopo(topo.src[:E_REAL], topo.dst[:E_REAL], N_DST,
                     topo.edge_weight[:E_REAL], topo.edge_mask[:E_REAL],
                     topo.in_deg, topo.dst_self)
    # slot 0, where every padded edge points, made large
    ga_big = ga.at[0].multiply(1e6)
    with jax.default_matmul_precision("highest"):
        padded, (dp_pad, dga_pad) = layer(params, ga_big, topo, d_out_arr)
        unpadded, (dp_real, dga_real) = layer(params, ga_big, real,
                                              d_out_arr)
    assert _rel(padded, unpadded) < 1e-6
    assert _rel(dga_pad, dga_real) < 1e-6
    for got, want in zip(jax.tree.leaves(dp_pad), jax.tree.leaves(dp_real)):
        assert _rel(got, want) < 1e-6

