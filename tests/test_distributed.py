"""Distributed paths on an 8-fake-device mesh run in a SUBPROCESS (so the
main pytest process keeps 1 CPU device for smoke realism)."""
import json
import subprocess
import sys
import textwrap


def _run(code: str, timeout=420):
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout,
        env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "PYTHONPATH": "src",
            "PATH": "/usr/bin:/bin",
        },
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
mesh = jax.make_mesh((4,2), ("data","model"), axis_types=(AxisType.Auto,)*2)
"""


def test_split_kv_decode_exact():
    _run(PREAMBLE + textwrap.dedent("""
        from repro.distributed.collectives import (
            make_split_kv_decode, decode_attention_ref)
        rng = np.random.default_rng(0)
        B,S,Hq,Hkv,D = 2, 64, 8, 2, 16
        q = jnp.asarray(rng.standard_normal((B,1,Hq,D)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((B,S,Hkv,D)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((B,S,Hkv,D)).astype(np.float32))
        for w in (None, 16):
            fn = make_split_kv_decode(mesh, ("model",), window=w)
            with jax.set_mesh(mesh):
                out = fn(q, k, v, jnp.int32(50))
            ref = decode_attention_ref(q, k, v, jnp.int32(50), window=w)
            assert float(jnp.max(jnp.abs(out-ref))) < 1e-5
        print("OK")
    """))


def test_partitioned_halo_matches_oracle():
    _run(PREAMBLE + textwrap.dedent("""
        from repro.graph import kronecker_graph, gcn_norm_coeffs
        from repro.graph.csr import add_self_loops
        from repro.graph.synthetic import random_features, random_labels
        from repro.models.gnn.layers import get_gnn, full_graph_topo, full_graph_loss
        from repro.distributed.gnn_parallel import (
            make_partitioned_train_step, build_partitioned_data)
        from repro.optim.adamw import adamw_init
        from repro.core.plan import build_plan
        g = add_self_loops(kronecker_graph(512, 6, seed=0))
        n = g.n_nodes
        parts = (np.arange(n) % 4).astype(np.int32)
        ew = gcn_norm_coeffs(g)
        data, n_local, n_halo, ro = build_partitioned_data(g, parts, 4, ew)
        X = random_features(n, 24, 0); Y = random_labels(n, 8, 0)
        Xr = X[ro.perm]; Yr = Y[ro.perm]
        spec = get_gnn("gcn")
        params = spec.init(jax.random.PRNGKey(0), 24, 32, 8, 2)
        step = make_partitioned_train_step("gcn", n_local, n_halo, mesh)
        with jax.set_mesh(mesh):
            p2, o2, loss = step(params, adamw_init(params),
                jnp.asarray(Xr.reshape(4*n_local, 24)),
                *[jnp.asarray(data[k].reshape(-1)) for k in
                  ["lsrc","ldst","lew","hsrc","hdst","hew","halo","deg"]],
                jnp.asarray(Yr.reshape(-1)))
        plan = build_plan(g, parts, 4, edge_weight=ew)
        topo = full_graph_topo(ro.graph.indptr, ro.graph.indices, n,
                               np.asarray(plan.edge_weight))
        oracle = full_graph_loss(spec, params, jnp.asarray(Xr), topo,
                                 jnp.asarray(Yr))
        assert abs(float(loss) - float(oracle)) < 1e-5
        print("OK")
    """))


def test_fullgraph_step_runs_sharded():
    _run(PREAMBLE + textwrap.dedent("""
        from jax.sharding import NamedSharding
        from repro.distributed.gnn_parallel import (
            make_fullgraph_train_step, fullgraph_inputs)
        from repro.models.gnn.layers import get_gnn
        from repro.optim.adamw import adamw_init
        from repro.graph import kronecker_graph, gcn_norm_coeffs
        from repro.graph.csr import add_self_loops
        from repro.graph.synthetic import random_features, random_labels
        g = add_self_loops(kronecker_graph(512, 6, seed=0))
        n_pad, args, shard = fullgraph_inputs(g.n_nodes, g.n_edges, 16, 8, mesh)
        step = make_fullgraph_train_step("gcn", n_pad)
        spec = get_gnn("gcn")
        params = spec.init(jax.random.PRNGKey(0), 16, 24, 8, 2)
        opt = adamw_init(params)
        ew = gcn_norm_coeffs(g)
        ei = g.edge_index()
        import numpy as np
        e_pad = args[1].shape[0]
        src = np.zeros(e_pad, np.int32); src[:g.n_edges] = ei[0]
        dst = np.zeros(e_pad, np.int32); dst[:g.n_edges] = ei[1]
        w = np.zeros(e_pad, np.float32); w[:g.n_edges] = ew
        x = np.zeros((n_pad, 16), np.float32)
        x[:g.n_nodes] = random_features(g.n_nodes, 16, 0)
        deg = np.ones(n_pad, np.float32)
        deg[:g.n_nodes] = np.maximum(g.in_degrees(), 1)
        y = np.zeros(n_pad, np.int32)
        y[:g.n_nodes] = random_labels(g.n_nodes, 8, 0)
        with jax.set_mesh(mesh):
            p2, o2, loss = jax.jit(step)(params, opt, x, src, dst, w, deg, y)
        assert np.isfinite(float(loss))
        print("OK")
    """))


def test_elastic_checkpoint_reshard():
    """Save params on a (4,2) mesh, restore them onto a (2,4) mesh."""
    _run(PREAMBLE + textwrap.dedent("""
        import tempfile
        from jax.sharding import NamedSharding
        from repro.train.checkpoint import save_checkpoint, restore_checkpoint, latest_checkpoint
        params = {"w": jnp.arange(64.).reshape(8, 8)}
        sh1 = {"w": NamedSharding(mesh, P("data", "model"))}
        p1 = jax.tree.map(lambda x, s: jax.device_put(x, s), params, sh1)
        d = tempfile.mkdtemp()
        save_checkpoint(d, 3, p1)
        mesh2 = jax.make_mesh((2,4), ("data","model"), axis_types=(AxisType.Auto,)*2)
        sh2 = {"w": NamedSharding(mesh2, P("model", "data"))}
        p2, _, step, _ = restore_checkpoint(latest_checkpoint(d), params, shardings=sh2)
        assert step == 3
        assert np.allclose(np.asarray(p2["w"]), np.asarray(params["w"]))
        assert p2["w"].sharding.spec == P("model", "data")
        print("OK")
    """))
