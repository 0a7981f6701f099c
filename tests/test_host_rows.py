"""The SSO hot path's row operations, each against a plain numpy oracle.

- the host gather (``ForwardRunner.gather``): a unit's gathered rows are
  ``X[u.req_global]`` bitwise, padding rows are zero, and reduced-precision
  storage is upcast exactly;
- the GCN and SAGE layers' edge aggregation (``models/gnn/layers.py``),
  against a float64 numpy sum: both linear orders, empty edge and
  destination sets, zero-weight padding edges, half-precision inputs;
- the host scatter (``core/engine.scatter_add_rows``, the ∇A write-back):
  bit-identical to ``np.add.at`` for the engine's duplicate-free rows;
- the pinned staging pool the H2D copies alias.
"""
import gc
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Counters, HostCache, StorageTier, build_plan
from repro.core.engine import scatter_add_rows
from repro.graph import kronecker_graph, switching_aware_partition
from repro.graph.csr import add_self_loops
from repro.models.gnn.layers import LocalTopo, gcn_apply, sage_apply
from repro.runtime import PipelineConfig, PipelineExecutor
from repro.runtime.forward import ForwardRunner


# --------------------------------------------------------------- host gather
def _gather_all(n_nodes, n_parts, d, store_dtype=np.float32,
                cache_bytes=64 << 20, pad=True):
    """Gather every unit of a fresh plan through ``ForwardRunner.gather``;
    returns ``(X as stored, [(unit, gathered buffer)])``."""
    g = add_self_loops(kronecker_graph(n_nodes, 6, seed=n_parts))
    parts = (switching_aware_partition(g, n_parts, max_iters=4, seed=0).parts
             if n_parts > 1 else np.zeros(g.n_nodes, np.int32))
    plan = build_plan(g, parts, n_parts)
    x = np.random.default_rng(d).standard_normal(
        (plan.n_nodes, d)).astype(store_dtype)
    c = Counters()
    st = StorageTier(tempfile.mkdtemp(), counters=c)
    st.alloc("act0", x.shape, store_dtype)
    st.write_rows("act0", 0, x)
    cache = HostCache(cache_bytes, st, c)
    cfg = PipelineConfig(depth=0)
    rt = PipelineExecutor(cfg, c, st, cache)
    runner = ForwardRunner(None, plan, [d], st, cache, c, rt, cfg,
                           store_dtype=store_dtype)
    out = []
    for u in plan.units:
        buf = runner.gather(0, u, u.r_pad if pad else u.n_req)
        out.append((u, buf.copy()))
        rt.pool.release(buf)
    rt.close()
    st.close()
    return x, out


class TestGatherRows:
    @pytest.mark.parametrize("n,parts,D", [
        (256, 4, 16), (300, 5, 48), (64, 3, 1),   # one-column rows
        (90, 2, 8), (257, 6, 130),                # odd, non-pow2 width
    ])
    def test_bit_identity_fp32(self, n, parts, D):
        x, out = _gather_all(n, parts, D)
        for u, buf in out:
            assert buf.shape == (u.r_pad, D) and buf.dtype == np.float32
            np.testing.assert_array_equal(buf[: u.n_req], x[u.req_global])
            assert not buf[u.n_req :].any()     # padding rows are zero

    @pytest.mark.parametrize("case", ["one_part_no_padding", "cache_holds_none"])
    def test_degenerate(self, case):
        # one partition: each unit reads only its own block, gathered with
        # no padding rows; a cache too small for any block: every block is
        # loaded past the cache and the rows are the same
        if case == "one_part_no_padding":
            x, out = _gather_all(120, 1, 12, pad=False)
        else:
            x, out = _gather_all(200, 4, 12, cache_bytes=64)
        for u, buf in out:
            np.testing.assert_array_equal(buf[: u.n_req], x[u.req_global])
            assert not buf[u.n_req :].any()

    @pytest.mark.parametrize("store_dtype", [jnp.bfloat16, np.float16])
    def test_low_precision_exact_copy(self, store_dtype):
        # reduced-precision storage: the gather upcasts into the float32
        # compute buffer, exactly (every half value is a float32 value)
        x, out = _gather_all(200, 4, 24, store_dtype=np.dtype(store_dtype))
        for u, buf in out:
            assert buf.dtype == np.float32
            np.testing.assert_array_equal(
                buf[: u.n_req], x[u.req_global].astype(np.float32))
            assert not buf[u.n_req :].any()


# ------------------------------------------------- GCN/SAGE edge aggregation
def _topo(rng, n_src, E, n_dst, n_pad=0):
    """A unit topology of ``E`` real edges (sorted dst) and ``n_pad``
    padding edges as the plan pads them: weight and mask 0, at row 0."""
    dst = np.sort(rng.integers(0, max(n_dst, 1), E)) if n_dst else \
        np.zeros(0, np.int64)
    src = rng.integers(0, n_src, dst.size)
    w = rng.standard_normal(dst.size)
    deg = np.maximum(np.bincount(dst, minlength=n_dst), 1)[:n_dst]
    padded = lambda a, dt: np.concatenate(
        [a, np.zeros(n_pad)]).astype(dt)
    topo = LocalTopo(
        src=jnp.asarray(padded(src, np.int32)),
        dst=jnp.asarray(padded(dst, np.int32)),
        n_dst=n_dst,
        edge_weight=jnp.asarray(padded(w, np.float32)),
        edge_mask=jnp.asarray(padded(np.ones(dst.size), np.float32)),
        in_deg=jnp.asarray(deg.astype(np.float32)),
        dst_self=jnp.asarray(
            rng.integers(0, n_src, n_dst).astype(np.int32)),
    )
    return topo, (src, dst, w.astype(np.float32), deg)


def _layer(model, rng, d_in, d_out):
    dense = lambda: {"w": rng.standard_normal((d_in, d_out)).astype(
        np.float32) / np.sqrt(d_in),
        "b": rng.standard_normal(d_out).astype(np.float32)}
    if model == "gcn":
        return {"lin": dense()}
    return {"self": dense(), "nbr": dense()}


def _oracle(model, p, ga, edges, n_dst):
    """The layer in float64 numpy: sum the weighted in-edge rows per dst
    (GCN), or average the in-edge rows and add the self term (SAGE)."""
    src, dst, w, deg = edges
    ga = np.asarray(ga, np.float64)
    f = lambda q: (np.asarray(q["w"], np.float64), np.asarray(q["b"],
                                                              np.float64))
    agg = np.zeros((n_dst, ga.shape[1]))
    if model == "gcn":
        np.add.at(agg, dst, w[:, None].astype(np.float64) * ga[src])
        wl, bl = f(p["lin"])
        return agg @ wl + bl
    np.add.at(agg, dst, ga[src])
    agg /= deg[:, None]
    (ws, bs), (wn, bn) = f(p["self"]), f(p["nbr"])
    return ga[p["_dst_self"]] @ ws + bs + agg @ wn + bn


def _apply(model, p, ga, topo):
    fn = gcn_apply if model == "gcn" else sage_apply
    q = {k: v for k, v in p.items() if k != "_dst_self"}
    with jax.default_matmul_precision("highest"):
        return np.asarray(fn(q, jnp.asarray(ga), topo, activate=False))


class TestGatherAggregate:
    @pytest.mark.parametrize("model,n,E,nd,D", [
        ("gcn", 64, 400, 32, 16), ("sage", 128, 1000, 64, 48),
        ("gcn", 10, 30, 5, 129), ("sage", 6, 1, 3, 8),  # a single edge
    ])
    def test_matches_float64_sum(self, model, n, E, nd, D, rng):
        # d_out == d_in: the aggregate-first order
        topo, edges = _topo(rng, n, E, nd)
        p = _layer(model, rng, D, D)
        p["_dst_self"] = np.asarray(topo.dst_self)
        ga = rng.standard_normal((n, D)).astype(np.float32)
        np.testing.assert_allclose(_apply(model, p, ga, topo),
                                   _oracle(model, p, ga, edges, nd),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("model", ["gcn", "sage"])
    def test_transform_first_matches_float64_sum(self, model, rng):
        # a narrowing layer transforms first and aggregates d_out-wide rows:
        # the same sum in another order
        n, E, nd, d_in, d_out = 64, 600, 24, 48, 12
        topo, edges = _topo(rng, n, E, nd)
        p = _layer(model, rng, d_in, d_out)
        p["_dst_self"] = np.asarray(topo.dst_self)
        ga = rng.standard_normal((n, d_in)).astype(np.float32)
        np.testing.assert_allclose(_apply(model, p, ga, topo),
                                   _oracle(model, p, ga, edges, nd),
                                   rtol=2e-5, atol=2e-5)

    def test_empty_edges_and_empty_dst(self, rng):
        # every edge is padding: each dst row is the bias alone
        topo, _ = _topo(rng, 8, 0, 5, n_pad=16)
        p = _layer("gcn", rng, 16, 16)
        ga = rng.standard_normal((8, 16)).astype(np.float32)
        out = _apply("gcn", p, ga, topo)
        np.testing.assert_array_equal(
            out, np.broadcast_to(p["lin"]["b"], (5, 16)))
        topo0, _ = _topo(rng, 8, 0, 0)
        assert _apply("gcn", p, ga, topo0).shape == (0, 16)

    @pytest.mark.parametrize("model", ["gcn", "sage"])
    def test_zero_weight_padding_edges_are_noops_in_value(self, model, rng):
        # padding edges (weight and mask 0, at row 0) add exact zeros: the
        # padded unit's output equals the unpadded one's bitwise
        n, E, nd, D = 32, 200, 16, 24
        topo, _ = _topo(np.random.default_rng(1), n, E, nd)
        padded, _ = _topo(np.random.default_rng(1), n, E, nd, n_pad=56)
        p = _layer(model, rng, D, D)
        ga = rng.standard_normal((n, D)).astype(np.float32)
        np.testing.assert_array_equal(_apply(model, p, ga, padded),
                                      _apply(model, p, ga, topo))

    @pytest.mark.parametrize("dtype,tol", [
        (jnp.bfloat16, 1e-1), (jnp.float16, 2e-2),
    ])
    def test_low_precision_tolerance(self, dtype, tol, rng):
        # rows and weights rounded to half precision (the float32 edge
        # weights promote the sums to float32): the output is within the
        # inputs' rounding of the float64 sum of the unrounded layer
        n, E, nd, D = 32, 120, 40, 32
        topo, edges = _topo(rng, n, E, nd)
        p = _layer("gcn", rng, D, D)
        ga = rng.standard_normal((n, D)).astype(np.float32)
        low = jax.tree.map(lambda a: jnp.asarray(a, dtype), p)
        with jax.default_matmul_precision("highest"):
            out = gcn_apply(low, jnp.asarray(ga, dtype), topo,
                            activate=False)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   _oracle("gcn", p, ga, edges, nd),
                                   rtol=tol, atol=tol)


# -------------------------------------------------------------- host scatter
class TestScatterAdd:
    @pytest.mark.parametrize("n,r,D", [
        (192, 128, 16), (230, 200, 48), (14, 9, 130), (7, 1, 8),
    ])
    def test_bit_identity_sorted_unique(self, n, r, D, rng):
        # the engine's rows: sorted, duplicate-free, with gaps
        base = rng.standard_normal((n, D), dtype=np.float32)
        rows = np.sort(rng.choice(n, r, replace=False))
        vals = rng.standard_normal((r, D), dtype=np.float32)
        out = base.copy()
        scatter_add_rows(out, rows, vals)
        want = base.copy()
        np.add.at(want, rows, vals)
        np.testing.assert_array_equal(out, want)

    def test_untouched_rows_keep_base_bits(self, rng):
        base = rng.standard_normal((16, 8), dtype=np.float32)
        rows = np.array([3, 7, 8, 12])
        out = base.copy()
        scatter_add_rows(out, rows,
                         rng.standard_normal((4, 8), dtype=np.float32))
        untouched = np.setdiff1d(np.arange(16), rows)
        np.testing.assert_array_equal(out[untouched], base[untouched])

    def test_empty_rows_returns_base(self, rng):
        base = rng.standard_normal((6, 8), dtype=np.float32)
        out = base.copy()
        scatter_add_rows(out, np.zeros(0, np.int64),
                         np.zeros((0, 8), np.float32))
        np.testing.assert_array_equal(out, base)

    def test_scatter_add_rows_bit_identity(self, rng):
        a = rng.standard_normal((48, 16), dtype=np.float32)
        b = a.copy()
        # sorted-unique, non-contiguous — the engine's actual row contract
        rows = np.sort(rng.permutation(48)[:30]).astype(np.int64)
        vals = rng.standard_normal((30, 16), dtype=np.float32)
        scatter_add_rows(a, rows, vals)
        np.add.at(b, rows, vals)
        np.testing.assert_array_equal(a, b)


# --------------------------------------------- host scatter reference path
class TestScatterAddRowsRef:
    """The sorted-``reduceat`` / contiguous-slice fast paths stay
    bit-identical to a bare ``np.add.at``."""

    def test_contiguous_run(self, rng):
        a = rng.standard_normal((64, 8), dtype=np.float32)
        b = a.copy()
        rows = np.arange(10, 30)
        vals = rng.standard_normal((20, 8), dtype=np.float32)
        scatter_add_rows(a, rows, vals)
        np.add.at(b, rows, vals)
        np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("seed", range(5))
    def test_unsorted_duplicate_free_random_rows(self, seed):
        rng = np.random.default_rng(seed)
        n = 200
        rows = rng.permutation(n)[:73]                 # duplicate-free
        a = rng.standard_normal((n, 12), dtype=np.float32)
        b = a.copy()
        vals = rng.standard_normal((73, 12), dtype=np.float32)
        scatter_add_rows(a, rows, vals)
        np.add.at(b, rows, vals)
        np.testing.assert_array_equal(a, b)

    def test_sorted_with_duplicates_one_rounding_of_add_at(self, rng):
        # with duplicates the segment sum lands on the base in one rounding
        # instead of per-element — documented ~1 ulp, not bit-identity
        # (no engine call site produces duplicate rows)
        a = rng.standard_normal((32, 6), dtype=np.float32)
        b = a.copy()
        rows = np.sort(rng.integers(0, 32, 100))
        vals = rng.standard_normal((100, 6), dtype=np.float32)
        scatter_add_rows(a, rows, vals)
        np.add.at(b, rows, vals)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_empty_and_single(self, rng):
        a = rng.standard_normal((8, 4), dtype=np.float32)
        b = a.copy()
        scatter_add_rows(a, np.zeros(0, np.int64),
                         np.zeros((0, 4), np.float32))
        np.testing.assert_array_equal(a, b)
        v = rng.standard_normal((1, 4), dtype=np.float32)
        scatter_add_rows(a, np.array([5]), v)
        np.add.at(b, np.array([5]), v)
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------- pinned staging pool
class TestPinnedPool:
    def _pool(self, cap=1 << 20):
        from repro.runtime.executor import BufferPool

        return BufferPool(max_bytes=cap)

    def test_buffers_are_64B_aligned(self):
        pool = self._pool()
        for shape in [(3, 5), (128, 16), (1, 1)]:
            a = pool.acquire(shape, np.float32)
            assert a.ctypes.data % 64 == 0
            assert a.flags["C_CONTIGUOUS"]
            pool.release(a)
        # alignment survives the free-list round trip
        b = pool.acquire((3, 5), np.float32)
        assert b.ctypes.data % 64 == 0

    def test_defer_release_recycles_after_device_array_dies(self):
        pool = self._pool()
        a = pool.acquire((64, 16), np.float32)
        a[:] = 1.0
        dev = jax.device_put(a)
        jax.block_until_ready(dev)
        addr = a.ctypes.data
        assert pool.defer_release(a)
        del a
        assert pool.deferred_pending == 1      # alive while dev aliases it
        del dev
        gc.collect()   # the device array sits in a reference cycle
        assert pool.deferred_pending == 0      # weakref fired -> recycled
        allocs = pool.allocations
        b = pool.acquire((64, 16), np.float32)
        assert b.ctypes.data == addr           # same buffer, no new alloc
        assert pool.allocations == allocs

    def test_defer_release_rejects_foreign_arrays(self):
        pool = self._pool()
        assert not pool.defer_release(np.zeros((4, 4), np.float32))

    def test_deferred_buffers_count_toward_no_new_state_leak(self):
        # releasing normally after a defer attempt must not double-park
        pool = self._pool()
        a = pool.acquire((8, 8), np.float32)
        assert pool.defer_release(a)
        ref_only = pool.deferred_pending
        del a
        assert pool.deferred_pending == ref_only - 1
