"""Gather/scatter kernel equivalence + dispatch-layer tests (interpret mode).

The contract under test is the PR's acceptance bar: with ``kernels="pallas"``
the engine's math is BIT-identical to the numpy reference engine, so the
kernel-level comparisons here are ``assert_array_equal`` for fp32 — not
tolerance checks. The one documented exception is the truly fused
gather+aggregate (``"pallas-fused"``): its per-edge accumulate is an FMA, so
it is compared bit-exactly against the :func:`gather_aggregate_ref_fma`
oracle and with a ~1-ulp tolerance against the vectorized reference.
"""
import gc
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import (
    KernelDispatch, VALID_MODES, scatter_add_rows_ref,
)
from repro.kernels.gather_scatter import (
    gather_aggregate, gather_aggregate_ref, gather_aggregate_ref_fma,
    gather_rows, gather_rows_ref, scatter_add, scatter_add_ref,
)


def _sorted_dst(rng, E, n_dst):
    return np.sort(rng.integers(0, n_dst, E)).astype(np.int32)


# ------------------------------------------------------------- gather_rows
class TestGatherRows:
    @pytest.mark.parametrize("n,r,D", [
        (64, 128, 16), (300, 77, 48), (9, 1, 200),   # pad_rows > n_rows
        (5, 3, 8), (257, 511, 130),                  # odd, non-pow2 feature
    ])
    def test_bit_identity_fp32(self, n, r, D, rng):
        table = rng.standard_normal((n, D), dtype=np.float32)
        rows = rng.integers(0, n, r).astype(np.int32)
        out = gather_rows(jnp.asarray(table), jnp.asarray(rows),
                          interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      gather_rows_ref(table, rows))

    @pytest.mark.parametrize("shape", [(0, 8), (8, 0)])
    def test_degenerate(self, shape, rng):
        n, D = 16, 8
        table = rng.standard_normal((n, D), dtype=np.float32)
        if shape[0] == 0:          # empty row request
            rows = np.zeros(0, np.int32)
        else:                      # zero-width features
            table = table[:, :0]
            rows = np.arange(4, dtype=np.int32)
        out = gather_rows(jnp.asarray(table), jnp.asarray(rows),
                          interpret=True)
        assert out.shape == (rows.size, table.shape[1])

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
    def test_low_precision_exact_copy(self, dtype, rng):
        # a gather is a copy — exact even in half precision
        table = jnp.asarray(
            rng.standard_normal((40, 24), dtype=np.float32), dtype
        )
        rows = jnp.asarray(rng.integers(0, 40, 100).astype(np.int32))
        out = gather_rows(table, rows, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out, np.float32),
            np.asarray(table, np.float32)[np.asarray(rows)],
        )


# -------------------------------------------------------- gather_aggregate
class TestGatherAggregate:
    @pytest.mark.parametrize("n,E,nd,D", [
        (64, 400, 32, 16), (128, 1000, 64, 48), (10, 30, 5, 129),
        (6, 1, 3, 8),                                  # single edge
    ])
    def test_bit_identity_vs_fma_oracle(self, n, E, nd, D, rng):
        table = rng.standard_normal((n, D), dtype=np.float32)
        erows = rng.integers(0, n, E).astype(np.int32)
        dst = _sorted_dst(rng, E, nd)
        w = rng.standard_normal(E, dtype=np.float32)
        out = gather_aggregate(
            jnp.asarray(table), jnp.asarray(erows), jnp.asarray(dst),
            jnp.asarray(w), nd, interpret=True,
        )
        np.testing.assert_array_equal(
            np.asarray(out),
            gather_aggregate_ref_fma(table, erows, dst, w, nd),
        )

    def test_one_ulp_of_vectorized_reference(self, rng):
        # FMA rounds once per edge, the vectorized oracle twice — the
        # divergence on multi-edge rows is bounded by ~1 ulp of the sum
        n, E, nd, D = 64, 600, 24, 32
        table = rng.standard_normal((n, D), dtype=np.float32)
        erows = rng.integers(0, n, E).astype(np.int32)
        dst = _sorted_dst(rng, E, nd)
        w = rng.standard_normal(E, dtype=np.float32)
        out = np.asarray(gather_aggregate(
            jnp.asarray(table), jnp.asarray(erows), jnp.asarray(dst),
            jnp.asarray(w), nd, interpret=True,
        ))
        ref = gather_aggregate_ref(table, erows, dst, w, nd)
        np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)
        assert np.any(out != ref), "expected >= 1 FMA-divergent row"

    def test_empty_edges_and_empty_dst(self, rng):
        table = rng.standard_normal((8, 16), dtype=np.float32)
        out = gather_aggregate(
            jnp.asarray(table), jnp.zeros(0, jnp.int32),
            jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.float32), 5,
            interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(out),
                                      np.zeros((5, 16), np.float32))
        out0 = gather_aggregate(
            jnp.asarray(table), jnp.zeros(0, jnp.int32),
            jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.float32), 0,
            interpret=True,
        )
        assert out0.shape == (0, 16)

    def test_zero_weight_padding_edges_are_noops_in_value(self, rng):
        # padding edges re-pointed at the last row with w=0 contribute
        # 0 * row — the padded row still matches the oracle bitwise
        n, E, nd, D = 32, 200, 16, 24
        table = rng.standard_normal((n, D), dtype=np.float32)
        erows = rng.integers(0, n, E).astype(np.int32)
        dst = _sorted_dst(rng, E, nd)
        w = rng.standard_normal(E, dtype=np.float32)
        w[dst == nd - 1] = 0.0                     # "padding" tail
        out = gather_aggregate(
            jnp.asarray(table), jnp.asarray(erows), jnp.asarray(dst),
            jnp.asarray(w), nd, interpret=True,
        )
        np.testing.assert_array_equal(
            np.asarray(out),
            gather_aggregate_ref_fma(table, erows, dst, w, nd),
        )

    @pytest.mark.parametrize("dtype,tol", [
        (jnp.bfloat16, 2e-1), (jnp.float16, 2e-2),
    ])
    def test_low_precision_tolerance(self, dtype, tol, rng):
        # tolerance vs the fp32 oracle scales with the per-row edge count
        # (~3 here): every accumulate rounds to the storage dtype
        n, E, nd, D = 32, 120, 40, 32
        table = rng.standard_normal((n, D), dtype=np.float32)
        erows = rng.integers(0, n, E).astype(np.int32)
        dst = _sorted_dst(rng, E, nd)
        w = rng.standard_normal(E, dtype=np.float32)
        out = gather_aggregate(
            jnp.asarray(table, dtype), jnp.asarray(erows),
            jnp.asarray(dst), jnp.asarray(w, dtype), nd, interpret=True,
        )
        ref = gather_aggregate_ref(table, erows, dst, w, nd)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, rtol=tol, atol=tol
        )


# ------------------------------------------------------------- scatter_add
class TestScatterAdd:
    @pytest.mark.parametrize("n,r,D", [
        (64, 128, 16), (30, 200, 48), (5, 9, 130), (7, 1, 8),
    ])
    def test_bit_identity_sorted_dups(self, n, r, D, rng):
        base = rng.standard_normal((n, D), dtype=np.float32)
        rows = np.sort(rng.integers(0, n, r)).astype(np.int32)
        vals = rng.standard_normal((r, D), dtype=np.float32)
        out = scatter_add(jnp.asarray(base), jnp.asarray(rows),
                          jnp.asarray(vals), interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      scatter_add_ref(base, rows, vals))

    def test_untouched_rows_keep_base_bits(self, rng):
        base = rng.standard_normal((16, 8), dtype=np.float32)
        rows = np.array([3, 3, 7], np.int32)
        vals = rng.standard_normal((3, 8), dtype=np.float32)
        out = np.asarray(scatter_add(
            jnp.asarray(base), jnp.asarray(rows), jnp.asarray(vals),
            interpret=True,
        ))
        untouched = np.setdiff1d(np.arange(16), rows)
        np.testing.assert_array_equal(out[untouched], base[untouched])

    def test_empty_rows_returns_base(self, rng):
        base = rng.standard_normal((6, 8), dtype=np.float32)
        out = scatter_add(jnp.asarray(base), jnp.zeros(0, jnp.int32),
                          jnp.zeros((0, 8), jnp.float32), interpret=True)
        np.testing.assert_array_equal(np.asarray(out), base)


# --------------------------------------------- host scatter reference path
class TestScatterAddRowsRef:
    """Satellite: the sorted-``reduceat`` / contiguous-slice fast paths must
    stay bit-identical to the seed engine's bare ``np.add.at``."""

    def test_contiguous_run(self, rng):
        a = rng.standard_normal((64, 8), dtype=np.float32)
        b = a.copy()
        rows = np.arange(10, 30)
        vals = rng.standard_normal((20, 8), dtype=np.float32)
        scatter_add_rows_ref(a, rows, vals)
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
        scatter_add_rows_ref(a, rows, vals)
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
        scatter_add_rows_ref(a, rows, vals)
        np.add.at(b, rows, vals)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_empty_and_single(self, rng):
        a = rng.standard_normal((8, 4), dtype=np.float32)
        b = a.copy()
        scatter_add_rows_ref(a, np.zeros(0, np.int64),
                             np.zeros((0, 4), np.float32))
        np.testing.assert_array_equal(a, b)
        v = rng.standard_normal((1, 4), dtype=np.float32)
        scatter_add_rows_ref(a, np.array([5]), v)
        np.add.at(b, np.array([5]), v)
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------- dispatch layer
class TestKernelDispatch:
    def test_mode_validation(self):
        with pytest.raises(ValueError):
            KernelDispatch("warp-speed")
        for m in VALID_MODES:
            KernelDispatch(m)

    def test_auto_resolves_reference_on_cpu(self):
        d = KernelDispatch("auto")
        assert d.mode == "reference" and not d.use_pallas

    def test_tpu_auto_is_reference_and_pallas_never_interprets(
        self, monkeypatch
    ):
        # v5e refuses the kernels' row blocks (tests/test_v5e_compile.py):
        # auto must not pick them, and a forced mode must compile for real
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        d = KernelDispatch("auto")
        assert d.backend == "tpu"
        assert d.mode == "reference" and not d.use_pallas
        for m in ("pallas", "pallas-fused"):
            d = KernelDispatch(m)
            assert d.mode == m and d.use_pallas and not d.interpret

    def test_forced_pallas_interprets_on_cpu(self):
        d = KernelDispatch("pallas")
        assert d.use_pallas and not d.fused_aggregate
        if d.backend == "cpu":
            assert d.interpret
        f = KernelDispatch("pallas-fused")
        assert f.use_pallas and f.fused_aggregate

    @pytest.mark.parametrize("mode", ["reference", "pallas"])
    def test_scatter_add_rows_bit_identity(self, mode, rng):
        d = KernelDispatch(mode)
        a = rng.standard_normal((48, 16), dtype=np.float32)
        b = a.copy()
        # sorted-unique, non-contiguous — the engine's actual row contract
        rows = np.sort(rng.permutation(48)[:30]).astype(np.int64)
        vals = rng.standard_normal((30, 16), dtype=np.float32)
        d.scatter_add_rows(a, rows, vals)
        np.add.at(b, rows, vals)
        np.testing.assert_array_equal(a, b)

    def test_contiguous_fast_path_spans_ref_even_in_pallas_mode(self, rng):
        d = KernelDispatch("pallas")
        a = rng.standard_normal((32, 8), dtype=np.float32)
        vals = rng.standard_normal((10, 8), dtype=np.float32)
        assert d.scatter_add_rows(a, np.arange(4, 14), vals) == "ref"
        assert d.scatter_add_rows(a, np.array([1, 5, 9]),  # strided: kernel
                                  rng.standard_normal((3, 8),
                                                      dtype=np.float32)
                                  ) == "pallas"
        # the engine's scatter span carries the path: the loss layer's
        # contiguous scatter takes the slice add, the regather backward's
        # strided rows the kernel
        import test_runtime as T
        from repro.core import Counters, HostCache, SSOEngine, StorageTier
        from repro.models.gnn.layers import get_gnn
        from repro.obs import Tracer
        from repro.runtime import PipelineConfig

        plan, Xr, Yr = T._setup(n_nodes=300, n_parts=3)
        spec = get_gnn("gcn")
        params = spec.init(jax.random.PRNGKey(0), 16, 24, 8, 2)
        c = Counters()
        c.tracer = Tracer()
        st_ = StorageTier(tempfile.mkdtemp(), counters=c)
        eng = SSOEngine(spec, plan, [16, 24, 8], st_,
                        HostCache(8 << 20, st_, c), c,
                        pipeline=PipelineConfig(depth=0, kernels="pallas"))
        eng.initialize(Xr)
        eng.run_epoch(params, Yr)
        eng.close()
        st_.close()
        paths = {}
        for e in c.tracer.events():
            if e["name"] == "scatter":
                paths.setdefault(e["args"]["pass"], set()).add(
                    e["args"]["path"])
        assert paths["loss"] == {"ref"}
        assert "pallas" in paths["bwd"]

    def test_fused_forward_matches_reference_apply_bitwise(self, rng):
        """The split-jit dispatch compiles the layer apply to the same
        executable the reference path runs — same bits, any model."""
        from repro.models.gnn.layers import get_gnn

        spec = get_gnn("gcn")
        d = KernelDispatch("pallas")
        n, D, H = 40, 16, 8
        params = spec.init(jax.random.PRNGKey(0), D, H, H, 1)
        stack = rng.standard_normal((n + 1, D), dtype=np.float32)
        stack[n] = 0.0
        idx = rng.integers(0, n, 30).astype(np.int32)
        topo = _tiny_topo(rng, n_src=30, n_dst=20)
        fwd = d.fused_forward_fn(spec, activate=True)
        out = fwd(params[0], jnp.asarray(stack), jnp.asarray(idx), topo)
        ga = jnp.asarray(stack[idx])
        ref = jax.jit(
            lambda p, g, t: spec.apply_layer(p, g, t, activate=True)
        )(params[0], ga, topo)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_fused_backward_matches_reference_vjp_bitwise(self, rng):
        from repro.models.gnn.layers import get_gnn

        spec = get_gnn("gcn")
        d = KernelDispatch("pallas")
        n, D, H = 40, 16, 8
        params = spec.init(jax.random.PRNGKey(0), D, H, H, 1)
        stack = rng.standard_normal((n + 1, D), dtype=np.float32)
        stack[n] = 0.0
        idx = rng.integers(0, n, 30).astype(np.int32)
        topo = _tiny_topo(rng, n_src=30, n_dst=20)
        d_out = jnp.asarray(
            rng.standard_normal((20, H), dtype=np.float32)
        )
        bwd = d.fused_backward_fn(spec, activate=False)
        dp, dga = bwd(params[0], jnp.asarray(stack), jnp.asarray(idx),
                      topo, d_out)

        ga = jnp.asarray(stack[idx])

        @jax.jit
        def ref_vjp(p, a, t, g):
            def f(pp, aa):
                return spec.apply_layer(pp, aa, t, activate=False)
            _, vjp = jax.vjp(f, p, a)
            return vjp(g)

        rdp, rdga = ref_vjp(params[0], ga, topo, d_out)
        for x, y in zip(jax.tree.leaves(dp), jax.tree.leaves(rdp)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(dga), np.asarray(rdga))


def _tiny_topo(rng, n_src, n_dst):
    """Minimal work-unit topology: sorted dst, all-real edges."""
    from repro.models.gnn.layers import LocalTopo

    E = 64
    dst = np.sort(rng.integers(0, n_dst, E)).astype(np.int32)
    src = rng.integers(0, n_src, E).astype(np.int32)
    w = rng.standard_normal(E).astype(np.float32)
    deg = np.maximum(np.bincount(dst, minlength=n_dst), 1)
    return LocalTopo(
        src=jnp.asarray(src), dst=jnp.asarray(dst), n_dst=n_dst,
        edge_weight=jnp.asarray(w),
        edge_mask=jnp.ones(E, jnp.float32),
        in_deg=jnp.asarray(deg.astype(np.float32)),
        dst_self=jnp.asarray(
            rng.integers(0, n_src, n_dst).astype(np.int32)
        ),
    )


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


# -------------------------------------------------- engine-level identity
@pytest.mark.slow
def test_engine_pallas_mode_bit_identical_to_reference():
    """End-to-end: one epoch under kernels='pallas' (serial AND depth-2
    pipelined) reproduces the reference engine's loss and gradients
    bitwise. This is the PR's acceptance criterion."""
    import test_runtime as T

    plan, Xr, Yr = T._setup(n_nodes=400, n_parts=3)
    dims = [16, 24, 8]
    l0, g0, _ = T._run(plan, Xr, Yr, dims, "regather", depth=0)
    for kw in [dict(depth=0), dict(depth=2, gather_workers=2)]:
        l1, g1, _ = T._run(plan, Xr, Yr, dims, "regather",
                           kernels="pallas", **kw)
        assert l0 == l1
        T._assert_trees_identical(g0, g1)


@pytest.mark.slow
def test_engine_pallas_fused_deterministic_and_close():
    """pallas-fused trades bit-compat with the reference order for the
    one-kernel aggregate: pipelined must still equal serial bitwise, and
    the loss stays within float tolerance of the reference."""
    import test_runtime as T

    plan, Xr, Yr = T._setup(n_nodes=400, n_parts=3)
    dims = [16, 24, 8]
    l0, g0, _ = T._run(plan, Xr, Yr, dims, "regather", depth=0)
    lf0, gf0, _ = T._run(plan, Xr, Yr, dims, "regather", depth=0,
                         kernels="pallas-fused")
    lf2, gf2, _ = T._run(plan, Xr, Yr, dims, "regather", depth=2,
                         kernels="pallas-fused")
    assert lf0 == lf2
    T._assert_trees_identical(gf0, gf2)
    np.testing.assert_allclose(lf0, l0, rtol=1e-5)
