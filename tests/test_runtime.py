"""Pipeline runtime tests (repro/runtime/ + the cache/storage APIs it needs).

The load-bearing property: a pipelined engine (depth >= 1) executes the exact
same floating-point program as the serial engine (depth == 0) — loss and
gradients are bit-identical, in both regather and snapshot modes, even under
cache thrashing. Plus: write-behind flushes on close, backpressure caps
in-flight bytes, pin/prefetch semantics, dirty-replacement flush, and plan
lookahead.
"""
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (
    Counters, HostCache, SSOEngine, StorageIOQueue, StorageTier, build_plan,
)
from repro.graph import (
    gcn_norm_coeffs, kronecker_graph, switching_aware_partition,
)
from repro.graph.csr import add_self_loops
from repro.graph.synthetic import random_features, random_labels
from repro.models.gnn.layers import get_gnn
from repro.runtime import BufferPool, PipelineConfig


def _setup(n_nodes=900, n_parts=5, d_in=16, seed=0):
    g = add_self_loops(kronecker_graph(n_nodes, 7, seed=seed))
    res = switching_aware_partition(g, n_parts, max_iters=8, seed=seed)
    plan = build_plan(g, res.parts, n_parts, edge_weight=gcn_norm_coeffs(g))
    X = random_features(g.n_nodes, d_in, seed)
    Y = random_labels(g.n_nodes, 8, seed)
    return plan, X[plan.ro.perm], Y[plan.ro.perm]


def _run(plan, Xr, Yr, dims, mode, depth, budget_kb=8192, epochs=1,
         gather_workers=1, transfer_stage=True, device_slots=2,
         async_d2h=True, zero_copy_h2d=True, model="gcn", trace=None):
    spec = get_gnn(model)
    params = spec.init(jax.random.PRNGKey(0), dims[0], dims[1], dims[-1],
                       len(dims) - 1)
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    cache = HostCache(budget_kb << 10, st_, c)
    eng = SSOEngine(
        spec, plan, dims, st_, cache, c, mode=mode,
        pipeline=PipelineConfig(depth=depth, gather_workers=gather_workers,
                                transfer_stage=transfer_stage,
                                device_slots=device_slots,
                                async_d2h=async_d2h,
                                zero_copy_h2d=zero_copy_h2d, trace=trace),
    )
    eng.initialize(Xr)
    for _ in range(epochs):
        loss, grads = eng.run_epoch(params, Yr)
    eng.close()
    st_.close()
    return loss, grads, c


def _assert_trees_identical(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _scatter_units(plan, dims):
    """Backward units that scatter ∇GA rows: every unit of layers l > 0."""
    return len(plan.schedule) * (len(dims) - 2)


# --------------------------------------------------------- engine equivalence
MODELS = ["gcn", "sage", "gat"]


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("mode", ["regather", "snapshot"])
@pytest.mark.parametrize("depth", [1, 3])
def test_pipelined_matches_serial_exactly(mode, depth, model):
    """Pipelined == serial bitwise for every model the benchmark runs: the
    baseline is the same model's serial engine (depth 0)."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    l0, g0, c0 = _run(plan, Xr, Yr, dims, mode, depth=0, model=model)
    l1, g1, c1 = _run(plan, Xr, Yr, dims, mode, depth=depth, model=model)
    assert l0 == l1
    _assert_trees_identical(g0, g1)
    # the pipelined backward scatters on the retire thread, serial inline
    assert c0.retired_scatter_units == 0
    assert c1.retired_scatter_units == _scatter_units(plan, dims)
    if mode == "regather":
        # the pipeline stages really ran on workers
        assert c1.stage_busy_seconds.get("gather", 0.0) > 0.0
        assert c1.cache_prefetches > 0


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("mode", ["regather", "snapshot"])
def test_multiworker_gather_matches_serial(mode, model):
    """gather_workers > 1: units complete out of order on the workers, the
    reassembly buffer re-serializes them — loss and grads stay bit-identical
    to the serial engine in both backward modes, for every model."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    l0, g0, _ = _run(plan, Xr, Yr, dims, mode, depth=0, model=model)
    l1, g1, c1 = _run(plan, Xr, Yr, dims, mode, depth=2, gather_workers=3,
                      model=model)
    assert l0 == l1
    _assert_trees_identical(g0, g1)
    # the backward aux stage really ran on workers
    assert c1.stage_busy_seconds.get("grad_fetch", 0.0) > 0.0


@pytest.mark.parametrize("depth", [0, 2])
def test_degraded_grad_spill_bit_identical(depth):
    """Satellite: cache.put of the grad write-back buffer fails (budget far
    below one partition's buffer) -> direct read-modify-write on storage via
    the I/O queue. Gradients must stay bit-identical to an uncapped-cache
    run and host_scatter_bytes must still be counted."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    l0, g0, c0 = _run(plan, Xr, Yr, dims, "regather", depth=0,
                      budget_kb=8192)
    l1, g1, c1 = _run(plan, Xr, Yr, dims, "regather", depth=depth,
                      budget_kb=4)
    assert l0 == l1
    _assert_trees_identical(g0, g1)
    # pipelined, the read-modify-writes run on the retire thread
    assert c1.retired_scatter_units == (
        _scatter_units(plan, dims) if depth else 0)
    assert c1.cache_bypass > 0          # puts really degraded
    assert c1.host_scatter_bytes > 0    # spill path still counts bytes
    assert c1.host_scatter_bytes == c0.host_scatter_bytes


def test_pipelined_matches_serial_under_thrash():
    """Tight budget: eviction/pin/bypass/degraded-spill paths all engage and
    must not change the math."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    l0, g0, _ = _run(plan, Xr, Yr, dims, "regather", depth=0, budget_kb=64)
    l1, g1, c1 = _run(plan, Xr, Yr, dims, "regather", depth=2, budget_kb=64)
    assert l0 == l1
    _assert_trees_identical(g0, g1)
    assert c1.cache_evictions > 0  # it really did thrash
    assert c1.retired_scatter_units == _scatter_units(plan, dims)


def test_retired_scatter_bit_identical_under_fast_thread_switching():
    """Stress: more gather workers than cores and a 10 us switch interval,
    so the retire thread's scatters interleave with gathers, prefetches
    and evictions at fine grain; two epochs of a tight cache must still
    match serial bitwise."""
    import os
    import sys

    plan, Xr, Yr = _setup()
    dims = [16, 24, 24, 8]
    l0, g0, _ = _run(plan, Xr, Yr, dims, "regather", depth=0, budget_kb=64,
                     epochs=2)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t0 = time.perf_counter()
        l1, g1, c1 = _run(plan, Xr, Yr, dims, "regather", depth=3,
                          budget_kb=64, epochs=2,
                          gather_workers=(os.cpu_count() or 1) + 1)
        assert time.perf_counter() - t0 < 120.0
    finally:
        sys.setswitchinterval(old)
    assert l0 == l1
    _assert_trees_identical(g0, g1)
    assert c1.cache_evictions > 0
    assert c1.retired_scatter_units == 2 * _scatter_units(plan, dims)


def test_pipelined_multi_epoch_stable():
    """Buffer-pool recycling across epochs must not leak state between runs."""
    plan, Xr, Yr = _setup(n_nodes=500, n_parts=4)
    dims = [16, 16, 8]
    l0, g0, _ = _run(plan, Xr, Yr, dims, "regather", depth=0, epochs=3)
    l1, g1, _ = _run(plan, Xr, Yr, dims, "regather", depth=2, epochs=3)
    assert l0 == l1
    _assert_trees_identical(g0, g1)


@pytest.mark.parametrize("depth", [0, 2])
def test_epoch2_sees_new_params(depth):
    """Regression: cached act{l} partitions from epoch 1 must be invalidated
    once the forward rewrites the layer — otherwise epoch 2 with UPDATED
    params gathers epoch-1 activations and silently trains on stale state."""
    plan, Xr, Yr = _setup(n_nodes=500, n_parts=4)
    dims = [16, 16, 8]
    spec = get_gnn("gcn")
    params_a = spec.init(jax.random.PRNGKey(0), 16, 16, 8, 2)
    params_b = spec.init(jax.random.PRNGKey(1), 16, 16, 8, 2)
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    cache = HostCache(64 << 20, st_, c)  # ample budget: everything caches
    eng = SSOEngine(spec, plan, dims, st_, cache, c,
                    pipeline=PipelineConfig(depth=depth))
    eng.initialize(Xr)
    eng.run_epoch(params_a, Yr)
    loss_b, grads_b = eng.run_epoch(params_b, Yr)
    eng.close()
    st_.close()
    # oracle: a fresh engine that never saw params_a
    c2 = Counters()
    st2 = StorageTier(tempfile.mkdtemp(), counters=c2)
    eng2 = SSOEngine(spec, plan, dims, st2, HostCache(64 << 20, st2, c2), c2,
                     pipeline=PipelineConfig(depth=depth))
    eng2.initialize(Xr)
    loss_ref, grads_ref = eng2.run_epoch(params_b, Yr)
    eng2.close()
    st2.close()
    assert loss_b == loss_ref
    _assert_trees_identical(grads_b, grads_ref)


def test_overlap_accounting():
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    t0 = time.perf_counter()
    _, _, c = _run(plan, Xr, Yr, dims, "regather", depth=2)
    wall = time.perf_counter() - t0
    s = c.overlap_summary(wall)
    assert s["busy_seconds"] > 0.0
    assert 0.0 <= s["overlapped_frac"] <= 1.0
    snap = c.snapshot()
    assert any(k.startswith("busy_") for k in snap)


def test_fwd_bwd_overlap_split():
    """The per-stage table separates forward from backward: loss logits
    fetch, regather, and the grad aux fetch all record worker busy time
    under their own names, and overlap_summary reports per-pass fractions
    instead of one blended number."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    t0 = time.perf_counter()
    _, _, c = _run(plan, Xr, Yr, dims, "regather", depth=2)
    wall = time.perf_counter() - t0
    for stage in ("gather", "loss_fetch", "regather", "grad_fetch"):
        assert c.stage_busy_seconds.get(stage, 0.0) > 0.0, stage
    s = c.overlap_summary(wall)
    assert 0.0 <= s["overlapped_frac_fwd"] <= 1.0
    assert 0.0 <= s["overlapped_frac_bwd"] <= 1.0
    assert s["overlapped_seconds_fwd"] <= s["busy_seconds"]
    assert s["overlapped_seconds_bwd"] <= s["busy_seconds"]


@pytest.mark.parametrize("model,dims,passes", [
    # both layers narrow: 2 forward + 2 backward passes
    ("sage", [16, 12, 8], 4),
    # no layer narrows
    ("gcn", [16, 16, 16], 0),
    # GCN narrowing at both layers transforms first in both passes
    ("gcn", [16, 12, 8], 4),
])
def test_narrow_aggregate_passes_counts_transform_first_passes(
        model, dims, passes):
    plan, Xr, Yr = _setup()
    _, _, c = _run(plan, Xr, Yr, dims, "regather", depth=2, model=model)
    assert c.narrow_aggregate_passes == passes


@pytest.mark.parametrize("model,dims,passes", [
    # three attention layers, each once forward and once backward
    ("gat", [16, 16, 16, 8], 6),
    ("sage", [16, 12, 8], 0),
])
def test_attention_passes_counts_gat_layer_passes(model, dims, passes):
    plan, Xr, Yr = _setup()
    _, _, c = _run(plan, Xr, Yr, dims, "regather", depth=2, model=model)
    assert c.attention_passes == passes
    # each pass over the plan's 5 units, every one dense enough for the
    # dense path
    assert c.attention_units == c.dense_attention_units == 5 * passes


# ------------------------------------------------------------- StorageIOQueue
def test_write_behind_flushes_on_close(rng):
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    st_.alloc("a", (64, 8), np.float32)
    data = rng.standard_normal((64, 8)).astype(np.float32)
    q = StorageIOQueue(st_, counters=c)
    for i in range(8):
        q.submit_write("a", i * 8, data[i * 8 : (i + 1) * 8].copy())
    q.close()
    np.testing.assert_array_equal(st_.read_rows("a", 0, 64), data)
    with pytest.raises(RuntimeError):
        q.submit_write("a", 0, data[:8])
    st_.close()


def test_backpressure_caps_inflight_bytes(rng):
    class SlowTier(StorageTier):
        def write_rows(self, name, row0, arr):
            time.sleep(0.003)
            super().write_rows(name, row0, arr)

    c = Counters()
    st_ = SlowTier(tempfile.mkdtemp(), counters=c)
    st_.alloc("a", (1024, 64), np.float32)
    row = rng.standard_normal((4, 64)).astype(np.float32)  # 1 KiB
    cap = 3 * row.nbytes
    q = StorageIOQueue(st_, max_inflight_bytes=cap, counters=c)
    for i in range(32):
        q.submit_write("a", i * 4, row.copy())
    q.close()
    assert q.max_inflight_observed <= cap
    assert c.stage_stall_seconds.get("write_submit", 0.0) > 0.0
    st_.close()


def test_async_read_roundtrip(rng):
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    st_.alloc("a", (32, 4), np.float32)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    q = StorageIOQueue(st_, counters=c)
    q.submit_write("a", 0, x)
    fut = q.submit_read("a", 8, 16)
    np.testing.assert_array_equal(fut.result(timeout=5), x[8:16])
    q.close()
    st_.close()


# ------------------------------------------------------------ vectored reads
def test_read_rows_batched_counts_one_op(rng):
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    st_.alloc("a", (64, 8), np.float32)
    st_.alloc("b", (64, 8), np.float32)
    xa = rng.standard_normal((64, 8)).astype(np.float32)
    xb = rng.standard_normal((64, 8)).astype(np.float32)
    st_.write_rows("a", 0, xa)
    st_.write_rows("b", 0, xb)
    ops0, bytes0 = c.storage_read_ops, c.storage_read_bytes
    outs = st_.read_rows_batched([("a", 0, 8), ("a", 32, 40), ("b", 4, 12)])
    np.testing.assert_array_equal(outs[0], xa[0:8])
    np.testing.assert_array_equal(outs[1], xa[32:40])
    np.testing.assert_array_equal(outs[2], xb[4:12])
    assert c.storage_read_ops - ops0 == 1         # ONE vectored submission
    assert c.storage_read_bytes - bytes0 == 3 * 8 * 8 * 4
    # each discontiguous range rounds to page granularity separately
    assert c.storage_read_paged_bytes >= 3 * st_.page
    assert st_.read_rows_batched([]) == []        # empty batch: no ops
    assert c.storage_read_ops - ops0 == 1
    st_.close()


def test_submit_read_batch_fifo_after_write(rng):
    """A batched read queued after a write must see the written data — the
    FIFO ordering the engine's degraded-mode grad spills rely on."""
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    st_.alloc("a", (32, 4), np.float32)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    q = StorageIOQueue(st_, counters=c)
    q.submit_write("a", 0, x)
    outs = q.submit_read_batch([("a", 0, 8), ("a", 16, 24)]).result(timeout=5)
    np.testing.assert_array_equal(outs[0], x[0:8])
    np.testing.assert_array_equal(outs[1], x[16:24])
    q.close()
    with pytest.raises(RuntimeError):
        q.submit_read_batch([("a", 0, 8)])
    st_.close()


# ------------------------------------------------------ cache pin / prefetch
def _mk_cache(budget):
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    st_.alloc("back", (1024, 64), np.float32)
    return HostCache(budget, st_, c), st_, c


def test_prefetch_pin_blocks_eviction(rng):
    entry = rng.standard_normal((64, 64)).astype(np.float32)  # 16 KiB
    cache, st_, c = _mk_cache(int(entry.nbytes * 2.5))
    assert cache.prefetch(("act", 0, 0), loader=lambda: entry.copy(), pin=True)
    assert c.cache_prefetches == 1
    # pressure: two more entries want the space
    cache.get(("act", 1, 0), loader=lambda: entry.copy())
    cache.get(("act", 2, 0), loader=lambda: entry.copy())
    assert cache.contains(("act", 0, 0))  # pinned survived
    cache.unpin(("act", 0, 0))
    cache.get(("act", 3, 0), loader=lambda: entry.copy())
    cache.get(("act", 4, 0), loader=lambda: entry.copy())
    assert not cache.contains(("act", 0, 0))  # unpinned got evicted
    st_.close()


def test_pin_counts_compose(rng):
    entry = rng.standard_normal((16, 64)).astype(np.float32)
    cache, st_, _ = _mk_cache(1 << 20)
    cache.prefetch(("act", 0, 0), loader=lambda: entry, pin=True)
    assert cache.pin(("act", 0, 0))        # second holder
    cache.unpin(("act", 0, 0))             # first release: still pinned
    assert cache._entries[("act", 0, 0)].pinned == 1
    cache.unpin(("act", 0, 0))
    assert cache._entries[("act", 0, 0)].pinned == 0
    cache.unpin(("act", 0, 0))             # floor at zero
    assert cache._entries[("act", 0, 0)].pinned == 0
    assert not cache.pin(("missing", 0, 0))
    st_.close()


def test_prefetch_many_batches_and_pins():
    cache, st_, c = _mk_cache(1 << 20)
    calls = []

    def batch_loader(missing):
        calls.append(list(missing))
        return [np.full((4, 4), k[2], np.float32) for k in missing]

    keys = [("act", 0, q) for q in range(4)]
    res = cache.prefetch_many(keys, batch_loader, pin=True)
    assert all(res[k] for k in keys)
    assert len(calls) == 1 and calls[0] == keys   # ONE batched load
    assert c.cache_prefetches == 4
    for k in keys:
        assert cache._entries[k].pinned == 1
        np.testing.assert_array_equal(
            cache.peek(k), np.full((4, 4), k[2], np.float32)
        )
    # all resident now: no second load, pin=False leaves counts alone
    res2 = cache.prefetch_many(keys, batch_loader, pin=False)
    assert all(res2[k] for k in keys) and len(calls) == 1
    assert all(cache._entries[k].pinned == 1 for k in keys)
    st_.close()


def test_prefetch_many_over_budget_bypasses():
    entry_bytes = 4 * 4 * 4
    cache, st_, c = _mk_cache(entry_bytes)  # room for exactly one entry

    def batch_loader(missing):
        return [np.full((4, 4), k[2], np.float32) for k in missing]

    keys = [("act", 0, q) for q in range(3)]
    res = cache.prefetch_many(keys, batch_loader, pin=True)
    # a pinned resident entry can't be evicted, so only one fits
    assert sum(bool(v) for v in res.values()) == 1
    assert c.cache_bypass == 2
    st_.close()


def test_acquire_release(rng):
    entry = rng.standard_normal((16, 64)).astype(np.float32)
    cache, st_, _ = _mk_cache(1 << 20)
    assert cache.acquire(("grad", 0, 0)) is None
    cache.put(("grad", 0, 0), entry, dirty=True, spill_name="back")
    arr = cache.acquire(("grad", 0, 0))
    np.testing.assert_array_equal(arr, entry)
    assert cache._entries[("grad", 0, 0)].pinned == 1
    cache.release(("grad", 0, 0))
    assert cache._entries[("grad", 0, 0)].pinned == 0
    st_.close()


def test_put_replacing_dirty_entry_flushes_first(rng):
    """Regression: replacing a dirty entry used to silently drop its
    unflushed data."""
    cache, st_, _ = _mk_cache(1 << 20)
    a = np.full((32, 64), 3.0, np.float32)
    b = np.full((32, 64), 7.0, np.float32)
    cache.put(("grad", 0, 0), a, dirty=True, spill_name="back", spill_row0=0)
    cache.put(("grad", 0, 0), b, dirty=False)  # clean replacement
    got = st_.read_rows("back", 0, 32)
    np.testing.assert_array_equal(got, a)      # old dirty data was flushed
    np.testing.assert_array_equal(cache.peek(("grad", 0, 0)), b)
    st_.close()


# ------------------------------------------------------- storage satellites
def test_scattered_empty_read_not_charged():
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    st_.alloc("a", (128, 16), np.float32)
    out = st_.read_rows_scattered("a", np.array([], np.int64))
    assert out.shape[0] == 0
    assert c.storage_read_ops == 0
    assert c.storage_read_bytes == 0
    assert c.storage_read_paged_bytes == 0
    st_.close()


# ------------------------------------------------------------ plan lookahead
def test_plan_lookahead_and_upcoming_parts():
    plan, _, _ = _setup(n_nodes=600, n_parts=4)
    sched = plan.schedule
    la = plan.lookahead(0, 2)
    assert [u.p for u in la] == sched[1:3]
    assert plan.lookahead(0, 0) == []
    assert plan.lookahead(len(sched) - 1, 3) == []  # truncates at the end
    up = plan.upcoming_parts(0, 2)
    expect = sorted(
        {int(q) for u in la for q in u.req_parts}
    )
    assert up.tolist() == expect
    assert plan.upcoming_parts(len(sched) - 1, 2).size == 0


# ------------------------------------------------- device-transfer stage
@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("mode", ["regather", "snapshot"])
@pytest.mark.parametrize("slots", [1, 2])
def test_transfer_stage_bit_identical(mode, slots, model):
    """The async H2D/D2H device-transfer stage (at 1 and 2 device slots)
    must not change the math — forward, regather and snapshot backward all
    stay bit-identical to the serial engine, for every model."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    l0, g0, _ = _run(plan, Xr, Yr, dims, mode, depth=0, model=model)
    l1, g1, c1 = _run(plan, Xr, Yr, dims, mode, depth=2,
                      transfer_stage=True, device_slots=slots, model=model)
    assert l0 == l1
    _assert_trees_identical(g0, g1)
    # H2D staging and D2H retire really ran on the transfer/retire threads
    assert c1.stage_busy_seconds.get("h2d", 0.0) > 0.0
    assert c1.stage_busy_seconds.get("d2h", 0.0) > 0.0


@pytest.mark.parametrize("model", MODELS)
def test_transfer_stage_off_bit_identical(model):
    """The inline jnp.asarray path (transfer stage disabled) remains
    available and bit-identical."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    l0, g0, _ = _run(plan, Xr, Yr, dims, "regather", depth=0, model=model)
    l1, g1, c1 = _run(plan, Xr, Yr, dims, "regather", depth=2,
                      transfer_stage=False, model=model)
    assert l0 == l1
    _assert_trees_identical(g0, g1)
    assert "h2d" not in c1.stage_busy_seconds
    # inline-staged inputs: the scatter stays on the compute loop
    assert c1.retired_scatter_units == 0


@pytest.mark.parametrize("model", MODELS)
def test_transfer_stage_sync_d2h_bit_identical(model):
    """async_d2h off: H2D staging still on the transfer thread, result
    copies synchronous — still bit-identical."""
    plan, Xr, Yr = _setup(n_nodes=500, n_parts=4)
    dims = [16, 16, 8]
    l0, g0, _ = _run(plan, Xr, Yr, dims, "regather", depth=0, model=model)
    l1, g1, c1 = _run(plan, Xr, Yr, dims, "regather", depth=2,
                      async_d2h=False, model=model)
    assert l0 == l1
    _assert_trees_identical(g0, g1)
    # synchronous copies: the scatter stays on the compute loop
    assert c1.retired_scatter_units == 0


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("mode", ["regather", "snapshot"])
def test_zero_copy_h2d_off_bit_identical(mode, model):
    """zero_copy_h2d=False forces the copying jnp.array staging — the math
    must not depend on whether device_put aliased the pinned buffer or
    copied it."""
    plan, Xr, Yr = _setup(n_nodes=500, n_parts=4)
    dims = [16, 16, 8]
    l0, g0, _ = _run(plan, Xr, Yr, dims, mode, depth=0, model=model)
    l1, g1, _ = _run(plan, Xr, Yr, dims, mode, depth=2,
                     zero_copy_h2d=False, model=model)
    assert l0 == l1
    _assert_trees_identical(g0, g1)


def test_device_slot_pool_bounds_staging():
    import threading

    from repro.runtime import DeviceSlotPool, PipelineExecutor

    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    rt = PipelineExecutor(
        PipelineConfig(depth=4, gather_workers=2, device_slots=2), c, st_
    )
    items = list(range(20))
    lock = threading.Lock()
    staged = {"cur": 0, "peak": 0}

    def transfer_fn(i, buf, aux):
        with lock:
            staged["cur"] += 1
            staged["peak"] = max(staged["peak"], staged["cur"])
        return buf + 1, aux

    out = []
    for it, buf, aux in rt.run_stream(
        items, lambda i: i * 10, transfer_fn=transfer_fn
    ):
        time.sleep(0.001)   # let the transfer thread try to run ahead
        with lock:
            staged["cur"] -= 1
        out.append((it, buf, aux))
    assert out == [(i, i * 10 + 1, None) for i in items]
    # staged-but-unconsumed units never exceed the slot count
    assert staged["peak"] <= 2
    assert c.stage_busy_seconds.get("h2d", 0.0) > 0.0
    rt.close()
    st_.close()

    # the pool primitive itself: acquire blocks at capacity, release wakes
    abort = threading.Event()
    pool = DeviceSlotPool(1, c, abort)
    s0 = pool.acquire()
    got = []
    t = threading.Thread(target=lambda: got.append(pool.acquire()))
    t.start()
    time.sleep(0.05)
    assert not got          # second acquire is blocked on the single slot
    pool.release(s0)
    t.join(timeout=2)
    assert got and pool.peak_in_use == 1


def test_run_stream_serial_applies_transfer_inline():
    from repro.runtime import PipelineExecutor

    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    rt = PipelineExecutor(PipelineConfig(depth=0), c, st_)
    out = list(rt.run_stream(
        [1, 2], lambda i: i * 10,
        transfer_fn=lambda i, buf, aux: (buf + 5, aux),
    ))
    assert out == [(1, 15, None), (2, 25, None)]
    rt.close()
    st_.close()


def test_retire_write_lands_and_drains(rng):
    """retire_write: copy_to_host_async + deferred np.asarray on the retire
    thread; drain_writes barriers both the retire queue and the writer."""
    from repro.runtime import PipelineExecutor

    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    st_.alloc("a", (64, 8), np.float32)
    rt = PipelineExecutor(PipelineConfig(depth=2), c, st_)
    x = rng.standard_normal((64, 8)).astype(np.float32)
    dev = jax.device_put(x)
    for i in range(8):
        sl = dev[i * 8 : (i + 1) * 8]
        sl.copy_to_host_async()
        rt.retire_write("a", i * 8, sl)
    rt.drain_writes()
    np.testing.assert_array_equal(st_.read_rows("a", 0, 64), x)
    assert c.stage_busy_seconds.get("d2h", 0.0) > 0.0
    assert c.d2h_bytes == x.nbytes
    rt.close()
    st_.close()


def _retire_executor(device_slots=2):
    from repro.runtime import PipelineExecutor

    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    rt = PipelineExecutor(
        PipelineConfig(depth=2, device_slots=device_slots), c, st_)
    return rt, c, st_


def test_retire_runs_callbacks_in_fifo_order_on_one_thread(rng):
    """retire: each callback gets its own host copy of the device result,
    in submission order, all on one thread that is not the caller's."""
    rt, c, st_ = _retire_executor()
    x = rng.standard_normal((64, 8)).astype(np.float32)
    dev = jax.device_put(x)
    got = []
    for i in range(16):
        sl = dev[i * 4 : (i + 1) * 4]
        sl.copy_to_host_async()
        rt.retire(sl, lambda a, i=i: got.append(
            (i, threading.get_ident(), a.copy())))
    rt.drain_writes()
    assert [g[0] for g in got] == list(range(16))
    assert len({g[1] for g in got}) == 1
    assert got[0][1] != threading.get_ident()
    np.testing.assert_array_equal(np.concatenate([g[2] for g in got]), x)
    assert c.d2h_bytes == x.nbytes
    assert c.stage_busy_seconds.get("d2h", 0.0) > 0.0
    rt.close()
    st_.close()


def test_retire_backpressure_charges_the_named_stall():
    """A caller over the cap (``max(2, 2 * device_slots)`` pending) waits
    until the retire thread frees a place, charged to its own stall name."""
    rt, c, st_ = _retire_executor(device_slots=1)   # cap 2
    go = threading.Event()
    done = []

    def slow(a):
        go.wait(5)
        done.append(int(a[0]))

    for i in range(2):
        rt.retire(jax.device_put(np.full(4, i, np.float32)), slow,
                  stall="retire_submit_test")
    threading.Timer(0.2, go.set).start()
    t0 = time.perf_counter()
    rt.retire(jax.device_put(np.full(4, 2, np.float32)), slow,
              stall="retire_submit_test")
    assert time.perf_counter() - t0 >= 0.15
    rt.drain_writes()
    assert done == [0, 1, 2]
    assert c.stage_stall_seconds["retire_submit_test"] >= 0.15
    assert "d2h_submit" not in c.stage_stall_seconds
    rt.close()
    st_.close()


def test_retire_error_surfaces_once_and_drops_the_retires_behind_it():
    """The first failing callback drops the retires queued behind it; the
    original exception is raised by the next drain, once, and the executor
    still closes."""
    rt, c, st_ = _retire_executor(device_slots=4)   # cap 8: nothing waits
    go = threading.Event()
    ran = []
    err = ValueError("scatter failed")

    def cb(a):
        go.wait(5)
        ran.append(int(a[0]))
        if a[0] == 1:
            raise err

    for i in range(5):
        rt.retire(jax.device_put(np.full(4, i, np.float32)), cb)
    go.set()
    with pytest.raises(ValueError) as ei:
        rt.drain_writes()
    assert ei.value is err
    assert ran == [0, 1]
    rt.drain_writes()                   # surfaced once
    rt.close()
    st_.close()


def test_cancel_retires_waits_out_the_running_one_and_drops_the_rest():
    """The unwind path: the running callback finishes, the queued ones never
    run, and its error is forgotten (the caller raises its own)."""
    rt, c, st_ = _retire_executor(device_slots=4)
    started, go = threading.Event(), threading.Event()
    ran = []

    def cb(a):
        started.set()
        go.wait(5)
        ran.append(int(a[0]))
        raise ValueError("after the fault")

    for i in range(4):
        rt.retire(jax.device_put(np.full(4, i, np.float32)), cb)
    assert started.wait(5)
    threading.Timer(0.1, go.set).start()
    rt.cancel_retires()
    assert ran == [0]
    rt.drain_writes()                   # nothing pending, no error
    rt.close()
    st_.close()


def test_retired_scatter_spans_carry_their_unit(tmp_path):
    """Traced: every backward layer's ``scatter`` span runs off the compute
    thread, in unit order per layer, with the unit's stream/seq/layer/pass;
    the loss layer's slice adds stay on the compute thread."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 24, 8]
    _, _, c = _run(plan, Xr, Yr, dims, "regather", depth=2,
                   trace=str(tmp_path / "trace.json"))
    ev = [e for e in c.tracer.events()
          if e["ph"] == "X" and e["name"] == "scatter"]
    main = threading.get_ident()
    n = len(plan.schedule)
    loss = [e for e in ev if e["args"]["pass"] == "loss"]
    bwd = [e for e in ev if e["args"]["pass"] == "bwd"]
    assert len(loss) == n and all(e["tid"] == main for e in loss)
    assert len(bwd) == c.retired_scatter_units == _scatter_units(plan, dims)
    assert all(e["tid"] != main for e in bwd)
    assert [(e["args"]["layer"], e["args"]["seq"]) for e in bwd] == (
        [(2, s) for s in range(n)] + [(1, s) for s in range(n)])
    assert len({(e["args"]["layer"], e["args"]["stream"]) for e in bwd}) == 2


# --------------------------------------------------------------- buffer pool
def test_buffer_pool_recycles():
    pool = BufferPool()
    a = pool.acquire((8, 4), np.float32)
    pool.release(a)
    b = pool.acquire((8, 4), np.float32)
    assert b is a
    assert pool.allocations == 1
    cdiff = pool.acquire((8, 8), np.float32)
    assert cdiff is not a
    assert pool.allocations == 2


def test_buffer_pool_byte_cap_trims_stalest_bucket():
    """Satellite: free lists are byte-capped — the stalest shape bucket is
    dropped on overflow instead of pinning peak memory forever."""
    c = Counters()
    one = 32 * 32 * 4
    pool = BufferPool(max_bytes=3 * one, counters=c)
    a = pool.acquire((32, 32), np.float32)     # bucket A
    b = pool.acquire((16, 64), np.float32)     # bucket B (same nbytes)
    pool.release(a)
    pool.release(b)                            # A is now the stalest bucket
    extra = [pool.acquire((8, 128), np.float32) for _ in range(3)]
    for e in extra:                            # bucket C overflows the cap
        pool.release(e)
    assert pool.trims >= 1
    assert c.pool_trims == pool.trims
    assert pool.free_bytes <= pool.max_bytes
    # the stalest bucket (A) was dropped; a fresh acquire must allocate
    n0 = pool.allocations
    a2 = pool.acquire((32, 32), np.float32)
    assert a2 is not a
    assert pool.allocations == n0 + 1


def test_buffer_pool_release_guards(rng):
    """Satellite: release refuses non-contiguous views, foreign/duplicate
    buffers, non-ndarrays, and buffers still owned by a pending
    submit_write."""
    c = Counters()
    pool = BufferPool(counters=c)
    a = pool.acquire((16, 8), np.float32)
    pool.release(a[:4])                 # view of a pooled buffer
    pool.release(np.zeros((4, 4))[::2])  # non-contiguous
    pool.release("not an array")
    pool.release(np.zeros((4, 4), np.float32))  # never issued by this pool
    assert pool.rejected == 4
    assert c.pool_release_rejects == 4
    pool.release(a)
    pool.release(a)                     # double release: second is refused
    assert pool.rejected == 5

    # ownership: a buffer queued on the write-behind path must not recycle
    class SlowTier(StorageTier):
        def write_rows(self, name, row0, arr):
            time.sleep(0.05)
            super().write_rows(name, row0, arr)

    st_ = SlowTier(tempfile.mkdtemp(), counters=c)
    st_.alloc("a", (64, 8), np.float32)
    q = StorageIOQueue(st_, counters=c)
    pool2 = BufferPool(counters=c, owner_check=q.owns)
    buf = pool2.acquire((8, 8), np.float32)
    buf[:] = rng.standard_normal((8, 8)).astype(np.float32)
    q.submit_write("a", 0, buf)
    pool2.release(buf)                  # write still in flight: refused
    assert pool2.rejected == 1
    q.drain()
    pool2.release(buf)                  # retired: recycles fine
    assert pool2.acquire((8, 8), np.float32) is buf
    q.close()
    st_.close()


def test_recycled_buffer_tails_zeroed_in_grad_and_loss_paths():
    """Satellite regression: a recycled pool buffer full of garbage must not
    leak into the padded tail rows of grad-fetch or loss-fetch outputs."""
    plan, Xr, Yr = _setup(n_nodes=500, n_parts=4)
    dims = [16, 16, 8]
    spec = get_gnn("gcn")
    params = spec.init(jax.random.PRNGKey(0), 16, 16, 8, 2)
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    cache = HostCache(8 << 20, st_, c)
    eng = SSOEngine(spec, plan, dims, st_, cache, c,
                    pipeline=PipelineConfig(depth=1))
    eng.initialize(Xr)
    eng.forward(params)                 # warms the cache and the pool
    u = plan.unit(plan.schedule[0])
    cache.put(("grad", 1, u.p),
              np.full((u.n_dst, dims[1]), 2.0, np.float32))
    # poison pooled buffers of the exact shapes the fetch paths will reuse
    for shape in [(u.d_pad, dims[1]), (u.r_pad, dims[0])]:
        junk = eng._rt.pool.acquire(shape, np.float32)
        junk[:] = np.nan
        eng._rt.pool.release(junk)
    out = eng._grad_fetch(1, u.p)
    np.testing.assert_array_equal(out[: u.n_dst], 2.0)
    assert np.all(out[u.n_dst:] == 0)   # padded tail rezeroed, no NaN leak
    ga = eng._gather(0, u, u.r_pad)
    assert np.all(np.isfinite(ga))
    assert np.all(ga[u.n_req:] == 0)
    eng.close()
    st_.close()


# ------------------------------------------------------- run_stream harness
def test_run_stream_multiworker_order_and_aux():
    """4 gather workers with skewed per-item latency: the reassembly buffer
    must re-serialize completions into input order, and the aux stage's
    result must ride along with its own item."""
    from repro.runtime import PipelineExecutor

    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    rt = PipelineExecutor(
        PipelineConfig(depth=3, gather_workers=4), c, st_
    )
    items = list(range(24))

    def gather_fn(i):
        time.sleep((i % 3) * 0.002)  # later items often finish first
        return i * 10

    out = list(rt.run_stream(
        items, gather_fn, aux_fn=lambda i: i + 100,
        gather_stage="g", aux_stage="a",
    ))
    assert [it for it, _, _ in out] == items
    assert [buf for _, buf, _ in out] == [i * 10 for i in items]
    assert [aux for _, _, aux in out] == [i + 100 for i in items]
    assert c.stage_busy_seconds.get("g", 0.0) > 0.0
    assert c.stage_busy_seconds.get("a", 0.0) > 0.0
    rt.close()
    st_.close()


def test_run_stream_serial_runs_aux_inline():
    from repro.runtime import PipelineExecutor

    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    rt = PipelineExecutor(PipelineConfig(depth=0), c, st_)
    order = []

    def gather_fn(i):
        order.append(("g", i))
        return i

    def aux_fn(i):
        order.append(("a", i))
        return -i

    out = list(rt.run_stream([1, 2], gather_fn, aux_fn=aux_fn))
    assert out == [(1, 1, -1), (2, 2, -2)]
    # serial order is gather-then-aux per unit, same as the old inline path
    assert order == [("g", 1), ("a", 1), ("g", 2), ("a", 2)]
    rt.close()
    st_.close()


# ----------------------------------------------------------- error handling
@pytest.mark.parametrize("workers", [1, 3])
def test_pipeline_stage_error_propagates(workers):
    from repro.runtime import PipelineExecutor

    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    rt = PipelineExecutor(
        PipelineConfig(depth=2, gather_workers=workers), c, st_
    )

    def bad_gather(it):
        raise ValueError(f"boom {it}")

    with pytest.raises(ValueError, match="boom"):
        for _ in rt.run_stream(list(range(8)), bad_gather):
            pass
    rt.close()
    st_.close()
