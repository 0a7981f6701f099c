"""Storage tier + host cache unit tests."""
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cache import HostCache
from repro.core.counters import Counters
from repro.core.storage import StorageTier


@pytest.fixture()
def storage():
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    yield st_
    st_.close()


class TestStorage:
    def test_roundtrip(self, storage, rng):
        storage.alloc("a", (100, 16), np.float32)
        x = rng.standard_normal((40, 16)).astype(np.float32)
        storage.write_rows("a", 30, x)
        y = storage.read_rows("a", 30, 70)
        np.testing.assert_array_equal(x, y)

    def test_page_accounting(self, storage):
        storage.alloc("a", (100, 16), np.float32)
        x = np.zeros((1, 16), np.float32)  # 64B write -> 1 page
        storage.write_rows("a", 0, x)
        assert storage.counters.storage_write_bytes == 64
        assert storage.counters.storage_write_paged_bytes == 16 * 1024

    def test_scattered_read_amplification(self, storage, rng):
        """Vertex-granular random reads amplify to >= one page per run
        (the paper's Appendix F anti-pattern)."""
        storage.alloc("a", (4096, 16), np.float32)
        rows = np.arange(0, 4096, 64)  # 64 scattered single rows
        storage.read_rows_scattered("a", rows)
        c = storage.counters
        assert c.storage_read_paged_bytes >= 64 * 16 * 1024
        assert c.storage_read_paged_bytes > 10 * c.storage_read_bytes

    def test_free_and_realloc(self, storage):
        storage.alloc("a", (10, 4))
        assert storage.exists("a")
        storage.free("a")
        assert not storage.exists("a")
        storage.alloc("a", (20, 4))
        assert storage.shape("a") == (20, 4)


class TestCache:
    def _mk(self, budget):
        c = Counters()
        st_ = StorageTier(tempfile.mkdtemp(), counters=c)
        st_.alloc("back", (1024, 64), np.float32)
        return HostCache(budget, st_, c), st_, c

    def test_hit_miss(self, rng):
        cache, st_, c = self._mk(1 << 20)
        arr = rng.standard_normal((16, 64)).astype(np.float32)
        got = cache.get(("act", 0, 0), loader=lambda: arr)
        np.testing.assert_array_equal(got, arr)
        assert c.cache_misses == 1
        got2 = cache.get(("act", 0, 0), loader=lambda: 1 / 0)
        np.testing.assert_array_equal(got2, arr)
        assert c.cache_hits == 1
        st_.close()

    def test_layerwise_lru_eviction(self, rng):
        """Whole least-recently-used LAYER evicts first (paper §4)."""
        entry = rng.standard_normal((100, 64)).astype(np.float32)  # 25.6KB
        cache, st_, c = self._mk(int(entry.nbytes * 4.5))
        for layer in range(2):
            for p in range(2):
                cache.get(("act", layer, p), loader=lambda: entry.copy())
        # touch layer 0 -> layer 1 becomes LRU
        cache.get(("act", 0, 0), loader=lambda: 1 / 0)
        cache.get(("act", 0, 1), loader=lambda: 1 / 0)
        # force eviction: new entry
        cache.get(("act", 2, 0), loader=lambda: entry.copy())
        assert cache.contains(("act", 0, 0)) and cache.contains(("act", 0, 1))
        assert not (
            cache.contains(("act", 1, 0)) and cache.contains(("act", 1, 1))
        )
        st_.close()

    def test_dirty_eviction_writes_back(self, rng):
        cache, st_, c = self._mk(1 << 18)  # 256KB
        buf = rng.standard_normal((512, 64)).astype(np.float32)  # 128KB
        ok = cache.put(("grad", 0, 0), buf.copy(), dirty=True,
                       spill_name="back", spill_row0=0)
        assert ok
        # force eviction with another large entry
        cache.get(("act", 1, 0), loader=lambda: buf.copy())
        cache.get(("act", 2, 0), loader=lambda: buf.copy())
        assert not cache.contains(("grad", 0, 0))
        got = st_.read_rows("back", 0, 512)
        np.testing.assert_array_equal(got, buf)
        st_.close()

    def test_oversize_streams_through(self, rng):
        cache, st_, c = self._mk(1 << 12)  # 4KB budget
        big = rng.standard_normal((512, 64)).astype(np.float32)
        got = cache.get(("act", 0, 0), loader=lambda: big)
        np.testing.assert_array_equal(got, big)
        assert c.cache_bypass == 1
        assert not cache.contains(("act", 0, 0))
        st_.close()

    @given(budget_kb=st.sampled_from([4, 64, 1024]), n_ops=st.integers(5, 40))
    @settings(max_examples=10, deadline=None)
    def test_budget_never_exceeded(self, budget_kb, n_ops):
        rng = np.random.default_rng(0)
        cache, st_, c = self._mk(budget_kb << 10)
        for i in range(n_ops):
            key = ("act", i % 3, i % 5)
            arr = rng.standard_normal((rng.integers(4, 64), 64)).astype(
                np.float32
            )
            cache.get(key, loader=lambda a=arr: a)
            assert cache.used_bytes <= cache.budget
        st_.close()


class TestReservations:
    """Satellite: the cache claims budget BEFORE materializing an incoming
    block (reserve / put(reserved_bytes) / prefetch_many(sizes)), so host
    memory never transiently exceeds budget_bytes; peak_bytes records the
    high-water mark the regression pins."""

    def _mk(self, budget):
        c = Counters()
        st_ = StorageTier(tempfile.mkdtemp(), counters=c)
        st_.alloc("back", (1024, 64), np.float32)
        return HostCache(budget, st_, c), st_, c

    def test_reserve_put_roundtrip(self, rng):
        entry = rng.standard_normal((16, 64)).astype(np.float32)
        cache, st_, _ = self._mk(3 * entry.nbytes)
        assert cache.reserve(entry.nbytes)
        assert cache.used_bytes == entry.nbytes     # claim counts now
        assert cache.put(("grad", 0, 0), entry.copy(),
                         reserved_bytes=entry.nbytes)
        assert cache.used_bytes == entry.nbytes     # claim consumed, once
        # an impossible claim is refused without touching residency
        assert not cache.reserve(cache.budget + 1)
        assert cache.contains(("grad", 0, 0))
        # abandoned claim releases its bytes
        assert cache.reserve(entry.nbytes)
        cache.unreserve(entry.nbytes)
        assert cache.used_bytes == entry.nbytes
        st_.close()

    def test_reserve_evicts_before_materialization(self, rng):
        entry = rng.standard_normal((64, 64)).astype(np.float32)
        cache, st_, _ = self._mk(int(entry.nbytes * 2.5))
        cache.get(("act", 0, 0), loader=lambda: entry.copy())
        cache.get(("act", 1, 0), loader=lambda: entry.copy())
        # claiming a third entry's bytes evicts NOW, before the caller
        # allocates the block — the old put() path allocated first
        assert cache.reserve(entry.nbytes)
        assert cache.used_bytes <= cache.budget
        assert len([k for k in [("act", 0, 0), ("act", 1, 0)]
                    if cache.contains(k)]) == 1
        cache.unreserve(entry.nbytes)
        st_.close()

    def test_prefetch_many_sizes_never_overshoots(self):
        blk = 64 * 64 * 4
        cache, st_, c = self._mk(2 * blk)     # room for exactly two blocks
        keys = [("act", 0, q) for q in range(4)]
        sizes = {k: blk for k in keys}
        seen = {}

        def batch_loader(missing):
            # the budget already covers the claims when the load runs —
            # materializing here can no longer overshoot
            seen["keys"] = list(missing)
            seen["used_at_load"] = cache.used_bytes
            return [np.full((64, 64), k[2], np.float32) for k in missing]

        res = cache.prefetch_many(keys, batch_loader, pin=True, sizes=sizes)
        assert sum(bool(v) for v in res.values()) == 2
        assert len(seen["keys"]) == 2          # unfittable keys NOT read
        assert seen["used_at_load"] == 2 * blk  # claims held during load
        assert cache.peak_bytes <= cache.budget  # the regression
        assert c.cache_bypass == 2
        for k in seen["keys"]:
            np.testing.assert_array_equal(
                cache.peek(k), np.full((64, 64), k[2], np.float32)
            )
        st_.close()

    def test_get_size_hint_reserves_before_load(self):
        blk = 64 * 64 * 4
        cache, st_, c = self._mk(2 * blk)
        mk = lambda v: np.full((64, 64), v, np.float32)
        cache.get(("act", 0, 0), loader=lambda: mk(0))
        cache.get(("act", 1, 0), loader=lambda: mk(1))
        seen = {}

        def loader():
            # the claim (and its eviction) landed before materialization
            seen["used_at_load"] = cache.used_bytes
            return mk(2)

        got = cache.get(("act", 2, 0), loader=loader, size_hint=blk)
        np.testing.assert_array_equal(got, mk(2))
        assert seen["used_at_load"] == 2 * blk
        assert cache.peak_bytes <= cache.budget
        assert cache.contains(("act", 2, 0))
        # an unfittable hinted block streams through without an insert
        big = np.zeros((200, 64), np.float32)
        got = cache.get(("act", 3, 0), loader=lambda: big,
                        size_hint=3 * blk)
        assert got is big
        assert not cache.contains(("act", 3, 0))
        assert cache.used_bytes <= cache.budget
        # a failing loader releases the claim
        with pytest.raises(IOError):
            cache.get(("act", 4, 0), loader=self._boom, size_hint=blk)
        assert cache.used_bytes <= 2 * blk
        st_.close()

    @staticmethod
    def _boom():
        raise IOError("nvme died")

    def test_prefetch_many_sizes_releases_claims_on_loader_error(self):
        blk = 16 * 64 * 4
        cache, st_, _ = self._mk(4 * blk)
        keys = [("act", 0, q) for q in range(2)]

        def bad_loader(missing):
            raise IOError("nvme died")

        with pytest.raises(IOError):
            cache.prefetch_many(keys, bad_loader,
                                sizes={k: blk for k in keys})
        assert cache.used_bytes == 0           # no leaked reservations
        st_.close()

    def test_engine_prefetch_peak_within_budget(self):
        """End-to-end: a pipelined epoch under a tight budget keeps the
        cache's high-water mark (including prefetch claims) within it."""
        import jax

        from repro.core import SSOEngine, build_plan
        from repro.graph import (
            gcn_norm_coeffs, kronecker_graph, switching_aware_partition,
        )
        from repro.graph.csr import add_self_loops
        from repro.graph.synthetic import random_features, random_labels
        from repro.models.gnn.layers import get_gnn
        from repro.runtime import PipelineConfig

        g = add_self_loops(kronecker_graph(600, 7, seed=0))
        res = switching_aware_partition(g, 4, max_iters=8, seed=0)
        plan = build_plan(g, res.parts, 4, edge_weight=gcn_norm_coeffs(g))
        spec = get_gnn("gcn")
        params = spec.init(jax.random.PRNGKey(0), 16, 16, 8, 2)
        Xr = random_features(g.n_nodes, 16, 0)[plan.ro.perm]
        Yr = random_labels(g.n_nodes, 8, 0)[plan.ro.perm]
        c = Counters()
        st_ = StorageTier(tempfile.mkdtemp(), counters=c)
        cache = HostCache(64 << 10, st_, c)    # thrashes hard
        eng = SSOEngine(spec, plan, [16, 16, 8], st_, cache, c,
                        pipeline=PipelineConfig(depth=2))
        eng.initialize(Xr)
        eng.run_epoch(params, Yr)
        eng.close()
        assert cache.peak_bytes <= cache.budget
        assert c.cache_evictions > 0           # pressure was real
        st_.close()


class TestCostModel:
    def test_backward_inequality(self):
        """Paper §5: B_host/B_SSD > 2(α+1)/(α+3) favors regathering;
        check the threshold values quoted (1.2–1.6 for α=2–8)."""
        for alpha, lo, hi in [(2.0, 1.1, 1.3), (8.0, 1.5, 1.7)]:
            thresh = 2 * (alpha + 1) / (alpha + 3)
            assert lo < thresh < hi

    def test_gnn_epoch_flops_hand_computed(self):
        """Satellite regression: the dead `* 0` vertex term is gone — a
        2-layer case computed by hand. Layer i costs 2·E·d_in (edge
        aggregation) + 2·V·d_in·d_out (vertex matmul); epoch = 3× forward."""
        from repro.core.costmodel import gnn_epoch_flops

        V, E, dims = 10, 40, [4, 8, 2]
        l0 = 2 * 40 * 4 + 2 * 10 * 4 * 8      # 320 + 640
        l1 = 2 * 40 * 8 + 2 * 10 * 8 * 2      # 640 + 320
        assert gnn_epoch_flops(V, E, dims) == 3.0 * (l0 + l1)  # 5760
        # the vertex matmul term really contributes (the old bug zeroed it)
        assert gnn_epoch_flops(V, E, dims) > 3.0 * (2 * E * 4 + 2 * E * 8)

    def test_modeled_time_uses_flops(self):
        from repro.core.costmodel import PAPER_WORKSTATION, modeled_time

        c = Counters()
        mt = modeled_time(c, PAPER_WORKSTATION, flops=197e12)
        assert mt.t_compute == pytest.approx(1.0)


class TestStorageAccounting:
    def test_alloc_bytes_and_peak(self):
        c = Counters()
        st_ = StorageTier(tempfile.mkdtemp(), counters=c)
        st_.alloc("a", (100, 16), np.float32)     # 6400 B
        st_.alloc("b", (50, 16), np.float16)      # 1600 B
        assert st_.allocated_bytes == 6400 + 1600
        assert st_.dtype("b") == np.float16
        st_.free("a")
        assert st_.allocated_bytes == 1600
        st_.alloc("b", (10, 16), np.float32)      # re-alloc replaces
        assert st_.allocated_bytes == 640
        assert c.storage_peak_alloc_bytes == 8000
        st_.close()
        assert st_.allocated_bytes == 0


class TestSpillQueue:
    """Satellite: dirty-eviction flushes route through the write-behind
    StorageIOQueue so an eviction never stalls cache users on a storage
    write (the old path held the cache RLock for the whole write_rows)."""

    class _SlowTier(StorageTier):
        WRITE_S = 0.15

        def write_rows(self, name, row0, arr):
            import time
            time.sleep(self.WRITE_S)
            super().write_rows(name, row0, arr)

    def _mk_slow(self, budget):
        from repro.core.storage import StorageIOQueue
        c = Counters()
        st_ = self._SlowTier(tempfile.mkdtemp(), counters=c)
        st_.alloc("back", (2048, 64), np.float32)
        q = StorageIOQueue(st_, counters=c)
        cache = HostCache(budget, st_, c)
        cache.set_spill_queue(q)
        return cache, st_, q, c

    def test_spill_routes_through_queue_and_lands(self, rng):
        import time
        cache, st_, q, c = self._mk_slow(1 << 17)  # room for one 128KB entry
        buf = rng.standard_normal((512, 64)).astype(np.float32)
        assert cache.put(("grad", 0, 0), buf, dirty=True,
                         spill_name="back", spill_row0=0)
        t0 = time.perf_counter()
        # evicts the dirty entry; the flush must be a queue submit, not a
        # synchronous slow write under the lock
        cache.get(("act", 1, 0), loader=lambda: buf.copy())
        assert time.perf_counter() - t0 < self._SlowTier.WRITE_S
        assert not cache.contains(("grad", 0, 0))
        q.drain()
        np.testing.assert_array_equal(st_.read_rows("back", 0, 512), buf)
        q.close()
        st_.close()

    def test_eviction_does_not_block_concurrent_cache_users(self, rng):
        import threading
        import time
        cache, st_, q, c = self._mk_slow(1 << 17)
        buf = rng.standard_normal((512, 64)).astype(np.float32)
        cache.put(("grad", 0, 0), buf, dirty=True,
                  spill_name="back", spill_row0=0)
        cache.put(("probe", 9, 9), np.zeros((4, 4), np.float32))
        # worker evicts the dirty entry (queue submit under the lock)...
        t = threading.Thread(
            target=lambda: cache.get(("act", 1, 0), loader=lambda: buf.copy())
        )
        t.start()
        time.sleep(0.01)
        # ...while the main thread's peek must not stall for the write
        t0 = time.perf_counter()
        cache.peek(("probe", 9, 9))
        assert time.perf_counter() - t0 < self._SlowTier.WRITE_S / 2
        t.join(timeout=5)
        q.drain()
        q.close()
        st_.close()

    def test_reader_through_queue_sees_spilled_data(self, rng):
        """FIFO ordering: a read submitted after the eviction's spill write
        observes the spilled data (what the engine's grad/snap reads rely
        on)."""
        cache, st_, q, c = self._mk_slow(1 << 17)
        buf = rng.standard_normal((512, 64)).astype(np.float32)
        cache.put(("grad", 0, 0), buf, dirty=True,
                  spill_name="back", spill_row0=0)
        cache.get(("act", 1, 0), loader=lambda: buf.copy())  # evicts + spills
        got = q.submit_read("back", 0, 512).result(timeout=10)
        np.testing.assert_array_equal(got, buf)
        q.close()
        st_.close()

    def test_dirty_replacement_spills_through_queue(self, rng):
        cache, st_, q, c = self._mk_slow(1 << 20)
        a = rng.standard_normal((64, 64)).astype(np.float32)
        cache.put(("grad", 0, 0), a, dirty=True, spill_name="back")
        cache.put(("grad", 0, 0), np.zeros((64, 64), np.float32))
        q.drain()
        np.testing.assert_array_equal(st_.read_rows("back", 0, 64), a)
        q.close()
        st_.close()

    def test_without_queue_flush_stays_synchronous(self, rng):
        """No spill queue wired: the old synchronous flush ordering holds
        (eviction returns only after the data is on storage)."""
        c = Counters()
        st_ = self._SlowTier(tempfile.mkdtemp(), counters=c)
        st_.alloc("back", (2048, 64), np.float32)
        cache = HostCache(1 << 17, st_, c)
        buf = rng.standard_normal((512, 64)).astype(np.float32)
        cache.put(("grad", 0, 0), buf, dirty=True,
                  spill_name="back", spill_row0=0)
        cache.get(("act", 1, 0), loader=lambda: buf.copy())
        np.testing.assert_array_equal(st_.read_rows("back", 0, 512), buf)
        st_.close()

    def test_spill_skips_write_backpressure(self, rng):
        """An eviction spill must not block on the queue's byte
        backpressure either — it runs under the cache RLock."""
        import time
        from repro.core.storage import StorageIOQueue
        c = Counters()
        st_ = self._SlowTier(tempfile.mkdtemp(), counters=c)
        st_.alloc("back", (2048, 64), np.float32)
        buf = rng.standard_normal((512, 64)).astype(np.float32)  # 128KB
        # cap below one buffer: regular writers would block until drained
        q = StorageIOQueue(st_, max_inflight_bytes=buf.nbytes // 2,
                           counters=c)
        cache = HostCache(1 << 17, st_, c)
        cache.set_spill_queue(q)
        q.submit_write("back", 1024, buf.copy(), wait=False)  # saturate
        cache.put(("grad", 0, 0), buf, dirty=True,
                  spill_name="back", spill_row0=0)
        t0 = time.perf_counter()
        cache.get(("act", 1, 0), loader=lambda: buf.copy())  # evict + spill
        assert time.perf_counter() - t0 < self._SlowTier.WRITE_S
        q.drain()
        np.testing.assert_array_equal(st_.read_rows("back", 0, 512), buf)
        q.close()
        st_.close()
