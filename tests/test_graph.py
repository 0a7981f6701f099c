"""Graph substrate: CSR, generators, partitioner, reorder, sampler."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph import (
    CSRGraph, coo_to_csr, expansion_ratio, kronecker_graph,
    partition_dependency_matrix, random_partition, spinner_like_partition,
    switching_aware_partition, watts_strogatz, reorder_by_partition,
    NeighborSampler,
)
from repro.graph.csr import add_self_loops, gcn_norm_coeffs, symmetrize
from repro.graph.partition import partition_balance


class TestCSR:
    def test_coo_roundtrip(self, rng):
        n, E = 100, 500
        src = rng.integers(0, n, E)
        dst = rng.integers(0, n, E)
        g = coo_to_csr(src, dst, n)
        g.validate()
        ei = g.edge_index()
        # every original edge present
        orig = set(zip(src.tolist(), dst.tolist()))
        new = set(zip(ei[0].tolist(), ei[1].tolist()))
        assert orig == new  # dedup only

    def test_self_loops(self, tiny_graph):
        g = tiny_graph
        ei = g.edge_index()
        loops = (ei[0] == ei[1]).sum()
        assert loops == g.n_nodes

    def test_gcn_norm_positive(self, tiny_graph):
        w = gcn_norm_coeffs(tiny_graph)
        assert w.shape == (tiny_graph.n_edges,)
        assert (w > 0).all() and (w <= 1.0 + 1e-6).all()

    def test_symmetrize(self, rng):
        g = coo_to_csr(rng.integers(0, 50, 200), rng.integers(0, 50, 200), 50)
        gs = symmetrize(g)
        ei = gs.edge_index()
        pairs = set(zip(ei[0].tolist(), ei[1].tolist()))
        assert all((d, s) in pairs for s, d in pairs)


class TestGenerators:
    def test_kronecker_power_law(self):
        g = kronecker_graph(5000, 10, seed=0)
        deg = g.in_degrees()
        # heavy tail: max degree far above mean
        assert deg.max() > 10 * deg.mean()

    def test_watts_strogatz_not_power_law(self):
        g = watts_strogatz(5000, k=16, seed=0)
        deg = g.in_degrees()
        assert deg.max() < 4 * deg.mean()


class TestPartitioner:
    def test_improves_alpha_over_random(self, small_graph):
        g = small_graph
        p = 8
        a_rand = expansion_ratio(g, random_partition(g.n_nodes, p, 0), p)
        res = switching_aware_partition(g, p, max_iters=20)
        a_sa = expansion_ratio(g, res.parts, p)
        assert a_sa < a_rand

    def test_balance_constraint(self, small_graph):
        res = switching_aware_partition(small_graph, 8, max_iters=20)
        assert partition_balance(res.parts, 8) <= 1.25

    def test_memory_is_csr_plus_labels(self, small_graph):
        """O(2|V| + 2|E|) claim: additional bytes == one int per edge."""
        res = switching_aware_partition(small_graph, 8, max_iters=5)
        assert res.additional_bytes == small_graph.n_edges * 4
        assert res.label_bytes == small_graph.n_nodes * 4

    def test_objective_monotone_ish(self, small_graph):
        res = switching_aware_partition(small_graph, 8, max_iters=20)
        h = res.objective_history
        assert h[-1] >= h[0]  # net improvement

    def test_dependency_matrix_diag_dominant(self, small_graph):
        res = switching_aware_partition(small_graph, 8, max_iters=20)
        M = partition_dependency_matrix(small_graph, res.parts, 8)
        # own-partition requirement is the largest per row (clustering)
        assert (np.argmax(M, axis=1) == np.arange(8)).mean() >= 0.75

    def test_spinner_baseline_runs(self, tiny_graph):
        res = spinner_like_partition(tiny_graph, 4, max_iters=10)
        assert res.parts.shape == (tiny_graph.n_nodes,)

    @given(
        n_parts=st.sampled_from([2, 4, 8]),
        seed=st.integers(0, 3),
    )
    @settings(max_examples=6, deadline=None)
    def test_partition_labels_valid(self, n_parts, seed):
        g = add_self_loops(kronecker_graph(500, 5, seed=seed))
        res = switching_aware_partition(g, n_parts, max_iters=8, seed=seed)
        assert res.parts.min() >= 0 and res.parts.max() < n_parts


class TestReorder:
    def test_edge_multiset_preserved(self, tiny_graph):
        g = tiny_graph
        res = switching_aware_partition(g, 4, max_iters=8)
        ro = reorder_by_partition(g, res.parts, 4)
        ro.graph.validate()
        k_old = np.sort(
            g.edge_index()[0].astype(np.int64) * g.n_nodes
            + g.edge_index()[1]
        )
        ei = ro.graph.edge_index()
        k_new = np.sort(
            ro.perm[ei[0]].astype(np.int64) * g.n_nodes + ro.perm[ei[1]]
        )
        assert np.array_equal(k_old, k_new)

    def test_partitions_contiguous(self, tiny_graph):
        res = switching_aware_partition(tiny_graph, 4, max_iters=8)
        ro = reorder_by_partition(tiny_graph, res.parts, 4)
        assert np.all(np.diff(ro.parts) >= 0)

    def test_adjacency_sorted_by_partition(self, tiny_graph):
        res = switching_aware_partition(tiny_graph, 4, max_iters=8)
        ro = reorder_by_partition(tiny_graph, res.parts, 4)
        rg = ro.graph
        for v in range(0, rg.n_nodes, 37):
            nbrs = rg.indices[rg.indptr[v]:rg.indptr[v + 1]]
            ps = ro.parts[nbrs]
            assert np.all(np.diff(ps.astype(int)) >= 0)

    def test_partition_reorder_concentrates_blocks(self, small_graph):
        """Partition-contiguous reordering concentrates edge mass into
        diagonal blocks of the adjacency: the locality the SSO plan's
        per-partition gather runs exploit."""
        g = small_graph
        block = 256

        def diag_fraction(ei):
            br = ei[1] // block
            bc = ei[0] // block
            return float(np.mean(br == bc))

        frac_orig = diag_fraction(g.edge_index())
        res = switching_aware_partition(g, 8, max_iters=10)
        ro = reorder_by_partition(g, res.parts, 8)
        frac_part = diag_fraction(ro.graph.edge_index())
        assert frac_part > frac_orig


class TestSampler:
    def test_mfg_shapes(self, small_graph):
        s = NeighborSampler(small_graph, [10, 5], seed=0)
        mfg = s.sample(np.arange(64))
        assert len(mfg.layers) == 2
        assert mfg.layers[-1].n_dst == 64
        for l in mfg.layers:
            assert l.src_index.max() < l.node_ids.shape[0]
            assert l.dst_index.max() < l.n_dst
            assert set(np.unique(l.edge_mask)) <= {0.0, 1.0}

    def test_sampled_edges_exist_in_graph(self, tiny_graph):
        g = tiny_graph
        s = NeighborSampler(g, [5], seed=1)
        mfg = s.sample(np.arange(32))
        l = mfg.layers[0]
        ei = g.edge_index()
        edges = set(zip(ei[0].tolist(), ei[1].tolist()))
        for e in range(len(l.src_index)):
            if l.edge_mask[e] > 0:
                s_g = int(l.node_ids[l.src_index[e]])
                d_g = int(l.node_ids[l.dst_index[e]])
                assert (s_g, d_g) in edges


class TestRemapEdgeWeight:
    def test_remap_roundtrip(self, tiny_graph):
        from repro.core.plan import remap_edge_weight

        g = tiny_graph
        parts = random_partition(g.n_nodes, 4, seed=0)
        ro = reorder_by_partition(g, parts, 4)
        w = np.arange(g.n_edges, dtype=np.float32)
        w_new = remap_edge_weight(g, ro, w)
        # spot-check: each reordered edge carries its original weight
        rg = ro.graph
        new_dst = np.repeat(np.arange(g.n_nodes), np.diff(rg.indptr))
        old_pairs = {}
        od = np.repeat(np.arange(g.n_nodes), np.diff(g.indptr))
        for e in range(g.n_edges):
            old_pairs[(int(od[e]), int(g.indices[e]))] = w[e]
        for e in range(0, rg.indptr[-1], max(1, g.n_edges // 64)):
            d, s = int(ro.perm[new_dst[e]]), int(ro.perm[rg.indices[e]])
            assert w_new[e] == old_pairs[(d, s)]

    def test_remap_rejects_malformed_reordered_graph(self, tiny_graph):
        """Satellite regression: a reordered graph whose edges don't exist
        in the original must raise instead of silently picking up a
        neighbor's weight via the raw searchsorted insertion point."""
        from repro.core.plan import remap_edge_weight

        g = tiny_graph
        parts = random_partition(g.n_nodes, 4, seed=0)
        ro = reorder_by_partition(g, parts, 4)
        w = np.ones(g.n_edges, np.float32)
        # corrupt one adjacency entry to an edge that does not exist
        bad = ro.graph.indices.copy()
        orig = bad[0]
        for cand in range(g.n_nodes):
            if cand != orig:
                bad[0] = cand
                try:
                    probe = CSRGraph(indptr=ro.graph.indptr, indices=bad,
                                     n_nodes=g.n_nodes)
                    import dataclasses
                    ro_bad = dataclasses.replace(ro, graph=probe)
                    remap_edge_weight(g, ro_bad, w)
                except ValueError:
                    return   # raised as required
        pytest.fail("malformed reordered graph did not raise")
