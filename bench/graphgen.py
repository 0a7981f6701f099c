"""The benchmark's own dataset generator, fixed so that no change to the
program can change the data a cell runs on.

``kronecker_graph`` and the CSR helpers are copies of
``repro.graph.synthetic.kronecker_graph`` and ``repro.graph.csr``
(``coo_to_csr``, ``symmetrize``, ``add_self_loops``). A cell's graph is the
symmetric R-MAT graph at the configuration's node count and average degree,
with a self loop on every node, generated from the configuration's
``graph_seed`` and kept under ``bench/.cache/`` so that only a checkout's
first run of a configuration generates it.
"""
from __future__ import annotations

import os

import numpy as np


def coo_to_csr(src, dst, n_nodes: int):
    """In-edge CSR ``(indptr int64, indices int32)`` of a COO edge list,
    deduplicated, destination-major with sources sorted."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    key = np.unique(dst * n_nodes + src)
    dst_u = key // n_nodes
    src_u = (key % n_nodes).astype(np.int32)
    indptr = np.zeros(n_nodes + 1, dtype=np.int64)
    np.add.at(indptr, dst_u + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, src_u


def _edge_dst(indptr):
    return np.repeat(np.arange(indptr.shape[0] - 1, dtype=np.int64),
                     np.diff(indptr))


def symmetrize(indptr, indices, n_nodes: int):
    dst = _edge_dst(indptr)
    src = indices.astype(np.int64)
    return coo_to_csr(np.concatenate([src, dst]), np.concatenate([dst, src]),
                      n_nodes)


def add_self_loops(indptr, indices, n_nodes: int):
    loop = np.arange(n_nodes, dtype=np.int64)
    return coo_to_csr(np.concatenate([indices.astype(np.int64), loop]),
                      np.concatenate([_edge_dst(indptr), loop]), n_nodes)


def kronecker_graph(n_nodes: int, avg_degree: int, seed: int,
                    a: float = 0.57, b: float = 0.19, c: float = 0.19):
    """R-MAT graph with a power-law degree distribution, symmetrized, as
    in-edge CSR ``(indptr, indices)``."""
    rng = np.random.default_rng(seed)
    scale = int(np.ceil(np.log2(max(n_nodes, 2))))
    n_edges = n_nodes * avg_degree
    # the original draws two unused arrays first; drawing them keeps the
    # stream, and so the graph, identical to it
    rng.random((scale, n_edges))
    rng.random((scale, n_edges))
    r = rng.random((scale, n_edges))
    src_bit = (r >= a + b).astype(np.int64)
    col_bit = np.where(r < a + b, (r >= a).astype(np.int64),
                       (r >= a + b + c).astype(np.int64))
    del r
    powers = (1 << np.arange(scale, dtype=np.int64))[:, None]
    src = (src_bit * powers).sum(axis=0) % n_nodes
    dst = (col_bit * powers).sum(axis=0) % n_nodes
    keep = src != dst
    indptr, indices = coo_to_csr(src[keep], dst[keep], n_nodes)
    return symmetrize(indptr, indices, n_nodes)


def cell_graph(cfg: dict, cache_dir: str):
    """The configuration's dataset graph, self loops added, as
    ``(indptr, indices)``; generated once per checkout and then loaded."""
    n, deg, gseed = cfg["n_nodes"], cfg["avg_degree"], cfg["graph_seed"]
    path = os.path.join(cache_dir, f"graph-kron-n{n}-d{deg}-s{gseed}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return z["indptr"], z["indices"]
    indptr, indices = add_self_loops(*kronecker_graph(n, deg, gseed), n)
    os.makedirs(cache_dir, exist_ok=True)
    tmp = path + ".part.npz"
    np.savez(tmp, indptr=indptr, indices=indices)
    os.replace(tmp, path)
    return indptr, indices
