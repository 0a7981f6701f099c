"""Config registry substrate: arch specs, cells (arch × shape), builders.

Every assigned architecture registers an ``ArchSpec`` whose ``build(shape,
mesh)`` returns a (step_fn, abstract_args, in_shardings, meta) tuple that
launch/dryrun.py lowers and compiles without allocating (ShapeDtypeStruct
stand-ins only). ``meta["model_flops"]`` carries the analytic MODEL_FLOPS for
the §Roofline usefulness ratio.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np


@dataclasses.dataclass
class Built:
    fn: Callable
    args: Tuple
    in_shardings: Tuple
    meta: Dict[str, Any]
    out_shardings: Any = None   # propagate param sharding through updates


@dataclasses.dataclass
class Cell:
    kind: str                      # train | prefill | decode | serve | retrieval
    skip: Optional[str] = None     # reason if this cell is skipped


@dataclasses.dataclass
class ArchSpec:
    name: str
    family: str                    # lm | gnn | recsys
    describe: str
    cells: Dict[str, Cell]
    build: Callable[[str, Any], Built]
    smoke: Callable[[], Dict[str, Any]]
    # XLA cost_analysis counts a scan body once; for scanned-layer archs the
    # dry-run compiles two reduced depths and extrapolates per-layer terms.
    # (L1, L2, L_full) or None for unscanned archs.
    layer_calib: Optional[Tuple[int, int, int]] = None
    # family "gnn": the GNNArch (model id, widths, dataset) the SSO
    # launchers build their workload from
    gnn: Any = None

    def runnable_shapes(self):
        return [s for s, c in self.cells.items() if c.skip is None]


# ---------------------------------------------------------------------------
# assigned GNN shape set (shared by the four GNN archs)
# ---------------------------------------------------------------------------

GNN_SHAPES: Dict[str, Dict[str, Any]] = {
    "full_graph_sm": dict(
        kind="fullgraph", n_nodes=2708, n_edges=10556, d_feat=1433, classes=7,
    ),
    "minibatch_lg": dict(
        kind="mfg", n_nodes=232965, n_edges=114615892, batch_nodes=1024,
        fanout=(15, 10), d_feat=602, classes=41,
    ),
    "ogb_products": dict(
        kind="fullgraph", n_nodes=2449029, n_edges=61859140, d_feat=100,
        classes=47,
    ),
    "molecule": dict(
        kind="batched", n_nodes=30, n_edges=64, batch=128, d_feat=32,
        classes=16,
    ),
}

# Full-graph datasets a GNN arch names (``GNNArch.dataset``): the shape the
# SSO launchers generate their graph at. Edges are directed CSR entries.
GNN_DATASETS: Dict[str, Dict[str, int]] = {
    "cora": dict(n_nodes=2708, n_edges=10556, d_feat=1433, classes=7),
    "reddit": dict(n_nodes=232965, n_edges=114615892, d_feat=602, classes=41),
    "ogb_products": dict(
        n_nodes=2449029, n_edges=61859140, d_feat=100, classes=47,
    ),
    # IGB-medium (paper Table 9)
    "igbm": dict(n_nodes=10_000_000, n_edges=120_100_000, d_feat=1024,
                 classes=19),
}

LM_SHAPES: Dict[str, Dict[str, Any]] = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

RECSYS_SHAPES: Dict[str, Dict[str, Any]] = {
    "train_batch": dict(kind="train", batch=65536),
    "serve_p99": dict(kind="serve", batch=512),
    "serve_bulk": dict(kind="serve", batch=262144),
    "retrieval_cand": dict(kind="retrieval", batch=1, n_candidates=1_000_000),
}


def mfg_hop_sizes(
    n_layers: int, batch_nodes: int, fanout, n_nodes: int, n_groups: int,
):
    """Static padded hop sizes for the sampled-training cell.

    GraphSAINT-style: the innermost (n_layers - len(fanout)) layers run on the
    sampled subgraph itself; the final len(fanout) layers contract through the
    MFG hops. Returns innermost-first [(n_src, n_dst, n_edges)]."""
    seeds = max(batch_nodes // n_groups, 1)
    sizes = [seeds]
    edges = []
    for f in fanout:  # outermost (seed side) first
        e = sizes[-1] * f
        s = min(sizes[-1] + e, n_nodes)
        edges.append(e)
        sizes.append(s)

    def r8(x):
        return int(((x + 7) // 8) * 8)

    hops = []
    inner = r8(sizes[-1])
    # deep layers on the sampled subgraph (src == dst == innermost set)
    sub_edges = r8(edges[-1])
    for _ in range(max(n_layers - len(fanout), 0)):
        hops.append((inner, inner, sub_edges))
    # contraction hops, innermost first
    for i in reversed(range(len(fanout))):
        hops.append((r8(sizes[i + 1]), r8(sizes[i]), r8(edges[i])))
    return hops


# ---------------------------------------------------------------------------
# MODEL_FLOPS estimators
# ---------------------------------------------------------------------------

def lm_model_flops(cfg, kind: str, batch: int, seq: int) -> float:
    n_active = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n_active * batch * seq
    if kind == "prefill":
        return 2.0 * n_active * batch * seq
    # decode: one token per sequence + attention over the cache
    attn = (
        2.0 * 2.0 * cfg.n_layers * batch * seq
        * cfg.n_heads * cfg.d_head
    )
    if cfg.window is not None:
        attn *= min(cfg.window / seq, 1.0)
    return 2.0 * n_active * batch + attn


def lm_attention_correction(cfg, kind: str, batch: int, seq: int):
    """Analytic attention FLOPs/bytes for train/prefill (GLOBAL, all chips).

    The chunked-attention q/kv scans are trip-count-undercounted by XLA
    cost_analysis (scan body counted once), so the dry-run adds this
    closed-form term matching the Pallas flash-attention target: streaming
    K/V per q block, online softmax. Decode has no scan (counted exactly)."""
    if kind == "decode":
        return dict(flops=0.0, bytes=0.0)
    S, B = seq, batch
    W = cfg.window
    if W is not None and S > W:
        pairs = W * S - W * W / 2.0
    else:
        pairs = S * (S + 1) / 2.0
    if cfg.attn_type == "mla":
        d_qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        d_v = cfg.v_head_dim
        h_kv = cfg.n_heads
    else:
        d_qk = d_v = cfg.d_head
        h_kv = cfg.n_kv_heads
    fwd_flops = B * cfg.n_heads * pairs * (2.0 * d_qk + 2.0 * d_v)
    mult = 4.0 if kind == "train" else 1.0       # fwd + remat fwd + bwd(2)
    flops = mult * cfg.n_layers * fwd_flops
    # bytes: K/V streamed once per q block; q/out read/written once
    nq = max(S // cfg.q_chunk, 1)
    kv_bytes = nq * B * h_kv * S * (d_qk + d_v) * 2.0
    qo_bytes = 3.0 * B * cfg.n_heads * S * (d_qk + d_v) * 2.0
    bmult = 3.0 if kind == "train" else 1.0
    nbytes = bmult * cfg.n_layers * (kv_bytes + qo_bytes)
    return dict(flops=flops, bytes=nbytes)


def gnn_model_flops(
    dims, n_nodes: int, n_edges: int, train: bool = True,
    model: str = "gcn",
) -> float:
    """Per-model FLOPs: edge-MLP models (graphcast) do O(d^2) work PER EDGE,
    which dominates everything at ogb scale — counting only the vertex
    matmuls underestimates GraphCast 200x (§Perf graphcast iteration 2,
    refuted 'replicated compute' hypothesis)."""
    f = 0.0
    for i in range(len(dims) - 1):
        d_in, d_out = dims[i], dims[i + 1]
        if model == "graphcast":
            # edge MLP (2d->h->h) + node MLP ((d+h)->h->h) + residual proj
            h = d_out
            f += 2.0 * n_edges * (2 * d_in * h + h * h)
            f += 2.0 * n_nodes * ((d_in + h) * h + h * h + d_in * h)
        elif model == "pna":
            # pre-MLP per node, 4 aggregators x 3 scalers, post-MLP
            f += 2.0 * n_nodes * d_in * d_in
            f += 8.0 * n_edges * d_in
            f += 2.0 * n_nodes * (12 * d_in + d_in) * d_out
        elif model == "sage":
            f += 2.0 * n_edges * d_in
            f += 4.0 * n_nodes * d_in * d_out        # self + neighbor
        elif model == "gat":
            f += 8.0 * n_edges * d_out               # scores + weighted agg
            f += 2.0 * n_nodes * d_in * d_out
        else:  # gcn/gin
            f += 2.0 * n_edges * d_in                # aggregation
            f += 2.0 * n_nodes * d_in * d_out        # vertex matmul
    return (3.0 if train else 1.0) * f


def recsys_model_flops(cfg, kind: str, batch: int, n_candidates: int = 0) -> float:
    dims_u = [cfg.n_user_fields * cfg.embed_dim] + list(cfg.tower_mlp)
    dims_i = [cfg.n_item_fields * cfg.embed_dim] + list(cfg.tower_mlp)
    mlp_u = sum(2 * a * b for a, b in zip(dims_u[:-1], dims_u[1:]))
    mlp_i = sum(2 * a * b for a, b in zip(dims_i[:-1], dims_i[1:]))
    if kind == "train":
        return 3.0 * batch * (mlp_u + mlp_i) + 3.0 * 2 * batch * batch * cfg.tower_mlp[-1]
    if kind == "serve":
        return batch * mlp_u
    return batch * mlp_u + 2.0 * batch * n_candidates * cfg.tower_mlp[-1]
