"""GNN layer functions shared by the oracle full-graph path, the SSO
partition-wise engine, and the distributed (sharded) path.

Every layer is a pure function ``apply(params_l, ga, topo) -> (n_dst, d_out)``
where ``ga`` holds the gathered source activations for the work unit (the
paper's ``GA_p^{l-1}``) and ``topo`` is the partition-local (or full-graph)
edge structure. Purity is what lets the regathering gradient engine call
``jax.vjp`` per (layer, partition) without any framework-retained residuals —
the JAX analogue of the paper's custom grad engine replacing torch.autograd.

Message passing is built on ``jax.ops.segment_sum``/``segment_max`` over edge
indices (JAX sparse is BCOO-only; scatter-style MP is the system substrate).

Order of the two linear steps in GCN and GraphSAGE (:func:`transform_first`):
a layer that narrows (``d_out < d_in``, read from its weight's shape) runs
its dense transform first and aggregates the transformed rows; every other
layer aggregates first. The aggregation is linear, so ``(A X) W == A (X W)``:
the order changes no mathematics, only the width at which the edge gather
and segment sum run — and, through ``jax.vjp``, the width of their
transposes in the backward (the cotangent's gather and scatter-add over the
edges). On the device those edge passes, not the matmuls, take the layer's
time, so the narrower side wins; the extra matmul runs over the unit's
gathered rows instead of its output rows. Layers that do not narrow keep
the aggregate-first program unchanged, bit for bit.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LocalTopo:
    """Partition-local (or full-graph) topology, all device arrays.

    ``src``/``dst`` index into the gathered-activation array / output rows.
    ``n_dst`` is static. Padded edges carry ``edge_mask == 0`` and point at
    slot 0 so gradients through padding vanish.
    """

    src: jnp.ndarray          # int32 (E,) rows of `ga`
    dst: jnp.ndarray          # int32 (E,) output rows in [0, n_dst)
    n_dst: int                # static
    edge_weight: jnp.ndarray  # float32 (E,)  (GCN sym-norm; 1.0 otherwise) * mask
    edge_mask: jnp.ndarray    # float32 (E,)  1=real edge, 0=padding
    in_deg: jnp.ndarray       # float32 (n_dst,) true in-degree (>=1 clamp applied)
    dst_self: jnp.ndarray     # int32 (n_dst,) row of each dst vertex inside `ga`


def _topo_flatten(t: "LocalTopo"):
    return (
        (t.src, t.dst, t.edge_weight, t.edge_mask, t.in_deg, t.dst_self),
        t.n_dst,
    )


def _topo_unflatten(n_dst, children):
    src, dst, ew, em, deg, ds = children
    return LocalTopo(src, dst, n_dst, ew, em, deg, ds)


jax.tree_util.register_pytree_node(LocalTopo, _topo_flatten, _topo_unflatten)


def _rows(x):
    """Pin edge/node-row sharding over the batch axes when a mesh is ambient
    (distributed full-graph path); no-op otherwise (SSO engine / CPU). Keeps
    GSPMD from replicating the per-edge MLP work on every chip (§Perf
    graphcast iteration 2)."""
    from repro.models.lm.sharding import DB, constrain

    return constrain(x, DB, *([None] * (x.ndim - 1)))


def _seg_sum(x, seg, n):
    return jax.ops.segment_sum(_rows(x), seg, num_segments=n)


def _seg_max(x, seg, n):
    return jax.ops.segment_max(x, seg, num_segments=n)


def _seg_min(x, seg, n):
    return -jax.ops.segment_max(-x, seg, num_segments=n)


def _dense(rng, d_in, d_out, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    k1, _ = jax.random.split(rng)
    return {
        "w": jax.random.normal(k1, (d_in, d_out), jnp.float32) * scale,
        "b": jnp.zeros((d_out,), jnp.float32),
    }


def _apply_dense(p, x):
    return x @ p["w"] + p["b"]


def _layernorm(x, eps: float = 1e-5):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


# --------------------------------------------------------------------------
# GCN (Kipf & Welling) — the paper's primary model
# --------------------------------------------------------------------------

def gcn_init(rng, d_in, d_out):
    return {"lin": _dense(rng, d_in, d_out)}


def transform_first(d_in: int, d_out: int) -> bool:
    """Whether a GCN or SAGE layer of widths ``d_in -> d_out`` runs its
    dense transform before the edge aggregation: exactly when it narrows,
    so the gather and segment sums over the edges move ``d_out``-wide rows
    instead of ``d_in``-wide ones."""
    return d_out < d_in


def gcn_apply(params, ga, topo: LocalTopo, activate: bool = True):
    lin = params["lin"]
    if transform_first(*lin["w"].shape):
        msg = (ga @ lin["w"])[topo.src] * topo.edge_weight[:, None]
        h = _seg_sum(msg, topo.dst, topo.n_dst) + lin["b"]
        return jax.nn.relu(h) if activate else h
    msg = ga[topo.src] * topo.edge_weight[:, None]
    agg = _seg_sum(msg, topo.dst, topo.n_dst)
    h = _apply_dense(params["lin"], agg)
    return jax.nn.relu(h) if activate else h


# --------------------------------------------------------------------------
# GraphSAGE (mean aggregator)
# --------------------------------------------------------------------------

def sage_init(rng, d_in, d_out):
    k1, k2 = jax.random.split(rng)
    return {"self": _dense(k1, d_in, d_out), "nbr": _dense(k2, d_in, d_out)}


def sage_apply(params, ga, topo: LocalTopo, activate: bool = True):
    nbr = params["nbr"]
    if transform_first(*nbr["w"].shape):
        msg = (ga @ nbr["w"])[topo.src] * topo.edge_mask[:, None]
        agg = _seg_sum(msg, topo.dst, topo.n_dst) / topo.in_deg[:, None]
        h = _apply_dense(params["self"], ga[topo.dst_self]) + (agg + nbr["b"])
        return jax.nn.relu(h) if activate else h
    msg = ga[topo.src] * topo.edge_mask[:, None]
    agg = _seg_sum(msg, topo.dst, topo.n_dst) / topo.in_deg[:, None]
    x_self = ga[topo.dst_self]
    h = _apply_dense(params["self"], x_self) + _apply_dense(params["nbr"], agg)
    return jax.nn.relu(h) if activate else h


# --------------------------------------------------------------------------
# GAT (Veličković et al. 2018, arXiv:1710.10903): multi-head graph attention
#
# The heads are read from the parameter tree: ``w`` is (d_in, H, d_head).
# A layer whose bias is H * d_head wide concatenates its heads (hidden
# layers); one whose bias is d_head wide averages them (the output layer of
# the paper's §3.3 models). A hidden layer whose input width equals its
# output width adds its own input before the ELU: the skip connection
# across the middle attentional layer of the inductive model.
#
# Scores, softmax and weighted sum take one of two forms, chosen from the
# unit's static shapes (``gat_dense``): per edge, with gathers and segment
# sums over the padded edges at H * d_head lanes; or, where the unit is
# dense enough, as a masked (H, n_dst, n_src) attention matrix applied to
# the rows by one float32 matmul per head.
# --------------------------------------------------------------------------

def gat_init(rng, d_in, d_out, n_heads: int = 4, average: bool = False):
    """A concatenating layer of ``n_heads`` heads of ``d_out // n_heads``
    (one head where they do not divide ``d_out``), or with ``average`` an
    averaging layer of ``n_heads`` heads of ``d_out``."""
    if not average and d_out % n_heads:
        n_heads = 1
    d_head = d_out if average else d_out // n_heads
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "w": jax.random.normal(k1, (d_in, n_heads, d_head), jnp.float32)
        / np.sqrt(d_in),
        "a_src": jax.random.normal(k2, (n_heads, d_head), jnp.float32) * 0.1,
        "a_dst": jax.random.normal(k3, (n_heads, d_head), jnp.float32) * 0.1,
        "b": jnp.zeros((d_out,), jnp.float32),
    }


def gat_dense(n_dst: int, n_src: int, n_edges: int, d_head: int) -> bool:
    """Whether a GAT layer over a unit of ``n_dst`` output rows, ``n_src``
    gathered rows and ``n_edges`` (padded) edges runs the dense path: when
    its ``(H, n_dst, n_src)`` attention tensor is no larger than the
    ``(E, H, d_head)`` messages the edge path would gather and scatter,
    ``d_head`` counted as at least one 128-lane tile. So the dense path
    never holds more than the edge path it replaces."""
    return n_dst * n_src <= max(d_head, 128) * n_edges


def _attend_edges(h, e_src, e_dst, topo: LocalTopo):
    """Each head's softmax-weighted sum over the in-edges, per edge: scores
    gathered to the edges, a segment max and two segment sums into the dst
    rows. ``h`` is (n_src, H, d_head); returns (n_dst, H, d_head)."""
    with jax.named_scope("gat_score"):
        score = jax.nn.leaky_relu(e_src[topo.src] + e_dst[topo.dst], 0.2)
    with jax.named_scope("gat_softmax"):
        # padded edges are left out of the max and weigh 0
        real = topo.edge_mask[:, None] > 0
        score = jnp.where(real, score, jnp.finfo(score.dtype).min)
        smax = _seg_max(score, topo.dst, topo.n_dst)
        smax = jnp.maximum(smax, -1e30)  # guard all-pad segments
        ex = jnp.where(real, jnp.exp(score - smax[topo.dst]), 0.0)
        den = jnp.maximum(_seg_sum(ex, topo.dst, topo.n_dst), 1e-9)
    with jax.named_scope("gat_aggregate"):
        # the softmax's division is taken after the sum, once per dst row
        msg = h[topo.src] * ex[:, :, None]
        return _seg_sum(msg, topo.dst, topo.n_dst) / den[:, :, None]


def _attend_dense(h, e_src, e_dst, topo: LocalTopo):
    """The same weighted sum over a dense ``(H, n_dst, n_src)`` attention
    matrix: a masked row softmax, then one matmul per head. A non-edge
    weighs exactly 0 and an edge listed twice weighs twice, as on the edge
    path; only the order of the sums differs."""
    with jax.named_scope("gat_mask"):
        # edge counts, one scatter of E scalars; padded edges add 0
        adj = jnp.zeros((topo.n_dst, h.shape[0]), h.dtype).at[
            topo.dst, topo.src].add(topo.edge_mask)
    with jax.named_scope("gat_score"):
        score = jax.nn.leaky_relu(e_src.T[:, None, :] + e_dst.T[:, :, None],
                                  0.2)
    with jax.named_scope("gat_softmax"):
        # non-edges are masked before the exp: the row max is taken over the
        # in-edges alone, and no non-edge's exp can overflow (its VJP would
        # then multiply 0 by inf)
        score = jnp.where(adj > 0, score, jnp.finfo(score.dtype).min)
        smax = jnp.maximum(score.max(axis=-1, keepdims=True), -1e30)
        ex = jnp.exp(score - smax) * adj
        den = jnp.maximum(ex.sum(axis=-1), 1e-9)
    with jax.named_scope("gat_aggregate"):
        # float32 products and sums, as the edge path's: the default
        # precision would round both operands to bfloat16
        agg = jnp.einsum("hds,she->dhe", ex, h,
                         precision=jax.lax.Precision.HIGHEST)
        return agg / den.T[:, :, None]


def gat_apply(params, ga, topo: LocalTopo, activate: bool = True):
    d_head = params["w"].shape[2]
    dense = gat_dense(topo.n_dst, ga.shape[0], topo.src.shape[0], d_head)
    return _gat(params, ga, topo, activate,
                _attend_dense if dense else _attend_edges)


def _gat(params, ga, topo: LocalTopo, activate: bool, attend):
    d_in, n_heads, d_head = params["w"].shape
    with jax.named_scope("gat_score"):
        h = jnp.einsum("nd,dhe->nhe", ga, params["w"])  # (n_src, H, d_head)
        e_src = jnp.einsum("nhe,he->nh", h, params["a_src"])
        e_dst = jnp.einsum("nhe,he->nh", h[topo.dst_self], params["a_dst"])
    agg = attend(h, e_src, e_dst, topo)
    with jax.named_scope("gat_aggregate"):
        if params["b"].shape[0] != n_heads * d_head:
            out = agg.mean(axis=1) + params["b"]
        else:
            out = agg.reshape(topo.n_dst, -1) + params["b"]
            if activate and d_in == out.shape[-1]:
                out = out + ga[topo.dst_self]
    return jax.nn.elu(out) if activate else out


# --------------------------------------------------------------------------
# GIN
# --------------------------------------------------------------------------

def gin_init(rng, d_in, d_out):
    k1, k2 = jax.random.split(rng)
    return {
        "mlp1": _dense(k1, d_in, d_out),
        "mlp2": _dense(k2, d_out, d_out),
        "eps": jnp.zeros(()),
    }


def gin_apply(params, ga, topo: LocalTopo, activate: bool = True):
    msg = ga[topo.src] * topo.edge_mask[:, None]
    agg = _seg_sum(msg, topo.dst, topo.n_dst)
    x = (1.0 + params["eps"]) * ga[topo.dst_self] + agg
    # GIN uses BatchNorm inside its MLPs; LayerNorm is the stateless
    # JAX-friendly equivalent (keeps sum-aggregation from exploding on
    # power-law degree distributions).
    h = _layernorm(jax.nn.relu(_apply_dense(params["mlp1"], x)))
    h = _apply_dense(params["mlp2"], h)
    return jax.nn.relu(h) if activate else h


# --------------------------------------------------------------------------
# PNA — mean/max/min/std aggregators × identity/amplification/attenuation
# --------------------------------------------------------------------------

def pna_init(rng, d_in, d_out):
    k1, k2 = jax.random.split(rng)
    return {
        "pre": _dense(k1, d_in, d_in),
        "post": _dense(k2, 12 * d_in + d_in, d_out),  # 4 agg x 3 scalers + self
        "log_mean_deg": jnp.asarray(1.0),  # set from data stats at init time
    }


def pna_apply(params, ga, topo: LocalTopo, activate: bool = True):
    msg = jax.nn.relu(_apply_dense(params["pre"], ga))[topo.src]
    msg = msg * topo.edge_mask[:, None]
    n, d = topo.n_dst, msg.shape[-1]
    deg = topo.in_deg[:, None]
    s = _seg_sum(msg, topo.dst, topo.n_dst)
    mean = s / deg
    neg = jnp.finfo(msg.dtype).min
    msk = jnp.where(topo.edge_mask[:, None] > 0, msg, neg)
    mx = jnp.maximum(_seg_max(msk, topo.dst, topo.n_dst), -1e30)
    mn = -jnp.maximum(_seg_max(-jnp.where(topo.edge_mask[:, None] > 0, msg, -neg),
                               topo.dst, topo.n_dst), -1e30)
    sq = _seg_sum(msg * msg, topo.dst, topo.n_dst) / deg
    std = jnp.sqrt(jnp.maximum(sq - mean * mean, 0.0) + 1e-5)
    aggs = jnp.concatenate([mean, mx, mn, std], axis=-1)  # (n, 4d)
    logd = jnp.log(deg + 1.0)
    amp = logd / params["log_mean_deg"]
    att = params["log_mean_deg"] / jnp.maximum(logd, 1e-5)
    scaled = jnp.concatenate([aggs, aggs * amp, aggs * att], axis=-1)  # (n,12d)
    x = jnp.concatenate([scaled, ga[topo.dst_self]], axis=-1)
    h = _apply_dense(params["post"], x)
    return jax.nn.relu(h) if activate else h


# --------------------------------------------------------------------------
# GraphCast-style processor layer (interaction network, node-centric variant)
#
# Faithful GraphCast keeps persistent edge latents; the SSO engine manages
# node-centric per-layer state, so edge latents are recomputed from endpoint
# features each layer (noted in DESIGN.md §4). Residual connections as in the
# processor.
# --------------------------------------------------------------------------

def graphcast_init(rng, d_in, d_out):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    d = d_out
    return {
        "edge1": _dense(k1, 2 * d_in, d),
        "edge2": _dense(k2, d, d),
        "node1": _dense(k3, d_in + d, d),
        "node2": _dense(k4, d, d),
        "proj": _dense(jax.random.fold_in(rng, 7), d_in, d),
    }


def graphcast_apply(params, ga, topo: LocalTopo, activate: bool = True):
    h_src = ga[topo.src]
    h_dst = ga[topo.dst_self][topo.dst]
    e = jnp.concatenate([h_src, h_dst], axis=-1)
    e = jax.nn.silu(_apply_dense(params["edge1"], e))
    # GraphCast applies LayerNorm after every MLP (encoder/processor/decoder).
    e = _layernorm(_apply_dense(params["edge2"], e)) * topo.edge_mask[:, None]
    agg = _seg_sum(e, topo.dst, topo.n_dst)
    x = jnp.concatenate([ga[topo.dst_self], agg], axis=-1)
    h = jax.nn.silu(_apply_dense(params["node1"], x))
    h = _layernorm(_apply_dense(params["node2"], h))
    h = h + _apply_dense(params["proj"], ga[topo.dst_self])  # residual
    return jax.nn.relu(h) if activate else h


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GNNSpec:
    name: str
    init_layer: Callable[..., Dict[str, Any]]
    apply_layer: Callable[..., jnp.ndarray]
    # the output layer's init where it differs from the hidden layers'
    init_output: Optional[Callable[..., Dict[str, Any]]] = None

    def transforms_first(self, d_in: int, d_out: int) -> bool:
        """Whether this model's layer of widths ``d_in -> d_out`` runs the
        transform-first order (GCN and SAGE layers that narrow); what the
        engine counts as ``narrow_aggregate_passes``."""
        return (self.apply_layer in (gcn_apply, sage_apply)
                and transform_first(d_in, d_out))

    def attends(self) -> bool:
        """Whether this model's layers weigh their edges by attention
        (GAT); what the engine counts as ``attention_passes``."""
        return self.apply_layer is gat_apply

    def dense_attention_units(self, params_l, units) -> int:
        """How many of ``units`` (each with its padded ``d_pad``, ``r_pad``
        and ``e_pad``) the attention layer of parameters ``params_l`` runs
        on the dense path (``gat_dense``); what the engine counts as
        ``dense_attention_units``."""
        d_head = params_l["w"].shape[-1]
        return sum(gat_dense(u.d_pad, u.r_pad, u.e_pad, d_head)
                   for u in units)

    def init(self, rng, d_in: int, d_hidden: int, d_out: int, n_layers: int):
        dims = [d_in] + [d_hidden] * (n_layers - 1) + [d_out]
        params = []
        for i in range(n_layers):
            rng, k = jax.random.split(rng)
            init = (self.init_output if i == n_layers - 1 and self.init_output
                    else self.init_layer)
            params.append(init(k, dims[i], dims[i + 1]))
        return params


GNN_REGISTRY: Dict[str, GNNSpec] = {
    "gcn": GNNSpec("gcn", gcn_init, gcn_apply),
    "sage": GNNSpec("sage", sage_init, sage_apply),
    # hidden layers concatenate 4 heads; the output layer averages 6 (§3.3)
    "gat": GNNSpec("gat", gat_init, gat_apply,
                   init_output=partial(gat_init, n_heads=6, average=True)),
    "gin": GNNSpec("gin", gin_init, gin_apply),
    "pna": GNNSpec("pna", pna_init, pna_apply),
    "graphcast": GNNSpec("graphcast", graphcast_init, graphcast_apply),
}


def get_gnn(name: str) -> GNNSpec:
    return GNN_REGISTRY[name]


# --------------------------------------------------------------------------
# Full-graph oracle helpers
# --------------------------------------------------------------------------

def full_graph_topo(
    indptr: np.ndarray,
    indices: np.ndarray,
    n_nodes: int,
    edge_weight: Optional[np.ndarray] = None,
) -> LocalTopo:
    dst = np.repeat(np.arange(n_nodes, dtype=np.int32), np.diff(indptr))
    e = indices.shape[0]
    ew = edge_weight if edge_weight is not None else np.ones(e, np.float32)
    deg = np.maximum(np.diff(indptr), 1).astype(np.float32)
    return LocalTopo(
        src=jnp.asarray(indices, jnp.int32),
        dst=jnp.asarray(dst),
        n_dst=n_nodes,
        edge_weight=jnp.asarray(ew, jnp.float32),
        edge_mask=jnp.ones((e,), jnp.float32),
        in_deg=jnp.asarray(deg),
        dst_self=jnp.arange(n_nodes, dtype=jnp.int32),
    )


def full_graph_forward(spec: GNNSpec, params: List, x, topo: LocalTopo):
    h = x
    for i, p in enumerate(params):
        h = spec.apply_layer(p, h, topo, activate=(i < len(params) - 1))
    return h


def softmax_xent(logits, labels, n_total: Optional[int] = None):
    """Mean CE over nodes (sum/n_total form so partitions compose exactly)."""
    n_total = n_total if n_total is not None else logits.shape[0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)
    return -ll.sum() / n_total


def full_graph_loss(spec, params, x, topo, labels):
    logits = full_graph_forward(spec, params, x, topo)
    return softmax_xent(logits, labels)
