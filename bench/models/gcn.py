"""Plain GCN layer (Kipf & Welling 2017) for the reference: forward,
backward by hand, and the least work of the program's layer calls.

    Z = A_hat (H W) + b,   H' = relu(Z) on every layer but the last,

with ``A_hat[d, s] = 1 / sqrt(deg(s) deg(d))`` over the in-edges (self loops
included) and ``deg`` the in-degree. The product is taken as ``A_hat (H W)``,
which equals ``(A_hat H) W``; the reference needs no agreement in rounding
order with the program, only float32 at ``highest`` precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def init(key, d_in: int, d_out: int, activate: bool):
    """Weights in the program's parameter tree layout, float32. Every
    layer has the same tree, whatever its position (``activate``)."""
    return {"lin": {"w": jax.random.normal(key, (d_in, d_out), jnp.float32)
                    / np.sqrt(d_in),
                    "b": jnp.zeros((d_out,), jnp.float32)}}


def edge_weight(src_deg: np.ndarray, dst_deg: np.ndarray) -> np.ndarray:
    return (1.0 / np.sqrt(src_deg.astype(np.float64)
                          * dst_deg.astype(np.float64))).astype(np.float32)


def forward(p, h, g, activate: bool):
    """Returns ``(z, out)``: the pre-activation and the layer's output."""
    z = g.agg(h @ p["lin"]["w"]) + p["lin"]["b"]
    return z, (jax.nn.relu(z) if activate else z)


def backward(p, h, z, d_out, g, activate: bool):
    """Returns ``(d_params, d_h)`` for the cotangent ``d_out`` of the output."""
    dz = d_out * (z > 0).astype(z.dtype) if activate else d_out
    pt = g.agg(dz, transpose=True)            # A_hat^T dZ
    w = p["lin"]["w"]
    return ({"lin": {"w": h.T @ pt, "b": dz.sum(axis=0)}}, pt @ w.T)


def model_flops(n_nodes, n_edges, d_in, d_out, activate):
    """Model FLOPs of one layer's forward: the edge sum
    and the matmul over every real node and edge
    (``repro.configs.base.gnn_model_flops``), at any position."""
    return 2.0 * n_edges * d_in + 2.0 * n_nodes * d_in * d_out


def fwd_cost(n_dst, n_req, n_edges, d_in, d_out, activate):
    """Least FLOPs and HBM bytes of one ``layer_apply`` call on real rows and
    edges: the weighted gather-sum over the edges, the matmul; read the
    gathered rows, the edge lists (src, dst, weight) and W, write the
    output."""
    flops = 2.0 * n_edges * d_in + 2.0 * n_dst * d_in * d_out
    nbytes = 4.0 * (n_req * d_in + d_in * d_out + n_dst * d_out) + 12.0 * n_edges
    return flops, nbytes


def bwd_cost(n_dst, n_req, n_edges, d_in, d_out, activate):
    """Least work of one ``layer_vjp`` call: the aggregation again (its
    result feeds dW), the matmul again only where ReLU needs Z, dW and
    dAgg, and the scatter of dAgg over the edges; read the gathered rows,
    the cotangent, the edges and W, write dGA and dW."""
    flops = (4.0 * n_edges * d_in
             + (6.0 if activate else 4.0) * n_dst * d_in * d_out)
    nbytes = (4.0 * (2 * n_req * d_in + n_dst * d_out + 2 * d_in * d_out)
              + 12.0 * n_edges)
    return flops, nbytes
