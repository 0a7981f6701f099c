"""Plain GraphSAGE layer with the mean aggregator (Hamilton et al. 2017,
arXiv:1706.02216) for the reference: forward, backward by hand, and the
least work of the program's layer calls.

    Z = H W_self + b_self + (D^-1 A H) W_nbr + b_nbr,   H' = relu(Z)

on every layer but the last, with ``A`` the in-edges (self loops included)
and ``D`` the in-degree. Aggregation is over all neighbours (full graph),
not over the paper's sampled 25-10 neighbourhoods. The product is taken as
``D^-1 A (H W_nbr)``, which equals ``(D^-1 A H) W_nbr``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dense(key, d_in, d_out):
    return {"w": jax.random.normal(key, (d_in, d_out), jnp.float32)
            / np.sqrt(d_in),
            "b": jnp.zeros((d_out,), jnp.float32)}


def init(key, d_in: int, d_out: int, activate: bool):
    """Weights in the program's parameter tree layout, float32. Every
    layer has the same tree, whatever its position (``activate``)."""
    k1, k2 = jax.random.split(key)
    return {"self": _dense(k1, d_in, d_out), "nbr": _dense(k2, d_in, d_out)}


def edge_weight(src_deg: np.ndarray, dst_deg: np.ndarray) -> np.ndarray:
    return np.ones(src_deg.shape, np.float32)


def forward(p, h, g, activate: bool):
    inv_deg = (1.0 / g.deg).astype(h.dtype)[:, None]
    z = (h @ p["self"]["w"] + p["self"]["b"]
         + g.agg(h @ p["nbr"]["w"]) * inv_deg + p["nbr"]["b"])
    return z, (jax.nn.relu(z) if activate else z)


def backward(p, h, z, d_out, g, activate: bool):
    dz = d_out * (z > 0).astype(z.dtype) if activate else d_out
    inv_deg = (1.0 / g.deg).astype(h.dtype)[:, None]
    q = g.agg(dz * inv_deg, transpose=True)   # A^T D^-1 dZ
    db = dz.sum(axis=0)
    dp = {"self": {"w": h.T @ dz, "b": db}, "nbr": {"w": h.T @ q, "b": db}}
    return dp, dz @ p["self"]["w"].T + q @ p["nbr"]["w"].T


def model_flops(n_nodes, n_edges, d_in, d_out, activate):
    """Model FLOPs of one layer's forward: the neighbour
    sum and the two matmuls over every real node and edge
    (``repro.configs.base.gnn_model_flops``), at any position."""
    return 2.0 * n_edges * d_in + 4.0 * n_nodes * d_in * d_out


def fwd_cost(n_dst, n_req, n_edges, d_in, d_out, activate):
    """Least FLOPs and HBM bytes of one ``layer_apply`` call on real rows and
    edges: the masked sum over the edges, the mean, two matmuls; read the
    gathered rows, the edge lists (src, dst, mask) and both W, write the
    output."""
    flops = 2.0 * n_edges * d_in + n_dst * d_in + 4.0 * n_dst * d_in * d_out
    nbytes = (4.0 * (n_req * d_in + 2 * d_in * d_out + n_dst * d_out)
              + 12.0 * n_edges)
    return flops, nbytes


def bwd_cost(n_dst, n_req, n_edges, d_in, d_out, activate):
    """Least work of one ``layer_vjp`` call: the mean aggregation again (it
    feeds dW_nbr), both matmuls again only where ReLU needs Z, the two dW
    and the two input cotangents, and the scatter over the edges; read the
    gathered rows, the cotangent, the edges and both W, write dGA and both
    dW."""
    flops = (4.0 * n_edges * d_in + 2.0 * n_dst * d_in
             + (12.0 if activate else 8.0) * n_dst * d_in * d_out)
    nbytes = (4.0 * (2 * n_req * d_in + n_dst * d_out + 4 * d_in * d_out)
              + 12.0 * n_edges)
    return flops, nbytes
