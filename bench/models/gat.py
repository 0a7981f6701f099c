"""Plain GAT layer (Veličković et al. 2018, arXiv:1710.10903, §3.3, the
inductive model) for the reference: forward, backward by hand, and the
least work of the program's layer calls.

Per head k, with ``Wh = H W_k`` and the in-edges of each node (self loops
included):

    e_ij = LeakyReLU_0.2(a_src_k . Wh_j + a_dst_k . Wh_i)
    alpha_ij = softmax_j(e_ij)   over node i's in-neighbourhood
    o_ik = sum_j alpha_ij Wh_j

A hidden layer concatenates its 4 heads, adds its input where the widths
match (the skip across the middle layer) and applies ELU; the output layer
averages its 6 heads and applies nothing. Every edge quantity is computed
over ``EdgeOp``'s ``(chunks, CHUNK)`` arrays, head-major ``(H, chunks,
CHUNK)`` so that the edges lie along the chip's lanes; the weighted sums,
whose messages are ``H * d_head`` wide, one chunk at a time. Padding edges
carry weight 0 and are left out of the softmax.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HEADS_HIDDEN, HEADS_OUT = 4, 6
SLOPE = 0.2


def heads(d_out: int, activate: bool):
    """``(H, d_head)``: 4 concatenated heads of ``d_out / 4`` on a hidden
    layer, 6 averaged heads of ``d_out`` on the output layer."""
    if activate:
        return HEADS_HIDDEN, d_out // HEADS_HIDDEN
    return HEADS_OUT, d_out


def _skip(d_in: int, d_out: int, activate: bool) -> bool:
    return activate and d_in == d_out


def init(key, d_in: int, d_out: int, activate: bool):
    """Weights in the program's parameter tree layout, float32: ``w``
    ``(d_in, H, d_head)``, attention vectors ``a_src``, ``a_dst``
    ``(H, d_head)``, bias ``(d_out,)``."""
    H, dh = heads(d_out, activate)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(k1, (d_in, H, dh), jnp.float32)
        / np.sqrt(d_in),
        "a_src": jax.random.normal(k2, (H, dh), jnp.float32),
        "a_dst": jax.random.normal(k3, (H, dh), jnp.float32),
        "b": jnp.zeros((d_out,), jnp.float32),
    }


def edge_weight(src_deg: np.ndarray, dst_deg: np.ndarray) -> np.ndarray:
    """1 on every real edge: ``EdgeOp``'s weights are the edge mask."""
    return np.ones(src_deg.shape, np.float32)


@jax.jit
def _attention(wh, a_src, a_dst, src, dst, w):
    """Every edge's score before the LeakyReLU and its attention
    coefficient, ``(H, chunks, CHUNK)`` each; padding has coefficient 0."""
    s_src = jnp.einsum("nhf,hf->hn", wh, a_src)
    s_dst = jnp.einsum("nhf,hf->hn", wh, a_dst)
    pre = s_src[:, src] + s_dst[:, dst]
    real = w > 0
    score = jnp.where(real, jnp.where(pre > 0, pre, SLOPE * pre), -jnp.inf)
    top = jnp.full(s_dst.shape, -jnp.inf, wh.dtype).at[:, dst].max(score)
    ex = jnp.where(real, jnp.exp(score - top[:, dst]), 0)
    den = jnp.zeros(s_dst.shape, wh.dtype).at[:, dst].add(ex)
    return pre, ex / den[:, dst]


@partial(jax.jit, static_argnames=("n",))
def _weighted_sum(x, alpha, gather, scatter, *, n):
    """``out[scatter_e, k] += alpha_ek x[gather_e, k]`` over every edge,
    one chunk at a time: ``(n, H, d_head)``."""
    def body(c, out):
        msg = x[gather[c]] * alpha[:, c].T[..., None]
        return out.at[scatter[c]].add(msg)

    out = jnp.zeros((n,) + x.shape[1:], x.dtype)
    return jax.lax.fori_loop(0, gather.shape[0], body, out)


@jax.jit
def _edge_dot(x, y, src, dst):
    """``<x[dst_e, k], y[src_e, k]>`` over each head's lanes, for every
    edge, one chunk at a time: ``(H, chunks, CHUNK)``."""
    dot = jax.lax.map(
        lambda c: jnp.einsum("ehf,ehf->he", x[dst[c]], y[src[c]]),
        jnp.arange(src.shape[0]))
    return jnp.swapaxes(dot, 0, 1)


def forward(p, h, g, activate: bool):
    wh = jnp.einsum("nd,dhf->nhf", h, p["w"])
    _, alpha = _attention(wh, p["a_src"], p["a_dst"], g.src, g.dst, g.w)
    agg = _weighted_sum(wh, alpha, g.src, g.dst, n=g.n)
    if not activate:
        z = agg.mean(axis=1) + p["b"]
        return z, z
    z = agg.reshape(g.n, -1) + p["b"]
    if _skip(h.shape[1], z.shape[1], activate):
        z = z + h
    return z, jax.nn.elu(z)


def backward(p, h, z, d_out, g, activate: bool):
    """Gradients of the layer's weights and input, given the cotangent of
    its output; the scores and coefficients are computed again from ``h``."""
    H, dh = p["a_src"].shape
    n = g.n
    dz = d_out * jnp.where(z > 0, 1, jnp.exp(z)) if activate else d_out
    db = dz.sum(axis=0)
    if activate:
        dagg = dz.reshape(n, H, dh)
    else:
        dagg = jnp.broadcast_to(dz[:, None, :] / H, (n, H, dh))
    wh = jnp.einsum("nd,dhf->nhf", h, p["w"])
    pre, alpha = _attention(wh, p["a_src"], p["a_dst"], g.src, g.dst, g.w)
    # o_i = sum_j alpha_ij Wh_j: to Wh_j through the transposed sum, to
    # alpha through each edge's dot product
    dwh = _weighted_sum(dagg, alpha, g.dst, g.src, n=n)
    dalpha = _edge_dot(dagg, wh, g.src, g.dst)
    # softmax over each in-neighbourhood, then the LeakyReLU
    t = jnp.zeros((H, n), h.dtype).at[:, g.dst].add(alpha * dalpha)
    dscore = alpha * (dalpha - t[:, g.dst])
    dpre = dscore * jnp.where(pre > 0, 1, SLOPE).astype(h.dtype)
    ds_src = jnp.zeros((H, n), h.dtype).at[:, g.src].add(dpre)
    ds_dst = jnp.zeros((H, n), h.dtype).at[:, g.dst].add(dpre)
    dwh = (dwh + ds_src.T[..., None] * p["a_src"]
           + ds_dst.T[..., None] * p["a_dst"])
    dp = {"w": jnp.einsum("nd,nhf->dhf", h, dwh),
          "a_src": jnp.einsum("hn,nhf->hf", ds_src, wh),
          "a_dst": jnp.einsum("hn,nhf->hf", ds_dst, wh),
          "b": db}
    dh_ = jnp.einsum("nhf,dhf->nd", dwh, p["w"])
    if _skip(h.shape[1], z.shape[1], activate):
        dh_ = dh_ + dz
    return dp, dh_


def _edge_flops(n_edges, H, hd):
    """Per-edge work of one forward: the score (add, LeakyReLU), the
    softmax (max, subtract, exp, sum) and the weighted sum at ``H * d_head``
    lanes."""
    return 6.0 * n_edges * H + 2.0 * n_edges * hd


def model_flops(n_nodes, n_edges, d_in, d_out, activate):
    """Model FLOPs of one layer's forward over every real node and edge:
    the transform, both scores of every node, the per-edge softmax and
    weighted sum, the softmax's division and the skip or the head mean."""
    H, dh = heads(d_out, activate)
    hd = H * dh
    return (2.0 * n_nodes * d_in * hd + 4.0 * n_nodes * hd
            + _edge_flops(n_edges, H, hd) + 2.0 * n_nodes * hd)


def fwd_cost(n_dst, n_req, n_edges, d_in, d_out, activate):
    """Least FLOPs and HBM bytes of one ``layer_apply`` call on real rows and
    edges: the transform over the gathered rows, their source scores and the
    destinations' scores, the per-edge softmax and weighted sum, the
    division and the skip or head mean; read the gathered rows, W and the
    attention vectors, the edge lists (src, dst, mask), write the output."""
    H, dh = heads(d_out, activate)
    hd = H * dh
    flops = (2.0 * n_req * d_in * hd + 2.0 * (n_req + n_dst) * hd
             + _edge_flops(n_edges, H, hd) + 2.0 * n_dst * hd)
    nbytes = (4.0 * (n_req * d_in + d_in * hd + 2 * hd + n_dst * d_out)
              + 12.0 * n_edges)
    return flops, nbytes


def bwd_cost(n_dst, n_req, n_edges, d_in, d_out, activate):
    """Least work of one ``layer_vjp`` call: the forward again (its
    coefficients and transformed rows feed every gradient), then the
    transposed weighted sum, each edge's dot product with the cotangent, the
    softmax's and the scores' backward, dW and the input cotangent; read the
    gathered rows, the cotangent, W and the edges, write dGA and the
    weights' gradients."""
    H, dh = heads(d_out, activate)
    hd = H * dh
    flops, _ = fwd_cost(n_dst, n_req, n_edges, d_in, d_out, activate)
    flops += (4.0 * n_edges * hd + 8.0 * n_edges * H
              + 2.0 * (n_req + n_dst) * hd + 4.0 * n_req * d_in * hd)
    nbytes = (4.0 * (2 * n_req * d_in + n_dst * d_out + 2 * d_in * hd
                     + 4 * hd) + 12.0 * n_edges)
    return flops, nbytes
