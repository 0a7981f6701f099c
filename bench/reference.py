"""Plain whole-graph reference for the output check: the layer equations of
``bench/models/<model>.py`` in ``jax.numpy``, op by op and layer by layer,
with the edge sums taken in fixed-size edge chunks so that it fits one chip
at a cell's size. It imports nothing of the program and takes nothing the program made:
graph, features, labels and weights all come from the benchmark.

``control=None`` runs float32 under ``highest`` matmul precision: that is
the reference. The configurations state float32 arrays at the chip's
default matmul precision; the step below that, which the check must
reject, is ``control="bf16"``: every array and operation in bfloat16.
``control="fp8"`` rounds every array through float8 (e4m3, scaled per
tensor by its largest magnitude) at each layer's inputs and outputs,
computed at the default precision, and is read for the record.
"""
from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 19


@partial(jax.jit, static_argnames=("n", "transpose"))
def _edge_sum(x, src, dst, w, *, n, transpose):
    """``out[d] += w * x[s]`` over every edge (``out[s] += w * x[d]`` when
    ``transpose``); edges come as ``(chunks, CHUNK)`` arrays, padded with
    weight 0."""
    gather, scatter = (dst, src) if transpose else (src, dst)

    def body(k, out):
        msg = x[gather[k]] * w[k][:, None]
        return out.at[scatter[k]].add(msg)

    out = jnp.zeros((n, x.shape[1]), x.dtype)
    return jax.lax.fori_loop(0, src.shape[0], body, out)


class EdgeOp:
    """The in-edge structure of a CSR graph on the device."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray, edge_weight,
                 dtype=jnp.float32, chunk: int = CHUNK):
        n = indptr.shape[0] - 1
        deg = np.maximum(np.diff(indptr), 1)
        src = indices.astype(np.int32)
        dst = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
        w = edge_weight(deg[src], deg[dst])
        e = src.shape[0]
        pad = (-e) % chunk

        def dev(a, fill=0):
            a = np.concatenate([a, np.full(pad, fill, a.dtype)])
            return jnp.asarray(a.reshape(-1, chunk))

        self.n = n
        self.src, self.dst = dev(src), dev(dst)
        self.w = dev(w).astype(dtype)
        self.deg = jnp.asarray(deg.astype(np.float32))

    def agg(self, x, transpose: bool = False):
        return _edge_sum(x, self.src, self.dst, self.w.astype(x.dtype),
                         n=self.n, transpose=transpose)


@jax.jit
def _xent(z, y, node_w):
    """Weighted cross-entropy and its gradient by hand, in ``z``'s dtype."""
    lse = jax.nn.logsumexp(z, axis=1)
    zy = jnp.take_along_axis(z, y[:, None], axis=1)[:, 0]
    loss = jnp.sum(node_w * (lse - zy))
    dz = (jax.nn.softmax(z, axis=1)
          - jax.nn.one_hot(y, z.shape[1], dtype=z.dtype)) * node_w[:, None]
    return loss, dz


@jax.jit
def _fp8(a):
    """``a`` rounded to float8 e4m3 values after scaling by its largest
    magnitude (to 448), and scaled back. The rounding is done with
    ``frexp``/``ldexp`` arithmetic, not a round trip through the float8
    type: the TPU compiler may drop a ``convert`` pair as excess
    precision."""
    x = a.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    xs = x / scale
    m, e = jnp.frexp(xs)                    # xs = m * 2**e, 0.5 <= |m| < 1
    normal = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)   # 4 significant bits
    sub = jnp.round(xs * 512.0) / 512.0     # below 2**-6: steps of 2**-9
    q = jnp.where(jnp.abs(xs) < 2.0 ** -6, sub, normal)
    return (q * scale).astype(a.dtype)


def run(model, indptr, indices, x, y, params, control=None,
        train: bool = True, node_w=None):
    """Whole-graph reference (``control=None``) or a lower-precision control
    (``"fp8"``, ``"bf16"``). Returns ``out`` (final-layer output, float32
    host array) and, when ``train``, ``loss`` and ``grads`` (host arrays in
    the parameter tree layout). ``node_w`` weights each node's loss term
    (default ``1/n``: the mean)."""
    dtype = jnp.bfloat16 if control == "bf16" else jnp.float32
    ctx = (jax.default_matmul_precision("highest") if control is None
           else contextlib.nullcontext())
    rnd = _fp8 if control == "fp8" else (lambda a: a)
    cast = partial(jax.tree.map, lambda a: rnd(jnp.asarray(a, dtype)))
    with ctx:
        g = EdgeOp(indptr, indices, model.edge_weight, dtype)
        params = cast(list(params))
        h = rnd(jnp.asarray(x, dtype))
        n_layers = len(params)
        saved = []
        for l, p in enumerate(params):
            z, out = model.forward(p, h, g, activate=l < n_layers - 1)
            z, out = rnd(z), rnd(out)
            saved.append((h, z))
            h = out
        res = {"out": np.asarray(h.astype(jnp.float32))}
        if not train:
            return res
        n = g.n
        if node_w is None:
            node_w = np.full(n, 1.0 / n, np.float32)
        loss, d = _xent(h, jnp.asarray(y, jnp.int32),
                        jnp.asarray(node_w, dtype))
        d = rnd(d)
        grads = [None] * n_layers
        for l in range(n_layers - 1, -1, -1):
            hl, zl = saved[l]
            dp, d = model.backward(params[l], hl, zl, d, g,
                                   activate=l < n_layers - 1)
            dp, d = cast(dp), rnd(d)
            grads[l] = jax.tree.map(
                lambda a: np.asarray(a.astype(jnp.float32)), dp)
        res.update(loss=float(loss), grads=grads)
        return res
