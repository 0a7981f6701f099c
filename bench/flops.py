"""FLOPs and bytes: the model's, per epoch or pass, and the least work of
each layer program the device runs.

``gnn_model_flops`` is the arithmetic of
``repro.configs.base.gnn_model_flops`` for the models the cells run, taken
from each ``bench/models/<model>.py``'s ``model_flops``. The per-call costs
come from the same modules and count the real rows and edges of each work
unit, never the padded ones, so that cutting padding shows as a gain.
"""
from __future__ import annotations


def gnn_model_flops(model_mod, dims, n_nodes: int, n_edges: int,
                    train: bool = True) -> float:
    """Model FLOPs of one forward pass, or of an epoch (forward plus twice
    the forward for the backward) with ``train``."""
    L = len(dims) - 1
    f = sum(model_mod.model_flops(n_nodes, n_edges, dims[l], dims[l + 1],
                                  l < L - 1)
            for l in range(L))
    return (3.0 if train else 1.0) * f


def layer_costs(model_mod, units, dims, peaks, backward: bool):
    """Least device seconds of one pass of ``layer_apply`` (or, with
    ``backward``, ``layer_vjp``) over every layer and work unit: for each
    call the larger of FLOPs over the bf16 peak and bytes over the HBM
    peak. ``units`` are ``(n_dst, n_req, n_edges)`` of real rows and edges.
    Returns ``(seconds, calls the byte bound decides, calls)``."""
    cost = model_mod.bwd_cost if backward else model_mod.fwd_cost
    n_layers = len(dims) - 1
    total, by_bytes, calls = 0.0, 0, 0
    for l in range(n_layers):
        for n_dst, n_req, n_edges in units:
            flops, nbytes = cost(n_dst, n_req, n_edges, dims[l], dims[l + 1],
                                 l < n_layers - 1)
            t_flops = flops / peaks["bf16_flops"]
            t_bytes = nbytes / peaks["hbm_bytes_per_s"]
            total += max(t_flops, t_bytes)
            by_bytes += t_bytes >= t_flops
            calls += 1
    return total, by_bytes, calls
