"""Family-level ArchSpec builders (LM / GNN / RecSys)."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (
    ArchSpec, Built, Cell, GNN_SHAPES, LM_SHAPES, RECSYS_SHAPES,
    gnn_model_flops, lm_attention_correction, lm_model_flops, mfg_hop_sizes,
    recsys_model_flops,
)
from repro.models.lm.transformer import LMConfig
from repro.models.lm import steps as lm_steps
from repro.models.recsys.two_tower import (
    TwoTowerConfig, init_two_tower, two_tower_loss, serve_user_tower,
    score_candidates,
)
from repro.models.lm.sharding import batch_spec, param_specs
from repro.optim.adamw import adamw_init, adamw_update
from repro.distributed import gnn_parallel as gp


# ---------------------------------------------------------------------------
# LM
# ---------------------------------------------------------------------------

def make_lm_arch(cfg: LMConfig, describe: str, smoke_cfg: LMConfig) -> ArchSpec:
    cells = {}
    for shape, s in LM_SHAPES.items():
        skip = None
        if shape == "long_500k" and not cfg.sub_quadratic:
            skip = (
                "full-attention arch: long_500k requires sub-quadratic "
                "attention (DESIGN.md §4)"
            )
        cells[shape] = Cell(kind=s["kind"], skip=skip)

    def build(
        shape: str, mesh: Mesh,
        n_layers: Optional[int] = None, unroll: bool = False,
        variant: Optional[str] = None,   # LM variants select via env flags
    ) -> Built:
        cfg_l = cfg
        if n_layers is not None or unroll:
            cfg_l = dataclasses.replace(
                cfg, n_layers=n_layers or cfg.n_layers, unroll_layers=unroll
            )
        return _build_lm(cfg_l, shape, mesh)

    def _build_lm(cfg: LMConfig, shape: str, mesh: Mesh) -> Built:
        s = LM_SHAPES[shape]
        kind, batch, seq = s["kind"], s["batch"], s["seq"]
        out_sh = None
        if kind == "train":
            fn, _, _, _ = lm_steps.make_train_step(cfg, mesh)
            args, shardings = lm_steps.lm_train_inputs(cfg, batch, seq, mesh)
            # params/opt-state keep their input sharding through the update —
            # without this, GSPMD can materialize unsharded stacked grads.
            out_sh = (shardings[0], shardings[1], None)
        elif kind == "prefill":
            fn = lm_steps.make_prefill_step(cfg, mesh)
            args, shardings = lm_steps.lm_prefill_inputs(cfg, batch, seq, mesh)
        else:
            fn = lm_steps.make_decode_step(cfg, mesh)
            args, shardings = lm_steps.lm_decode_inputs(cfg, batch, seq, mesh)
            out_sh = (None, shardings[1])  # cache keeps its sharding
        corr = lm_attention_correction(cfg, kind, batch, seq)
        meta = dict(
            model_flops=lm_model_flops(cfg, kind, batch, seq) + corr["flops"],
            attn_corr_flops=corr["flops"],
            attn_corr_bytes=corr["bytes"],
            params=cfg.param_count(),
            active_params=cfg.active_param_count(),
            kind=kind,
        )
        return Built(fn, args, shardings, meta, out_shardings=out_sh)

    def smoke():
        from repro.models.lm.transformer import init_lm_params, lm_loss
        params = init_lm_params(jax.random.PRNGKey(0), smoke_cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, smoke_cfg.vocab)
        (loss, (ce, aux)), grads = jax.value_and_grad(
            lambda p: lm_loss(p, toks, smoke_cfg), has_aux=True
        )(params)
        gn = float(
            sum(jnp.sum(jnp.abs(g)) for g in jax.tree.leaves(grads))
        )
        return dict(loss=float(loss), grad_norm=gn,
                    finite=bool(np.isfinite(float(loss)) and np.isfinite(gn)))

    fd = cfg.moe.first_dense if cfg.moe is not None else 0
    calib = (fd + 2, fd + 4, cfg.n_layers)
    return ArchSpec(cfg.name, "lm", describe, cells, build, smoke,
                    layer_calib=calib)


# ---------------------------------------------------------------------------
# GNN
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GNNArch:
    name: str
    model: str             # key in GNN_REGISTRY
    n_layers: int
    d_hidden: int
    dataset: str           # key in GNN_DATASETS
    loss_kind: str = "ce"  # graphcast: "mse"
    d_out_override: Optional[int] = None   # graphcast: 227 vars
    note: str = ""


def gnn_dims(a: GNNArch, d_feat: int, classes: int):
    d_out = a.d_out_override or classes
    return [d_feat] + [a.d_hidden] * (a.n_layers - 1) + [d_out]


def _abstract_gnn_params(a: GNNArch, dims):
    from repro.models.gnn.layers import get_gnn
    spec = get_gnn(a.model)
    return jax.eval_shape(
        lambda k: spec.init(k, dims[0], a.d_hidden, dims[-1], a.n_layers),
        jax.random.PRNGKey(0),
    )


def make_gnn_arch(a: GNNArch, describe: str) -> ArchSpec:
    cells = {s: Cell(kind=v["kind"]) for s, v in GNN_SHAPES.items()}

    def build(shape: str, mesh: Mesh, variant: str = "base") -> Built:
        """variant: "base" (CAGNET-style, sharded) | "unsharded" (GSPMD left
        alone — §Perf iteration-0 diagnostic) | "halo" (partitioned-halo,
        the beyond-paper optimization)."""
        s = GNN_SHAPES[shape]
        dims = gnn_dims(a, s["d_feat"], s.get("classes", 16))
        d_out = dims[-1]
        p_abs = _abstract_gnn_params(a, dims)
        o_abs = jax.eval_shape(adamw_init, p_abs)
        rep = NamedSharding(mesh, P())
        pshard = jax.tree.map(lambda _: rep, p_abs)
        oshard = {"m": pshard, "v": pshard, "step": rep}

        if s["kind"] == "fullgraph" and variant == "halo":
            n_local, n_halo, args, shard = gp.partitioned_inputs(
                s["n_nodes"], s["n_edges"], s["d_feat"], d_out, mesh,
                loss_kind=a.loss_kind,
            )
            fn = gp.make_partitioned_train_step(
                a.model, n_local, n_halo, mesh, loss_kind=a.loss_kind,
            )
            flops = gnn_model_flops(dims, s["n_nodes"], s["n_edges"], model=a.model)
            meta = dict(model_flops=flops, kind="train", dims=dims,
                        variant=variant)
            return Built(fn, (p_abs, o_abs) + tuple(args),
                         (pshard, oshard) + tuple(shard), meta)
        if s["kind"] == "fullgraph":
            n_pad, args, shard = gp.fullgraph_inputs(
                s["n_nodes"], s["n_edges"], s["d_feat"], d_out, mesh,
                loss_kind=a.loss_kind,
            )
            fn = gp.make_fullgraph_train_step(
                a.model, n_pad, loss_kind=a.loss_kind,
                sharded=(variant != "unsharded"),
                remat=(variant != "unsharded"),
            )
            flops = gnn_model_flops(dims, s["n_nodes"], s["n_edges"], model=a.model)
        elif s["kind"] == "mfg":
            data_axes = tuple(x for x in ("pod", "data") if x in mesh.axis_names)
            n_groups = int(np.prod([
                mesh.devices.shape[mesh.axis_names.index(x)] for x in data_axes
            ]))
            hops = mfg_hop_sizes(
                a.n_layers, s["batch_nodes"], s["fanout"], s["n_nodes"],
                n_groups,
            )
            fn = gp.make_mfg_train_step(a.model, hops, loss_kind=a.loss_kind)
            (x_in, hop_args, labels), (lead, hop_shard, lead2) = gp.mfg_inputs(
                hops, s["d_feat"], d_out, n_groups, mesh,
                loss_kind=a.loss_kind,
            )
            args = (x_in, hop_args, labels)
            shard = (lead, hop_shard, lead2)
            tot_e = n_groups * sum(h[2] for h in hops)
            tot_n = n_groups * sum(h[1] for h in hops)
            flops = gnn_model_flops(
                dims, tot_n // max(a.n_layers, 1), tot_e // max(a.n_layers, 1),
                model=a.model,
            )
        else:  # batched small graphs
            fn = gp.make_batched_graph_train_step(
                a.model, s["n_nodes"], loss_kind=a.loss_kind
            )
            args, shard = gp.batched_graph_inputs(
                s["n_nodes"], s["n_edges"], s["d_feat"], d_out, s["batch"],
                mesh, loss_kind=a.loss_kind,
            )
            flops = s["batch"] * gnn_model_flops(
                dims, s["n_nodes"], s["n_edges"], model=a.model
            )
        meta = dict(model_flops=flops, kind="train", dims=dims)
        return Built(fn, (p_abs, o_abs) + tuple(args),
                     (pshard, oshard) + tuple(shard), meta)

    def smoke():
        from repro.graph import kronecker_graph, gcn_norm_coeffs
        from repro.graph.csr import add_self_loops
        from repro.graph.synthetic import random_features, random_labels
        from repro.models.gnn.layers import (
            get_gnn, full_graph_topo, full_graph_forward,
        )
        spec = get_gnn(a.model)
        g = add_self_loops(kronecker_graph(512, 6, seed=0))
        d_feat, classes = 24, 8
        n_layers = min(a.n_layers, 3)
        d_hidden = min(a.d_hidden, 32)
        d_out = 8 if a.loss_kind == "ce" else 12
        params = spec.init(jax.random.PRNGKey(0), d_feat, d_hidden, d_out, n_layers)
        x = jnp.asarray(random_features(g.n_nodes, d_feat, 0))
        topo = full_graph_topo(g.indptr, g.indices, g.n_nodes, gcn_norm_coeffs(g))
        out = full_graph_forward(spec, params, x, topo)
        ok = bool(jnp.all(jnp.isfinite(out)))
        # one train step
        if a.loss_kind == "mse":
            y = jnp.asarray(random_features(g.n_nodes, d_out, 1))
            loss_fn = lambda p: jnp.mean(
                (full_graph_forward(spec, p, x, topo) - y) ** 2
            )
        else:
            from repro.models.gnn.layers import full_graph_loss
            y = jnp.asarray(random_labels(g.n_nodes, d_out, 1))
            loss_fn = lambda p: full_graph_loss(spec, p, x, topo, y)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        gn = float(sum(jnp.sum(jnp.abs(l)) for l in jax.tree.leaves(grads)))
        return dict(
            loss=float(loss), grad_norm=gn,
            out_shape=tuple(out.shape),
            finite=ok and bool(np.isfinite(float(loss))),
        )

    return ArchSpec(a.name, "gnn", describe, cells, build, smoke, gnn=a)


# ---------------------------------------------------------------------------
# RecSys
# ---------------------------------------------------------------------------

def make_recsys_arch(cfg: TwoTowerConfig, describe: str) -> ArchSpec:
    cells = {s: Cell(kind=v["kind"]) for s, v in RECSYS_SHAPES.items()}

    def _param_shardings(mesh):
        p_abs = jax.eval_shape(
            lambda k: init_two_tower(k, cfg), jax.random.PRNGKey(0)
        )
        specs = param_specs(p_abs, mesh)
        return p_abs, jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    def build(shape: str, mesh: Mesh) -> Built:
        s = RECSYS_SHAPES[shape]
        batch = s["batch"]
        p_abs, pshard = _param_shardings(mesh)
        bsh = NamedSharding(mesh, batch_spec(batch, mesh))

        def S(shape_, dt):
            return jax.ShapeDtypeStruct(shape_, dt)

        uids = S((batch, cfg.n_user_fields, cfg.bag_size), jnp.int32)
        if s["kind"] == "train":
            o_abs = jax.eval_shape(adamw_init, p_abs)
            oshard = {"m": pshard, "v": pshard,
                      "step": NamedSharding(mesh, P())}
            iids = S((batch, cfg.n_item_fields, cfg.bag_size), jnp.int32)

            def fn(params, opt_state, u, i):
                (loss, acc), grads = jax.value_and_grad(
                    lambda p: two_tower_loss(p, u, i, cfg), has_aux=True
                )(params)
                params2, opt2 = adamw_update(grads, params, opt_state, lr=1e-3)
                return params2, opt2, loss

            args = (p_abs, o_abs, uids, iids)
            shard = (pshard, oshard, bsh, bsh)
            flops = recsys_model_flops(cfg, "train", batch)
        elif s["kind"] == "serve":
            def fn(params, u):
                return serve_user_tower(params, u, cfg)

            args = (p_abs, uids)
            shard = (pshard, bsh)
            flops = recsys_model_flops(cfg, "serve", batch)
        else:  # retrieval
            nc = s["n_candidates"]
            cand = S((nc, cfg.tower_mlp[-1]), jnp.float32)
            data_axes = tuple(
                x for x in ("pod", "data") if x in mesh.axis_names
            )

            def fn(params, u, c):
                return score_candidates(params, u, c, cfg, top_k=128)

            args = (p_abs, uids, cand)
            shard = (
                pshard, NamedSharding(mesh, P(None)),
                NamedSharding(mesh, P(data_axes, None)),
            )
            flops = recsys_model_flops(cfg, "retrieval", batch, nc)
        meta = dict(model_flops=flops, kind=s["kind"])
        return Built(fn, args, shard, meta)

    def smoke():
        small = dataclasses.replace(
            cfg, embed_dim=16, tower_mlp=(32, 16), bag_size=4,
            user_vocab=1000, item_vocab=1000,
        )
        params = init_two_tower(jax.random.PRNGKey(0), small)
        u = jax.random.randint(
            jax.random.PRNGKey(1), (8, small.n_user_fields, 4), 0, 1000
        )
        i = jax.random.randint(
            jax.random.PRNGKey(2), (8, small.n_item_fields, 4), 0, 1000
        )
        (loss, acc), grads = jax.value_and_grad(
            lambda p: two_tower_loss(p, u, i, small), has_aux=True
        )(params)
        gn = float(sum(jnp.sum(jnp.abs(g)) for g in jax.tree.leaves(grads)))
        return dict(loss=float(loss), grad_norm=gn,
                    finite=bool(np.isfinite(float(loss))))

    return ArchSpec(cfg.name, "recsys", describe, cells, build, smoke)
