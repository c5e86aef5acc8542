"""Shared dry-run/smoke plumbing for the four GNN architectures.

The four assignment shapes:
  full_graph_sm  N=2,708  E=10,556  d_feat=1,433   (cora-like full-batch)
  minibatch_lg   1,024 seeds × fanout 15·10 on a 232,965-node graph
                 (reddit-like; the step sees the SAMPLED subgraph —
                 169,984 nodes / 168,960 edges, static shapes)
  ogb_products   N=2,449,029  E=61,859,140  d_feat=100 (full-batch-large)
  molecule       128 graphs × 30 nodes / 64 edges (block-diagonal batch)

Node/edge arrays shard over ALL mesh axes (batch_over_all policy — GNN has
no TP dim, so 'model' joins the data axes); dry-run dims are padded up to a
512 multiple (pad rows carry zero masks).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..data.sampler import subgraph_shape
from ..dist.sharding import ShardingPolicy
from ..optim import AdamW
from .base import Bundle, pad_to

MB_NODES, MB_EDGES = subgraph_shape(1024, (15, 10))

GNN_SHAPES = {
    "full_graph_sm": dict(n_nodes=2708, n_edges=10556, d_feat=1433,
                          n_classes=7, task="node"),
    "minibatch_lg": dict(n_nodes=MB_NODES, n_edges=MB_EDGES, d_feat=602,
                         n_classes=41, task="node", sampled=True),
    "ogb_products": dict(n_nodes=2449029, n_edges=61859140, d_feat=100,
                         n_classes=47, task="node"),
    "molecule": dict(n_nodes=30 * 128, n_edges=64 * 128, d_feat=16,
                     n_classes=1, task="graph", n_graphs=128),
}


def gnn_policy(mesh) -> ShardingPolicy:
    return ShardingPolicy(mesh_axes=tuple(mesh.axis_names), fsdp=False,
                          batch_over_all=True)


def padded_dims(shape_info, mesh) -> tuple[int, int]:
    m = int(np.prod(mesh.devices.shape))
    return (pad_to(shape_info["n_nodes"], m),
            pad_to(shape_info["n_edges"], m))


def gnn_train_bundle(mesh, shape_info, *, params_abs, loss_closure,
                     batch_sds: dict, batch_row_sharded: dict,
                     description: str) -> Bundle:
    """Generic GNN train-step bundle: replicated small params + AdamW,
    node/edge tensors sharded over every mesh axis."""
    policy = gnn_policy(mesh)
    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(policy.data_axes))
    opt = AdamW(lr=1e-3, weight_decay=0.0)
    opt_abs = opt.init_abstract(params_abs)
    state = {"params": params_abs, "opt": opt_abs,
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    pshard = jax.tree.map(lambda _: repl, params_abs)
    state_shard = {"params": pshard,
                   "opt": {"m": pshard, "v": pshard, "count": repl},
                   "step": repl}
    batch_shard = {k: (rows if batch_row_sharded.get(k, True) else repl)
                   for k in batch_sds}

    def train_step(state, batch):
        def lf(p):
            return loss_closure(p, batch)
        loss, grads = jax.value_and_grad(lf)(state["params"])
        params, opt_state = opt.update(state["params"], grads, state["opt"])
        return ({"params": params, "opt": opt_state,
                 "step": state["step"] + 1}, {"loss": loss})

    return Bundle(fn=train_step, args=(state, batch_sds),
                  in_shardings=(state_shard, batch_shard), donate=(0,),
                  description=description)


def node_batch_sds(n_nodes, n_edges, d_feat, *, with_pos=False,
                   n_graphs=None, triplet_cap=None):
    f32, i32 = jnp.float32, jnp.int32
    sds = {
        "node_feat": jax.ShapeDtypeStruct((n_nodes, d_feat), f32),
        "src": jax.ShapeDtypeStruct((n_edges,), i32),
        "dst": jax.ShapeDtypeStruct((n_edges,), i32),
        "labels": jax.ShapeDtypeStruct(
            ((n_graphs,) if n_graphs else (n_nodes,)), i32),
        "label_mask": jax.ShapeDtypeStruct(
            ((n_graphs,) if n_graphs else (n_nodes,)), f32),
    }
    if with_pos:
        sds["positions"] = jax.ShapeDtypeStruct((n_nodes, 3), f32)
    if n_graphs:
        sds["graph_id"] = jax.ShapeDtypeStruct((n_nodes,), i32)
    if triplet_cap:
        t = n_edges * triplet_cap
        sds["t_kj"] = jax.ShapeDtypeStruct((t,), i32)
        sds["t_ji"] = jax.ShapeDtypeStruct((t,), i32)
        sds["t_mask"] = jax.ShapeDtypeStruct((t,), f32)
    return sds


def gnn_flops_info(shape_name: str, per_node_flops: float,
                   per_edge_flops: float, n_params: int,
                   train: bool = True, scan_factor: int = 1) -> dict:
    info = GNN_SHAPES[shape_name]
    fwd = (info["n_nodes"] * per_node_flops
           + info["n_edges"] * per_edge_flops)
    model_flops = 3 * fwd if train else fwd  # fwd + bwd ≈ 2×fwd
    return {"n_params": n_params, "n_active": n_params,
            "tokens": info["n_nodes"], "model_flops": model_flops,
            "kind": "train", "scan_factor": scan_factor}


def gnn_partitioned_bundle(mesh, shape_info, *, params_abs, local_loss,
                           batch_sds: dict, description: str) -> Bundle:
    """Partition-parallel GNN train step (DistGNN cd-0 style).

    For web-scale full-batch graphs whose edge tensors cannot replicate
    (XLA SPMD replicates dynamically-indexed gathers), the data pipeline
    pre-partitions the graph (METIS-like, minimizing cut edges) and each
    device runs the model on its LOCAL subgraph inside shard_map;
    cross-partition edges are handled by delayed/dropped aggregation within
    the step (published: DistGNN's cd-0; bounded-staleness variants exist).
    Gradients psum through shard_map's autodiff; loss is pmean'd.

    ``local_loss(params, local_batch, n_local)`` runs unchanged model code
    on per-shard arrays.
    """
    policy = gnn_policy(mesh)
    axes = policy.data_axes
    n_dev = int(np.prod(mesh.devices.shape))
    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(axes))
    opt = AdamW(lr=1e-3, weight_decay=0.0)
    state = {"params": params_abs, "opt": opt.init_abstract(params_abs),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    pshard = jax.tree.map(lambda _: repl, params_abs)
    state_shard = {"params": pshard,
                   "opt": {"m": pshard, "v": pshard, "count": repl},
                   "step": repl}
    batch_shard = {k: rows for k in batch_sds}

    def sharded_loss(params, batch):
        def local(params, b):
            loss = local_loss(params, b)
            for ax in axes:
                loss = jax.lax.pmean(loss, ax)
            return loss
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), {k: P(axes) for k in batch_sds}),
            out_specs=P(), check_vma=False)(params, batch)

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: sharded_loss(p, batch))(state["params"])
        params, opt_state = opt.update(state["params"], grads, state["opt"])
        return ({"params": params, "opt": opt_state,
                 "step": state["step"] + 1}, {"loss": loss})

    return Bundle(fn=train_step, args=(state, batch_sds),
                  in_shardings=(state_shard, batch_shard), donate=(0,),
                  description=description + " [partition-parallel cd-0]")
