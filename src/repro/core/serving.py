"""Distributed tree-ensemble serving step (the paper's arch at pod scale).

Same math as ``repro.kernels.ref`` (bit-identical — tested).  Batched tree
inference is embarrassingly row-parallel, but GSPMD does not see that: the
loop-carried node-index vector gets replicated and every per-level gather
emits a (rows,) all-reduce — measured 5.37 GB/device/step on serve_1m; adding
with_sharding_constraint inside the loop body made it *worse* (10.7 GB of
all-gather on top).  EXPERIMENTS.md §Perf (tree cell) logs both iterations.

The fix is manual SPMD: ``shard_map`` over every mesh axis with replicated
node tables — all compute is local by construction, collectives drop to
exactly zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.ref import tree_predict_integer_ref
from repro.sharding.ops import current_mesh


def _local_predict(tables: dict, x_keys, depth: int):
    acc = tree_predict_integer_ref(
        x_keys,
        tables["feature"],
        tables["threshold_key"],
        tables["left"],
        tables["right"],
        tables["leaf_fixed"],
        depth,
    )
    return acc, jnp.argmax(acc, axis=1).astype(jnp.int32)


def tree_serve_step(tables: dict, x_keys, depth: int):
    """tables: feature/threshold_key/left/right (T,N) + leaf_fixed (T,N,C).
    x_keys: (B, F) int32.  Returns (scores (B,C) uint32, preds (B,) int32).

    Inside a ``use_mesh`` context the rows are shard_map'ed over every mesh
    axis (tables replicated); otherwise runs locally (CPU tests).
    """
    mesh = current_mesh()
    if mesh is None:
        return _local_predict(tables, x_keys, depth)
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    fn = jax.shard_map(
        lambda t, x: _local_predict(t, x, depth),
        mesh=mesh,
        in_specs=(P(), P(axes, None)),
        out_specs=(P(axes, None), P(axes)),
        check_vma=False,
    )
    return fn(tables, x_keys)
