"""Logical-axis sharding rules for every architecture family.

Mesh axes:
  * ``pod``   — outer data parallelism across pods (multi-pod mesh only)
  * ``data``  — intra-pod data parallelism
  * ``model`` — tensor/expert/sequence parallelism (intra-pod, fastest ICI)

LM rules (Megatron-style TP with GQA-aware KV handling):
  embeddings vocab-sharded; attention Q projections column-parallel on the
  flattened (H*Dh) dim; **K/V projections replicated** (GQA kv-heads [8] do
  not divide the 16-way model axis — replicating the small KV computation
  avoids a reshape-forced resharding, see DESIGN.md §5); output and FFN-down
  row-parallel; FFN-up/gate column-parallel.  MoE experts expert-parallel
  when n_experts % model_size == 0 (deepseek 64e), otherwise per-expert
  tensor-parallel (mixtral 8e on a 16-way axis).

Decode caches are **sequence-sharded** over ``model`` (split-K / flash-
decoding style): KV slots divide evenly, every chip holds 1/16th of the
cache, and the softmax combine is XLA's partial-reduce.

Recsys embedding tables are vocab-sharded over ``model`` when large
(>= 4 * model axis rows), replicated otherwise.  GNN node/edge arrays are
sharded over the flattened (pod, data, model) axis set.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "dp_axes", "dp_size", "model_size", "ns", "replicated",
    "lm_param_pspecs", "lm_batch_pspec", "kv_cache_pspecs",
    "recsys_param_pspecs", "gnn_param_pspecs", "tree_shardings",
]


def dp_axes(mesh: Mesh):
    """Axes used for batch (data) parallelism."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def dp_size(mesh: Mesh) -> int:
    """Total data-parallel ways: product of the mesh's dp axis sizes."""
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_size(mesh: Mesh) -> int:
    return mesh.shape["model"]


def ns(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def tree_shardings(mesh: Mesh, pspec_tree) -> Any:
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# --------------------------------------------------------------------------
# LM family
# --------------------------------------------------------------------------


def _lm_leaf_pspec(path: str, shape, mesh: Mesh, n_kv_heads: int = 0) -> P:
    ms = model_size(mesh)
    rank = len(shape)

    def last_div(d):
        return shape[d] % ms == 0

    if "emb" in path and "unemb" not in path:
        return P("model", None) if last_div(0) else P()
    if "unemb" in path:
        return P(None, "model") if last_div(1) else P()
    # stacked layer params have a leading L axis (rank+1 vs their math rank)
    if any(k in path for k in ("wq", "w_kv_b")):
        # column-parallel: shard the flattened head-output dim (last)
        if rank == 3 and last_div(2):
            return P(None, None, "model")
        if rank == 2 and last_div(1):  # bias (L, F)
            return P(None, "model")
        return P()
    if any(k in path for k in ("wk", "wv")):
        # column-parallel only when kv heads divide TP cleanly (reshape-safe);
        # otherwise ROW-parallel on d_model (partial sums; GSPMD inserts the
        # all-reduce) — keeps KV params + their f32 optimizer moments sharded.
        if n_kv_heads % ms == 0 and rank == 3 and last_div(2):
            return P(None, None, "model")
        if rank == 3 and shape[1] % ms == 0:
            return P(None, "model", None)
        return P()
    if "w_kv_a" in path:  # MLA down-projection: row-parallel on d_model
        return P(None, "model", None) if rank == 3 and shape[1] % ms == 0 else P()
    if "kv_norm" in path:
        return P()
    if "wo" in path:
        if rank == 3 and shape[1] % ms == 0:
            return P(None, "model", None)
        return P()
    if any(k in path for k in ("ffn", "shared")):
        if "w2" in path:
            return P(None, "model", None) if rank == 3 and shape[1] % ms == 0 else P()
        if rank == 3 and last_div(2):
            return P(None, None, "model")
        return P()
    if "router" in path:
        return P()
    if "moe" in path and rank == 4:  # (L, E, D, F) expert weights
        if shape[1] % ms == 0:
            return P(None, "model", None, None)  # expert-parallel
        # per-expert tensor-parallel
        if "w2" in path:
            return P(None, None, "model", None) if shape[2] % ms == 0 else P()
        return P(None, None, None, "model") if shape[3] % ms == 0 else P()
    return P()  # norms, scalars


def lm_param_pspecs(param_specs, mesh: Mesh, n_kv_heads: int = 0):
    """ShapeDtypeStruct pytree -> PartitionSpec pytree."""

    def assign(path, leaf):
        path_str = "/".join(str(getattr(k, "key", k)) for k in path)
        return _lm_leaf_pspec(path_str, leaf.shape, mesh, n_kv_heads)

    return jax.tree_util.tree_map_with_path(assign, param_specs)


def lm_batch_pspec(mesh: Mesh) -> P:
    return P(dp_axes(mesh), None)


def kv_cache_pspecs(cache_specs, mesh: Mesh, batch_shardable: bool = True):
    """Sequence-shard decode caches over `model`; batch over dp axes."""
    dp = dp_axes(mesh) if batch_shardable else None

    def assign(path, leaf):
        name = str(getattr(path[-1], "name", getattr(path[-1], "key", "")))
        if name in ("k", "v", "c_kv", "k_rope"):
            # (L, B, slots, ...) — shard slots over model if divisible
            spec = [None, dp, None] + [None] * (len(leaf.shape) - 3)
            if leaf.shape[2] % model_size(mesh) == 0:
                spec[2] = "model"
            return P(*spec)
        return P()  # slot_pos, pos

    return jax.tree_util.tree_map_with_path(assign, cache_specs)


# --------------------------------------------------------------------------
# Recsys
# --------------------------------------------------------------------------


def recsys_param_pspecs(param_specs, mesh: Mesh):
    ms = model_size(mesh)

    def assign(path, leaf):
        path_str = "/".join(str(getattr(k, "key", k)) for k in path)
        if ("table_" in path_str or "wide_" in path_str) and len(leaf.shape) == 2:
            if leaf.shape[0] >= 4 * ms:
                return P("model", None)
        return P()

    return jax.tree_util.tree_map_with_path(assign, param_specs)


def recsys_batch_pspec(mesh: Mesh, rank: int) -> P:
    return P(dp_axes(mesh), *([None] * (rank - 1)))


# --------------------------------------------------------------------------
# GNN
# --------------------------------------------------------------------------


def gnn_param_pspecs(param_specs, mesh: Mesh):
    return jax.tree.map(lambda _: P(), param_specs)  # tiny params: replicate


def graph_axes(mesh: Mesh):
    """Flattened axis tuple for sharding node/edge arrays."""
    return tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
