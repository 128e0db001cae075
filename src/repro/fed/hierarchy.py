"""LPS/GPS hierarchical aggregation (paper §II-D, Algorithm 1).

Two modes:

* **Simulation** (host loop over users): ``lps_round`` aggregates each
  cluster's clients with FedAvg; ``gps_aggregate`` averages the *common*
  sub-tree across LPSs (weighted by cluster sample counts) and grafts it
  back into every LPS model — exactly the paper's "share the weights of the
  first common layers with the GPS ... aggregate ... broadcast back".

* **Distributed** (shard_map): cluster membership is data-dependent, so LPS
  groups cannot be static mesh axes.  ``masked_cluster_mean`` computes all
  per-cluster means in ONE batched collective: a one-hot membership matrix
  turns per-cluster FedAvg into ``einsum('u...,ut->t...') / counts`` followed
  by a single ``psum`` over the user axis — the TPU-idiomatic form of the
  paper's LPS message exchange (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.fed.fedavg import (AVG_PRECISION, fedavg as _fedavg,
                               weighted_mean as _wmean)
from repro.fed import partition as part

PyTree = Any

__all__ = ["lps_round", "gps_aggregate", "gps_aggregate_stacked",
           "masked_cluster_mean"]


def lps_round(cluster_client_params: Sequence[PyTree],
              n_samples: Sequence[int]) -> PyTree:
    """One LPS aggregation: FedAvg over the cluster's clients."""
    return _fedavg(cluster_client_params, n_samples)


def gps_aggregate(lps_params: Sequence[PyTree],
                  cluster_weights: Sequence[float],
                  is_common: part.PathPred) -> list[PyTree]:
    """GPS round: average common layers across LPSs, broadcast back.

    Returns the new per-LPS parameter pytrees (common part replaced by the
    global average, task-specific part untouched).
    """
    splits = [part.split_params(p, is_common) for p in lps_params]
    commons = [c for c, _ in splits]
    specifics = [s for _, s in splits]
    avg_common = _wmean(commons, list(cluster_weights))
    return [part.merge_params(avg_common, s) for s in specifics]


def gps_aggregate_stacked(stack: PyTree, cluster_weights: jax.Array,
                          is_common: part.PathPred,
                          axis: str | None = None) -> PyTree:
    """In-jit GPS round over CLUSTER-STACKED params (leaves ``(T, ...)``).

    The traceable counterpart of ``gps_aggregate`` used by the fused
    MT-HFL trainer: common leaves are replaced by their
    ``cluster_weights``-weighted mean over the leading cluster axis and
    broadcast back; task-specific leaves pass through untouched.  Empty
    clusters carry weight 0 and so are excluded from the average (they
    still RECEIVE the broadcast common part, like any LPS).

    ``axis``: mesh axis to psum over when the cluster axis is sharded
    under ``shard_map`` (same idiom as ``masked_cluster_mean``); ``None``
    for single-host.  If every weight is zero the stack is returned
    unchanged.
    """
    w = jnp.asarray(cluster_weights, jnp.float32)
    total = jnp.sum(w)
    if axis is not None:
        total = jax.lax.psum(total, axis)
    wn = w / jnp.maximum(total, 1e-8)

    def leaf(path, v):
        if not is_common(path):
            return v
        num = jnp.tensordot(wn, v.astype(jnp.float32), axes=1,
                            precision=AVG_PRECISION)
        if axis is not None:
            num = jax.lax.psum(num, axis)
        avg = jnp.broadcast_to(num[None], v.shape)
        return jnp.where(total > 0, avg, v.astype(jnp.float32)).astype(v.dtype)

    return part.tree_path_map(leaf, stack)


def masked_cluster_mean(values: PyTree, onehot: jax.Array,
                        weights: jax.Array, axis: str | None = None) -> PyTree:
    """Batched per-cluster weighted mean (all LPS FedAvgs in one shot).

    ``values``: pytree of arrays with leading user axis ``(U, ...)`` (the
    local shard when used inside shard_map).
    ``onehot (U, T)``: cluster membership; ``weights (U,)``: sample counts.
    ``axis``: mesh axis name to psum over (inside shard_map), or None for
    single-host.

    Returns a pytree with leading cluster axis ``(T, ...)``.
    """
    w = onehot * weights[:, None]                       # (U, T)
    denom = jnp.sum(w, axis=0)                          # (T,)
    if axis is not None:
        denom = jax.lax.psum(denom, axis)
    denom = jnp.maximum(denom, 1e-8)

    def reduce_leaf(v):
        vf = v.astype(jnp.float32)
        num = jnp.einsum("u...,ut->t...", vf, w, precision=AVG_PRECISION)
        if axis is not None:
            num = jax.lax.psum(num, axis)
        out = num / denom.reshape((-1,) + (1,) * (num.ndim - 1))
        return out.astype(v.dtype)

    return jax.tree.map(reduce_leaf, values)
