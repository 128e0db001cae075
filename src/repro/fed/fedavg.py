"""FedAvg aggregation primitives (McMahan et al., AISTATS'17)."""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

PyTree = Any

__all__ = ["weighted_mean", "fedavg", "AVG_PRECISION"]

#: Precision of every parameter-average contraction (FedAvg, the GPS
#: average).  At default precision a TPU rounds the operands of an f32 dot
#: to bf16, which would round every averaged parameter to 8 mantissa bits.
AVG_PRECISION = jax.lax.Precision.HIGHEST


def weighted_mean(trees: Sequence[PyTree], weights: Sequence[float]) -> PyTree:
    """Sample-count-weighted average of parameter pytrees."""
    w = jnp.asarray(weights, dtype=jnp.float32)
    w = w / jnp.sum(w)

    def avg(*leaves):
        acc = sum(wi * l.astype(jnp.float32) for wi, l in zip(w, leaves))
        return acc.astype(leaves[0].dtype)

    return jax.tree.map(avg, *trees)


def fedavg(client_params: Sequence[PyTree],
           n_samples: Sequence[int]) -> PyTree:
    """Standard FedAvg: average client models weighted by local sample count."""
    return weighted_mean(client_params, [float(n) for n in n_samples])
