"""Client-side local update (the inner loop of FedAvg).

``local_update`` runs ``steps`` optimizer steps over pre-batched data with
``jax.lax.scan`` so one client round is a single jit-compiled call.
``fused_lps_round`` vmaps that scan over a stacked client axis and folds
the FedAvg aggregation in, so one jit call performs a cluster's ENTIRE
local round.  ``masked_lps_round`` is the fully traceable variant the
fused MT-HFL trainer vmaps over a padded cluster axis: batch sampling
happens in-jit from per-client fold_in keys and the FedAvg is weighted by
a membership mask, so ragged and empty clusters need no Python branches.

Batch sampling is keyed, not stateful: ``sample_batch_indices`` derives
every mini-batch from ``(round_key, user_id)``, so the reference loop and
the fused trainer draw bit-identical batches regardless of cluster
iteration order (the parity contract of ``tests/test_trainer_parity.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro import optim
from repro.fed.fedavg import AVG_PRECISION

PyTree = Any
LossFn = Callable[[PyTree, dict], jax.Array]

__all__ = ["ClientConfig", "local_update", "fused_lps_round",
           "masked_lps_round", "sample_batch_indices",
           "participation_mask",
           "make_keyed_batch_stack", "make_batches", "make_batch_stack"]

# fold_in tag separating the participation stream from the batch stream
# (both derive from the same per-cluster round key)
_PARTICIPATION_FOLD = 7451


@dataclasses.dataclass(frozen=True)
class ClientConfig:
    lr: float = 0.05
    optimizer: str = "sgd"          # sgd | momentum | adamw
    clip_norm: float = 0.0          # 0 disables
    weight_decay: float = 0.0


@functools.lru_cache(maxsize=None)
def _make_opt(cfg: ClientConfig) -> optim.Optimizer:
    # Cached so repeated rounds with the same ClientConfig reuse ONE
    # Optimizer object: the jits below take it as a static argument, and a
    # fresh (init, update) closure pair per call would be a cache miss —
    # i.e. a recompile on every round.
    if cfg.optimizer == "sgd":
        return optim.sgd(cfg.lr)
    if cfg.optimizer == "momentum":
        return optim.momentum(cfg.lr)
    if cfg.optimizer == "adamw":
        return optim.adamw(cfg.lr, weight_decay=cfg.weight_decay)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")


def _scan_steps(params: PyTree, batches: dict, loss_fn: LossFn,
                optimizer: optim.Optimizer, clip_norm: float
                ) -> tuple[PyTree, jax.Array]:
    """``steps`` optimizer steps via lax.scan (one client, traceable)."""
    opt_state = optimizer.init(params)

    def step(carry, batch):
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        if clip_norm:
            grads = optim.clip_by_global_norm(grads, clip_norm)
        updates, s = optimizer.update(grads, s, p)
        p = optim.apply_updates(p, updates)
        return (p, s), loss

    (params, _), losses = jax.lax.scan(step, (params, opt_state), batches)
    return params, losses


_run = jax.jit(_scan_steps,
               static_argnames=("loss_fn", "optimizer", "clip_norm"))


@partial(jax.jit, static_argnames=("loss_fn", "optimizer", "clip_norm"))
def _run_lps(params: PyTree, batches: dict, weights: jax.Array,
             loss_fn: LossFn, optimizer: optim.Optimizer,
             clip_norm: float) -> tuple[PyTree, jax.Array]:
    new_params, losses = jax.vmap(
        lambda b: _scan_steps(params, b, loss_fn, optimizer, clip_norm)
    )(batches)
    w = weights.astype(jnp.float32)
    w = w / jnp.sum(w)
    avg = jax.tree.map(
        lambda x: jnp.tensordot(w, x.astype(jnp.float32), axes=1,
                                precision=AVG_PRECISION).astype(x.dtype),
        new_params)
    return avg, losses


def local_update(params: PyTree, batches: dict, loss_fn: LossFn,
                 cfg: ClientConfig) -> tuple[PyTree, jax.Array]:
    """Run one client's local round.

    ``batches``: pytree of arrays with a leading ``steps`` axis (stacked
    mini-batches).  Returns (new_params, per-step losses).
    """
    return _run(params, batches, loss_fn, _make_opt(cfg), cfg.clip_norm)


def fused_lps_round(params: PyTree, batches: dict, weights: jax.Array,
                    loss_fn: LossFn, cfg: ClientConfig
                    ) -> tuple[PyTree, jax.Array]:
    """One LPS round — every client's local scan AND the FedAvg — in one jit.

    ``batches``: pytree with leading ``(clients, steps, batch, ...)`` axes
    (from ``make_batch_stack``); every client starts from the same
    ``params`` (the LPS broadcast) and the sample-count-``weights``ed
    average comes back, plus per-client per-step ``losses``.
    """
    return _run_lps(params, batches, jnp.asarray(weights), loss_fn,
                    _make_opt(cfg), cfg.clip_norm)


def sample_batch_indices(key: jax.Array, steps: int, batch_size: int,
                         n: jax.Array | int) -> jax.Array:
    """``(steps, batch)`` uniform-with-replacement indices in ``[0, n)``.

    Traceable in ``n`` (padded batches carry per-client sample counts), so
    the same draw works host-side in the reference loop and in-jit under
    the fused trainer's vmap — the two paths see identical batches.
    """
    r = jax.random.randint(key, (steps, batch_size), 0, jnp.int32(2**31 - 1),
                           dtype=jnp.int32)
    return r % jnp.maximum(jnp.asarray(n, jnp.int32), 1)


def participation_mask(round_key: jax.Array, uids, rate) -> jax.Array:
    """Per-round straggler/dropout mask: client ``uid`` participates iff
    its keyed uniform draw clears ``rate`` (the expected dropout
    fraction).  Keyed off ``(round_key, uid)`` through a dedicated
    fold-in tag, so the draw is independent of the batch stream,
    invariant to cluster numbering, and IDENTICAL whether evaluated
    host-side (reference loop) or in-jit under the fused trainer's vmap
    — the same contract as ``sample_batch_indices``.  ``rate`` may be a
    traced scalar: ``rate == 0.0`` reproduces full participation
    exactly (uniform draws live in [0, 1)), so threading it through the
    fused super-stack costs no retrace.

    Returns a float32 ``(C,)`` mask, 1.0 = participating.
    """
    pk = jax.random.fold_in(round_key, _PARTICIPATION_FOLD)
    uids = jnp.asarray(uids, jnp.int32)
    draws = jax.vmap(
        lambda u: jax.random.uniform(jax.random.fold_in(pk, u)))(uids)
    return (draws >= rate).astype(jnp.float32)


def make_keyed_batch_stack(datasets: Sequence[tuple], uids: Sequence[int],
                           round_key: jax.Array, batch_size: int,
                           steps: int) -> dict:
    """Key-derived batches for a whole cluster -> ``(clients, steps, batch)``.

    The per-client key is ``fold_in(round_key, user_id)`` — exactly the
    derivation ``masked_lps_round`` performs in-jit, so the reference loop
    trains on the same samples as the fused trainer.
    """
    import numpy as np

    xs, ys = [], []
    for (x, y), uid in zip(datasets, uids):
        ck = jax.random.fold_in(round_key, int(uid))
        idx = np.asarray(sample_batch_indices(ck, steps, batch_size, len(y)))
        xs.append(np.asarray(x)[idx])
        ys.append(np.asarray(y)[idx])
    return {"x": jnp.asarray(np.stack(xs)), "y": jnp.asarray(np.stack(ys))}


def masked_lps_round(params: PyTree, x: jax.Array, y: jax.Array,
                     n_per: jax.Array, uids: jax.Array, mask: jax.Array,
                     round_key: jax.Array, loss_fn: LossFn,
                     optimizer: optim.Optimizer, clip_norm: float,
                     steps: int, batch_size: int
                     ) -> tuple[PyTree, jax.Array]:
    """One cluster's LPS round over PADDED client slots — fully traceable.

    ``x (C_max, n_max, ...)`` / ``y (C_max, n_max)``: zero-padded client
    data; ``n_per (C_max,)`` true sample counts (>= 1 even on padding
    slots, they are weighted out); ``uids (C_max,)`` user ids keying the
    batch streams; ``mask (C_max,)`` 1.0 on real clients.  Batches are
    sampled in-jit from ``fold_in(round_key, uid)``, every slot runs the
    ``lax.scan`` local update, and the FedAvg weights are ``n_per * mask``
    so padding slots contribute exactly zero.  An all-masked (empty)
    cluster returns ``params`` unchanged and a NaN loss.

    Designed to be ``vmap``-ed over a leading cluster axis by the fused
    MT-HFL trainer; see ``repro.fed.trainer``.
    """

    def one_client(x_c, y_c, n_c, uid):
        ck = jax.random.fold_in(round_key, uid)
        idx = sample_batch_indices(ck, steps, batch_size, n_c)
        batches = {"x": x_c[idx], "y": y_c[idx]}
        return _scan_steps(params, batches, loss_fn, optimizer, clip_norm)

    new_params, losses = jax.vmap(one_client)(x, y, n_per, uids)

    w = n_per.astype(jnp.float32) * mask.astype(jnp.float32)
    total = jnp.sum(w)
    nonempty = total > 0
    wn = w / jnp.maximum(total, 1e-8)

    def fedavg_leaf(l, p0):
        # Padding slots trained on zero data; where() them out BEFORE the
        # contraction so a non-finite padded result cannot poison the
        # average (NaN * 0 == NaN).
        m = mask.reshape((-1,) + (1,) * (l.ndim - 1))
        lf = jnp.where(m > 0, l.astype(jnp.float32), 0.0)
        avg = jnp.tensordot(wn, lf, axes=1, precision=AVG_PRECISION)
        return jnp.where(nonempty, avg,
                         p0.astype(jnp.float32)).astype(p0.dtype)

    avg = jax.tree.map(fedavg_leaf, new_params, params)
    loss_sum = jnp.sum(jnp.where(mask[:, None] > 0, losses, 0.0))
    loss_cnt = jnp.sum(mask) * losses.shape[1]
    mean_loss = jnp.where(nonempty, loss_sum / jnp.maximum(loss_cnt, 1.0),
                          jnp.nan)
    return avg, mean_loss


def make_batches(x, y, batch_size: int, steps: int, rng) -> dict:
    """Stack ``steps`` random mini-batches from (x, y) -> scan-ready pytree."""
    import numpy as np

    n = len(y)
    idx = rng.integers(0, n, size=(steps, min(batch_size, n)))
    return {"x": jnp.asarray(x[idx]), "y": jnp.asarray(y[idx])}


def make_batch_stack(datasets: Sequence[tuple], batch_size: int,
                     steps: int, rng) -> dict:
    """Batches for a whole cluster -> ``(clients, steps, batch)`` pytree.

    ``datasets``: per-client ``(x, y)`` pairs.  Sampling is uniform WITH
    replacement so every client yields the same batch shape even when some
    hold fewer than ``batch_size`` samples (ragged clusters stay stackable).
    """
    import numpy as np

    xs, ys = [], []
    for x, y in datasets:
        idx = rng.integers(0, len(y), size=(steps, batch_size))
        xs.append(x[idx])
        ys.append(y[idx])
    return {"x": jnp.asarray(np.stack(xs)), "y": jnp.asarray(np.stack(ys))}
