"""Inputs made on the device from ``--seed``: the benchmark's own copies of
the data generators, so that no later change to the program moves them.

* ``task_mixture``: each task owns a random rank-``rank`` subspace of
  ``R^m``; every user's rows are draws from its task's subspace plus
  isotropic noise (the arithmetic of the program's
  ``data.synthetic.make_task_feature_mixture``, moved onto the device).
* ``signatures``: the ``(lam, V)`` a user uploads, made by the benchmark
  from such rows (random projection, Gram, top-k eigenpairs).
* ``task_layout`` / ``row_counts``: which task each user serves and how
  many of its row slots hold data; every seed draws the same multiset,
  in another order, so seeds change the data and not the work.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def host_rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng((seed, tag))


def task_layout(seed: int, n_users: int, weights: list[float]) -> np.ndarray:
    """``(N,)`` task of each user: counts in proportion to ``weights``
    (largest remainder), shuffled by the seed."""
    w = np.asarray(weights, np.float64) / np.sum(weights)
    counts = np.floor(w * n_users).astype(int)
    rest = np.argsort(-(w * n_users - counts))[:n_users - counts.sum()]
    counts[rest] += 1
    tasks = np.repeat(np.arange(len(w)), counts).astype(np.int32)
    return host_rng(seed, 1).permutation(tasks)


def row_counts(seed: int, n_users: int, rows: int, lo: int | None
               ) -> np.ndarray | None:
    """Valid rows per user, evenly spread over ``[lo, rows]`` and shuffled;
    ``None`` when every user fills all ``rows`` slots."""
    if lo is None or lo >= rows:
        return None
    counts = np.round(np.linspace(lo, rows, n_users)).astype(np.int32)
    return host_rng(seed, 2).permutation(counts)


def valid_rows(c: dict, seed: int) -> int:
    """Valid rows over all users of a one-shot population ``c`` (the
    ``oneshot`` group of a configuration)."""
    counts = row_counts(seed, c["n_users"], c["rows"], c["min_rows"])
    return c["n_users"] * c["rows"] if counts is None else int(counts.sum())


def _bases(k: jax.Array, n_tasks: int, m: int, rank: int) -> jax.Array:
    g = jax.random.normal(k, (n_tasks, m, rank), jnp.float32)
    return jnp.linalg.qr(g)[0]                        # (T, m, rank)


def _block_rows(k, bases, tasks_b, rows, noise):
    """Rows of one block of users: ``z B_t^T + noise * eps``."""
    kz, ke = jax.random.split(k)
    b = tasks_b.shape[0]
    rank, m = bases.shape[2], bases.shape[1]
    z = jax.random.normal(kz, (b, rows, rank), jnp.float32)
    eps = jax.random.normal(ke, (b, rows, m), jnp.float32)
    return jnp.einsum("brk,bmk->brm", z, bases[tasks_b],
                      precision=HIGHEST) + noise * eps


@partial(jax.jit, static_argnames=("n_tasks", "rows", "m", "rank", "block"))
def task_mixture(k, tasks, *, n_tasks: int, rows: int, m: int, rank: int,
                 noise: float, block: int):
    """``(N, rows, m)`` float32 raw rows on the device, in one program.

    ``tasks`` is ``(N,)`` int32 with ``N`` a multiple of ``block``."""
    kb, kr = jax.random.split(k)
    bases = _bases(kb, n_tasks, m, rank)
    n = tasks.shape[0]
    tb = tasks.reshape(n // block, block)
    keys = jax.random.split(kr, n // block)
    out = jax.lax.map(
        lambda a: _block_rows(a[0], bases, a[1], rows, noise), (keys, tb))
    return out.reshape(n, rows, m)


def rp_matrix(k, m: int, d: int) -> jax.Array:
    """A shared random projection ``(m, d)`` scaled by ``1/sqrt(d)``."""
    return jax.random.normal(k, (m, d), jnp.float32) / np.sqrt(d)


@partial(jax.jit, static_argnames=("n_tasks", "rows", "m", "rank", "block",
                                   "top_k"))
def signatures(kb, kr, tasks, n_valid, w, *, n_tasks: int, rows: int,
               m: int, rank: int, noise: float, block: int, top_k: int):
    """The ``(lam (N, k), V (N, d, k))`` uploads of ``N`` users whose rows
    follow ``task_mixture``: projected by ``w``, Gram over each user's
    valid rows, top-``k`` eigenpairs (descending).  ``kb`` draws the task
    subspaces (share it between populations of the same tasks), ``kr``
    the rows.  The raw rows live one block at a time; a last, partial
    block is padded and its padding dropped."""
    bases = _bases(kb, n_tasks, m, rank)
    n = tasks.shape[0]
    pad = -n % block
    tasks = jnp.pad(tasks, (0, pad))
    n_valid = jnp.pad(n_valid, (0, pad), constant_values=rows)
    keys = jax.random.split(kr, (n + pad) // block)

    def one_block(a):
        kk, tb, nv = a
        x = _block_rows(kk, bases, tb, rows, noise)
        live = (jnp.arange(rows)[None, :] < nv[:, None])[..., None]
        f = jnp.einsum("brm,md->brd", jnp.where(live, x, 0.0), w,
                       precision=HIGHEST)
        g = jnp.einsum("brd,bre->bde", f, f,
                       precision=HIGHEST) / nv[:, None, None]
        lam, v = jnp.linalg.eigh(g)
        return lam[:, ::-1][:, :top_k], v[:, :, ::-1][:, :, :top_k]

    lam, v = jax.lax.map(one_block, (keys, tasks.reshape(-1, block),
                                     n_valid.reshape(-1, block)))
    d = w.shape[1]
    return (jnp.maximum(lam, 0.0).reshape(-1, top_k)[:n],
            v.reshape(-1, d, top_k)[:n])


@partial(jax.jit, static_argnames=("m", "rank", "n_classes", "n_tasks"))
def class_structure(k, task_of_class, *, m: int, rank: int, n_classes: int,
                    n_tasks: int, mean_scale: float):
    """Per task an orthonormal ``(m, rank)`` subspace; per class a
    ``(m, rank // 2)`` subspace of its own and a mean inside its task's
    subspace of length ``mean_scale`` (same-task classes share the
    subspace their means and main variation live in)."""
    kt, kc, km = jax.random.split(k, 3)
    tb = jnp.linalg.qr(jax.random.normal(kt, (n_tasks, m, rank)))[0]
    cb = jnp.linalg.qr(jax.random.normal(kc, (n_classes, m, rank // 2)))[0]
    w = jax.random.normal(km, (n_classes, rank))
    w = w / jnp.linalg.norm(w, axis=1, keepdims=True)
    mu = mean_scale * jnp.einsum("cmr,cr->cm", tb[task_of_class], w,
                                 precision=HIGHEST)
    return tb, cb, mu


@jax.jit
def class_images(k, cls, tb_of, cb, mu, task_scale, class_scale, noise):
    """Images ``(C, n, m)`` of classes ``cls (C, n)``, one client at a
    time: class mean + task-subspace and class-subspace variation + pixel
    noise (the arithmetic of the program's
    ``data.synthetic.make_task_dataset``, on the device).  ``tb_of`` is
    each class's task subspace ``(classes, m, rank)``."""

    def client(a):
        kk, c = a
        kt, kc, ke = jax.random.split(kk, 3)
        n, m = c.shape[0], mu.shape[1]
        zt = jax.random.normal(kt, (n, tb_of.shape[2]))
        zc = jax.random.normal(kc, (n, cb.shape[2]))
        eps = jax.random.normal(ke, (n, m))
        return (mu[c]
                + task_scale * jnp.einsum("nmr,nr->nm", tb_of[c], zt,
                                          precision=HIGHEST)
                + class_scale * jnp.einsum("nmr,nr->nm", cb[c], zc,
                                           precision=HIGHEST)
                + noise * eps)

    return jax.lax.map(client, (jax.random.split(k, cls.shape[0]), cls))
