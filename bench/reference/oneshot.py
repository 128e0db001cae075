"""Plain reference of the one-shot protocol (paper Algorithm 2), written
from the paper and independent of the program: the same shared random
projection, each user's Gram over its valid rows, its top-k eigenpairs,
the directed relevance of every pair, the symmetrized ``R`` and an
average-linkage HAC cut.

``dtype="f32"`` is the reference: matrix products at ``HIGHEST``
precision on the device in blocks of users, eigenpairs and HAC in
float64 on the host.  ``dtype="fp8"`` is the control, one precision
below the bfloat16 matrix inputs the configuration states: the same
steps with every matrix product's inputs rounded to float8 (e4m3) and
float32 accumulation.  That includes the products that form the top-k
eigenpairs: the control takes the leading ``k + oversample`` eigenvectors
of its Gram and forms its eigenpairs by a Rayleigh-Ritz step on them
(``Q^T G Q``, then ``Q W``), each product at float8 inputs.  At float32
that step returns the exact eigenpairs, so the reference skips it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def shared_projection(seed: int, m: int, d: int) -> np.ndarray:
    """The public random projection every user applies: standard normal
    ``(m, d)`` from ``default_rng((seed, 11))``, scaled by ``1/sqrt(d)``."""
    rng = np.random.default_rng((seed, 11))
    return (rng.standard_normal((m, d)) / np.sqrt(d)).astype(np.float32)


def _q(a, dtype: str):
    """A matrix product's input as the precision computes it."""
    if dtype == "fp8":
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return a


@partial(jax.jit, static_argnames=("dtype",))
def _grams(x, nv, w, dtype: str):
    """``(B, d, d)`` Grams of a block of users over their valid rows."""
    live = (jnp.arange(x.shape[1])[None, :] < nv[:, None])[..., None]
    x = jnp.where(live, x, 0.0)
    f = jnp.einsum("brm,md->brd", _q(x, dtype), _q(w, dtype),
                   precision=HIGHEST)
    g = jnp.einsum("brd,bre->bde", _q(f, dtype), _q(f, dtype),
                   precision=HIGHEST)
    return g / nv[:, None, None]


@partial(jax.jit, static_argnames=("dtype",))
def _lam_hat(g_blk, v_all, dtype: str):
    """``(B, N, k)``: ``||G_i v_jk||`` for the block's users ``i``."""
    p = jnp.einsum("ide,jek->ijdk", _q(g_blk, dtype), _q(v_all, dtype),
                   precision=HIGHEST)
    return jnp.sqrt(jnp.sum(p * p, axis=2))


@partial(jax.jit, static_argnames=("dtype",))
def _ritz_matrix(g, q, dtype: str):
    """``Q^T G Q`` of a block of users, symmetrized."""
    gq = jnp.einsum("nde,nep->ndp", _q(g, dtype), _q(q, dtype),
                    precision=HIGHEST)
    b = jnp.einsum("ndp,ndq->npq", _q(q, dtype), _q(gq, dtype),
                   precision=HIGHEST)
    return (b + jnp.swapaxes(b, 1, 2)) / 2.0


@partial(jax.jit, static_argnames=("dtype",))
def _ritz_vectors(q, w, dtype: str):
    return jnp.einsum("ndp,npk->ndk", _q(q, dtype), _q(w, dtype),
                      precision=HIGHEST)


def spectrum(grams: np.ndarray, top_k: int, dtype: str = "f32",
             oversample: int = 8, block: int = 256
             ) -> tuple[np.ndarray, np.ndarray]:
    """Top-``k`` eigenpairs ``lam (N, k)``, ``v (N, d, k)``, descending.

    ``"f32"``: ``eigh`` in float64.  Lower precisions: a Rayleigh-Ritz
    step on the leading ``k + oversample`` eigenvectors, its products at
    that precision's inputs."""
    lam, v = np.linalg.eigh(grams)
    lam, v = lam[:, ::-1], v[:, :, ::-1]
    if dtype == "f32":
        return np.maximum(lam[:, :top_k], 0.0), v[:, :, :top_k]
    p = min(top_k + oversample, grams.shape[-1])
    q = jnp.asarray(v[:, :, :p], jnp.float32)
    g = jnp.asarray(grams, jnp.float32)
    b = np.concatenate([
        np.asarray(_ritz_matrix(g[s:s + block], q[s:s + block], dtype))
        for s in range(0, len(grams), block)]).astype(np.float64)
    lam_b, w_b = np.linalg.eigh(b)
    lam_b, w_b = lam_b[:, ::-1][:, :top_k], w_b[:, :, ::-1][:, :, :top_k]
    w_dev = jnp.asarray(w_b, jnp.float32)
    v_out = np.concatenate([
        np.asarray(_ritz_vectors(q[s:s + block], w_dev[s:s + block], dtype))
        for s in range(0, len(grams), block)]).astype(np.float64)
    return np.maximum(lam_b, 0.0), v_out


def relevance(lam_own: np.ndarray, lam_hat: np.ndarray, floor: float
              ) -> np.ndarray:
    """Paper Eqs. 3-4: geometric mean over k of ``min/max`` of the two
    spectra, each floored at ``floor``."""
    a = np.maximum(lam_own, floor)
    b = np.maximum(lam_hat, floor)
    return np.exp(np.mean(np.log(np.minimum(a, b))
                          - np.log(np.maximum(a, b)), axis=-1))


def hac_labels(big_r: np.ndarray, n_clusters: int) -> np.ndarray:
    """Average-linkage agglomeration on similarity ``R`` (distance
    ``1 - R``), cut into ``n_clusters``."""
    from scipy.cluster.hierarchy import fcluster, linkage
    from scipy.spatial.distance import squareform

    dist = 1.0 - np.asarray(big_r, np.float64)
    dist = (dist + dist.T) / 2.0
    np.fill_diagonal(dist, 0.0)
    z = linkage(squareform(np.maximum(dist, 0.0), checks=False),
                method="average")
    return fcluster(z, n_clusters, criterion="maxclust") - 1


def reference(raw, n_valid, w: np.ndarray, top_k: int, n_clusters: int,
              eig_floor: float, dtype: str = "f32", block: int = 64) -> dict:
    """``lam (N, k)``, ``v (N, d, k)``, ``grams (N, d, d)`` (float64),
    ``big_r (N, N)`` and ``labels (N,)`` from raw rows ``(N, n, m)``."""
    n, rows, _ = raw.shape
    nv = (jnp.full((n,), rows, jnp.float32) if n_valid is None
          else jnp.asarray(n_valid, jnp.float32))
    wd = jnp.asarray(w)
    grams = np.concatenate([
        np.asarray(_grams(raw[s:s + block], nv[s:s + block], wd, dtype))
        for s in range(0, n, block)]).astype(np.float64)
    lam, v = spectrum(grams, top_k, dtype)
    g_dev = jnp.asarray(grams, jnp.float32)
    v_dev = jnp.asarray(v, jnp.float32)
    lam_hat = np.concatenate([
        np.asarray(_lam_hat(g_dev[s:s + block], v_dev, dtype))
        for s in range(0, n, block)]).astype(np.float64)
    r = relevance(lam[:, None, :], lam_hat, eig_floor)
    big_r = (r + r.T) / 2.0
    return {"lam": lam, "v": v, "grams": grams, "big_r": big_r,
            "labels": hac_labels(big_r, n_clusters)}


def label_mismatch(a: np.ndarray, b: np.ndarray) -> int:
    """Users outside the best one-to-one matching of two partitions."""
    from scipy.optimize import linear_sum_assignment

    a, b = np.asarray(a), np.asarray(b)
    ua, ia = np.unique(a, return_inverse=True)
    ub, ib = np.unique(b, return_inverse=True)
    table = np.zeros((len(ua), len(ub)), np.int64)
    np.add.at(table, (ia, ib), 1)
    rows, cols = linear_sum_assignment(-table)
    return int(len(a) - table[rows, cols].sum())


def compare(lam, v, big_r, labels, ref: dict) -> dict:
    """The numbers that decide ``correct`` for one one-shot result.

    * ``lam_rel``: worst ``|lam - lam_ref|`` over a user's top eigenvalue;
    * ``v_resid``: worst eigen-residual of a returned eigenvector against
      the reference Gram, ``||G v - (v'Gv) v|| / lam_ref_1`` (a rotation
      inside a near-degenerate eigenspace is no error);
    * ``r_gap``: worst ``|R - R_ref|``;
    * ``r_mean``: mean ``|R - R_ref|`` over every pair;
    * ``label_mismatch``: users outside the best matching of the two
      partitions.
    """
    lam = np.asarray(lam, np.float64)
    v = np.asarray(v, np.float64)
    g = ref["grams"]
    top = np.maximum(ref["lam"][:, :1], 1e-30)
    gv = np.einsum("nde,nek->ndk", g, v)
    rho = np.einsum("ndk,ndk->nk", v, gv) / np.maximum(
        np.einsum("ndk,ndk->nk", v, v), 1e-30)
    resid = np.linalg.norm(gv - v * rho[:, None, :], axis=1) / top
    r_err = np.abs(np.asarray(big_r, np.float64) - ref["big_r"])
    return {
        "lam_rel": float(np.max(np.abs(lam - ref["lam"]) / top)),
        "v_resid": float(np.max(resid)),
        "r_gap": float(np.max(r_err)),
        "r_mean": float(np.mean(r_err)),
        "label_mismatch": float(label_mismatch(labels, ref["labels"])),
    }
