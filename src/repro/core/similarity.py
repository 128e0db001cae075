"""Data-similarity estimation (paper §II-B, Eqs. 1-5).

Each user i holds features ``F_i = Phi(X_i) in R^{n_i x d}``.  The protocol:

  1. ``gram(F_i)``            -> ``G_i = (1/n_i) F_i^T F_i``            (Eq. 1)
  2. ``spectrum(G_i)``        -> top-k eigenpairs ``(lam_i, V_i)``
  3. ``cross_project(G_i, V_j)`` -> ``lamhat_k = ||G_i v_k^{(j)}||``    (Eq. 2)
  4. ``relevance(lam_i, lamhat)`` -> ``r(i,j)`` geometric-mean ratio    (Eqs. 3-4)
  5. ``symmetrize(r)``        -> ``R(i,j) = (r(i,j)+r(j,i))/2``         (Eq. 5)

Everything is jit-able and batched over users where noted.  The Gram matrix
and the cross-projection are the compute hot spots; ``repro.kernels.gram``
and ``repro.kernels.eigproject`` provide Pallas TPU kernels for them, and
these functions accept an ``impl`` switch (``"jnp"`` default, ``"pallas"``
on TPU / interpret mode).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SimilarityConfig",
    "pad_ragged",
    "prepare_user_batch",
    "gram",
    "spectrum",
    "user_signature",
    "cross_project",
    "relevance",
    "relevance_matrix",
    "signature_relevance",
    "symmetrize",
    "similarity_matrix",
    "perturb_eigenvectors",
    "subsample_rows",
]

EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class SimilarityConfig:
    """Configuration of the one-shot similarity protocol.

    Attributes:
      top_k: number of eigenvectors each user shares (paper Fig. 4: 5 suffice;
        we default to 8 for margin).  ``0`` means "all d".
      eig_floor: eigenvalues below this are clamped before the min/max ratio
        (paper §III: tiny eigenvalues drift the geometric mean).
      impl: kernel implementation inside the protocol, "jnp" reference maths
        or "pallas" TPU kernels.
      backend: which ``ProtocolEngine`` backend runs the protocol —
        "jnp" (single host), "pallas" (single host, forces ``impl="pallas"``)
        or "shard_map" (users sharded over a mesh axis, paper star topology
        mapped onto collectives).
      block_users: ``0`` runs the dense path (full ``(N, d, d)`` Gram stack
        in one jit).  ``> 0`` enables blockwise streaming: users are
        processed in tiles of this size, Grams live only per tile, and
        cross-projection is Gram-free — peak memory O(block_users * d^2).
        Single-host backends only.
      landmarks: ``0`` scores every user pair (O(N^2) relevance entries).
        ``> 0`` enables the Nystrom-SKETCHED flat path: all N users are
        scored against ``landmarks`` landmark signatures only (the
        ``kernels/assign`` projector-affinity scorer) and R is completed
        from the m x m landmark block — O(N * m) scored entries instead of
        O(N^2).  Mutually exclusive with ``block_users`` (the sketched
        path never materializes the N x N cross-projection the streaming
        tiles exist to bound; combining them has no meaning and is
        rejected).  Single-host backends only; must be < N at run time.
      mesh_axis: mesh axis users are sharded over (shard_map backend).
    """

    top_k: int = 8
    eig_floor: float = 1e-6
    impl: str = "jnp"
    backend: str = "jnp"
    block_users: int = 0
    landmarks: int = 0
    mesh_axis: str = "data"

    def __post_init__(self):
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = all d eigenpairs), "
                             f"got {self.top_k}")
        if self.eig_floor <= 0:
            raise ValueError(f"eig_floor must be positive (it clamps the "
                             f"min/max ratio), got {self.eig_floor}")
        if self.impl not in ("jnp", "pallas"):
            raise ValueError(f"impl must be 'jnp' or 'pallas', "
                             f"got {self.impl!r}")
        if self.block_users < 0:
            raise ValueError(f"block_users must be >= 0, "
                             f"got {self.block_users}")
        if self.landmarks < 0:
            raise ValueError(f"landmarks must be >= 0 (0 = exact, no "
                             f"sketch), got {self.landmarks}")
        if self.landmarks and self.block_users:
            raise ValueError(
                "landmarks and block_users are mutually exclusive: the "
                "sketched path scores O(N * m) entries and never builds "
                "the N x N matrix blockwise streaming tiles — pick one")


def pad_ragged(features: Sequence[np.ndarray], device: bool = True
               ) -> tuple[jax.Array, jax.Array]:
    """Zero-pad a ragged list of per-user ``(n_i, d)`` feature matrices.

    Returns ``(padded (N, n_max, d) float32, n_valid (N,) float32)`` — the
    single conversion point used by ``similarity_matrix``,
    ``one_shot_clustering``, the ``ProtocolEngine`` and the
    ``SignatureEngine``.  ``device=False`` keeps the padded stack as host
    numpy (the raw-ingest streaming path device-puts one row-chunk at a
    time instead of the whole stack).
    """
    counts = [f.shape[0] for f in features]
    n_max = max(counts)
    d = features[0].shape[1]
    padded = np.zeros((len(features), n_max, d), dtype=np.float32)
    for i, f in enumerate(features):
        padded[i, : f.shape[0]] = f
    counts = np.asarray(counts, dtype=np.float32)
    if device:
        return jnp.asarray(padded), jnp.asarray(counts)
    return padded, counts


def prepare_user_batch(data, n_valid=None, device: bool = True):
    """Normalize either accepted user-batch form to ``(padded, n_valid)``.

    Ragged lists of per-user ``(n_i, d)`` arrays are zero-padded via
    ``pad_ragged``; stacked ``(N, n, d)`` arrays pass through (host numpy
    when ``device=False`` — the streaming ingest path — device arrays
    otherwise) with full-length counts unless the true ones are supplied.
    The single input-normalization point shared by ``ProtocolEngine`` and
    ``SignatureEngine``.
    """
    if not isinstance(data, (jax.Array, np.ndarray)):
        if n_valid is not None:
            raise ValueError("n_valid is derived from ragged input; "
                             "pass one or the other")
        padded, counts = pad_ragged(data, device=device)
        return padded, jnp.asarray(counts)
    if data.ndim != 3:
        raise ValueError(f"user batch must be (N, n, m)-shaped "
                         f"(users, rows, dim), got shape {data.shape}")
    if device:
        data = jnp.asarray(data)
    if n_valid is None:
        n_valid = jnp.full((data.shape[0],), data.shape[1], jnp.float32)
    return data, jnp.asarray(n_valid, jnp.float32)


# ---------------------------------------------------------------------------
# Step 1: Gram matrix (Eq. 1)
# ---------------------------------------------------------------------------

def gram(features: jax.Array, *, n_valid: jax.Array | int | None = None,
         impl: str = "jnp") -> jax.Array:
    """``(1/n) F^T F`` for one user's feature matrix ``F (n, d)``.

    ``n_valid`` supports ragged per-user sample counts under a padded batch:
    rows ``>= n_valid`` must already be zero, and the normalisation uses
    ``n_valid`` instead of the padded length.
    """
    n = features.shape[0] if n_valid is None else n_valid
    n = jnp.maximum(jnp.asarray(n, features.dtype), 1.0)
    if impl == "pallas":
        from repro.kernels.gram import ops as gram_ops

        g = gram_ops.gram_matrix(features)
    else:
        g = features.T @ features
    return g / n


def batched_gram(features: jax.Array, n_valid: jax.Array | None = None,
                 *, impl: str = "jnp") -> jax.Array:
    """Vectorised Gram over a user axis: ``features (N, n, d) -> (N, d, d)``."""
    if n_valid is None:
        n_valid = jnp.full((features.shape[0],), features.shape[1],
                           dtype=features.dtype)
    return jax.vmap(lambda f, nv: gram(f, n_valid=nv, impl=impl))(
        features, n_valid)


# ---------------------------------------------------------------------------
# Step 2: eigen-decomposition -> user signature
# ---------------------------------------------------------------------------

def spectrum(g: jax.Array, top_k: int = 0) -> tuple[jax.Array, jax.Array]:
    """Eigen-decomposition of a PSD Gram matrix, descending order.

    Returns ``(lam (k,), V (d, k))`` with ``k = top_k or d``.  ``jnp.linalg
    .eigh`` returns ascending order, so we flip.  The Gram matrix is PSD by
    construction; numerical negatives are clamped at 0.
    """
    lam, v = jnp.linalg.eigh(g)
    lam = jnp.maximum(lam[::-1], 0.0)
    v = v[:, ::-1]
    if top_k and top_k < lam.shape[0]:
        lam = lam[:top_k]
        v = v[:, :top_k]
    return lam, v


def user_signature(features: jax.Array, cfg: SimilarityConfig,
                   *, n_valid: jax.Array | int | None = None
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One user's public signature: ``(lam (k,), V (d,k), G (d,d))``.

    ``lam`` and ``V`` are what the user shares; ``G`` stays private and is
    used locally for cross-projection.
    """
    g = gram(features, n_valid=n_valid, impl=cfg.impl)
    lam, v = spectrum(g, cfg.top_k)
    return lam, v, g


# ---------------------------------------------------------------------------
# Step 3: cross-projection (Eq. 2)
# ---------------------------------------------------------------------------

def cross_project(g_own: jax.Array, v_other: jax.Array) -> jax.Array:
    """``lamhat_k = || G_i v_k^{(j)} ||_2`` for each eigenvector column.

    ``g_own (d, d)``, ``v_other (d, k)`` -> ``(k,)``.  The Pallas form
    projects a whole Gram stack at once (``relevance_matrix``).
    """
    proj = g_own @ v_other                      # (d, k)
    return jnp.sqrt(jnp.sum(proj * proj, axis=0))


# ---------------------------------------------------------------------------
# Step 4: relevance (Eqs. 3-4)
# ---------------------------------------------------------------------------

def relevance(lam_own: jax.Array, lam_hat: jax.Array,
              eig_floor: float = 1e-6) -> jax.Array:
    """Geometric mean of the min/max eigenvalue ratios.

    Both spectra are floored at ``eig_floor`` first (paper §III
    "Communication Improvement": a single tiny eigenvalue otherwise drives
    the product to ~0 regardless of the rest).  Computed in log space for
    stability: ``exp(mean_k log(min/max))``.
    """
    a = jnp.maximum(lam_own, eig_floor)
    b = jnp.maximum(lam_hat, eig_floor)
    lo = jnp.minimum(a, b)
    hi = jnp.maximum(a, b)
    return jnp.exp(jnp.mean(jnp.log(lo) - jnp.log(hi)))


def relevance_matrix(grams: jax.Array, lams: jax.Array, vs: jax.Array,
                     eig_floor: float = 1e-6, *, impl: str = "jnp"
                     ) -> jax.Array:
    """All-pairs directed relevance ``r (N, N)``.

    ``grams (N, d, d)``: each user's private Gram.
    ``lams (N, k)``, ``vs (N, d, k)``: the shared signatures.
    ``r[i, j]`` is user *i*'s estimate of its relevance to user *j*
    (projects j's eigenvectors through i's Gram, compares against i's own
    spectrum — paper Algorithm 2 lines 7-12).

    ``impl="pallas"`` projects every Gram against the whole ``(d, N * k)``
    signature table in one ``kernels.eigproject`` call; ``grams`` may hold
    B != N rows (a device's local users against the gathered table), and
    ``r`` is then ``(B, N)``.
    """
    if impl == "pallas":
        from repro.kernels.eigproject import ops as proj_ops

        n, d, k = vs.shape
        v_table = vs.transpose(1, 0, 2).reshape(d, n * k)
        lam_hat = proj_ops.project_norms_table(grams, v_table)
        lam_hat = lam_hat.reshape(grams.shape[0], n, k)
        return jax.vmap(lambda lam_i, lh_i: jax.vmap(
            lambda lh: relevance(lam_i, lh, eig_floor))(lh_i))(lams, lam_hat)

    def row(g_i, lam_i):
        def one(v_j):
            lam_hat = cross_project(g_i, v_j)
            return relevance(lam_i, lam_hat, eig_floor)

        return jax.vmap(one)(vs)

    return jax.vmap(row)(grams, lams)


@partial(jax.jit, static_argnames=("eig_floor",))
def signature_relevance(lam, v, eig_floor: float = 1e-6):
    """Symmetrized relevance ``R (N, N)`` from SHARED signatures only.

    Rank-k Gram reconstruction: ``G_i v ~ V_i diag(lam_i) (V_i^T v)``, so
    ``lamhat(i, j) = ||diag(lam_i) (V_i^T V_j)||`` column-wise — O(k^2 d)
    per pair instead of O(k d^2), and computable by the GPS without any
    private Gram.  Row-mapped so peak memory stays O(N k^2).

    Shared by the ``MembershipEngine`` drift re-cluster and the
    ``core.hierarchy`` global stage (clustering the per-group directory
    entries): both decide over compressed signatures the GPS already
    holds, with no extra protocol round.
    """

    def row(args):
        lam_i, v_i = args
        c = jnp.einsum("dk,ndl->nkl", v_i, v)            # (N, k, k)
        lam_hat = jnp.sqrt(jnp.sum((lam_i[None, :, None] * c) ** 2,
                                   axis=1))              # (N, k)
        return jax.vmap(lambda lh: relevance(lam_i, lh, eig_floor)
                        )(lam_hat)

    r = jax.lax.map(row, (lam, v))
    return symmetrize(r)


# ---------------------------------------------------------------------------
# Beyond-paper: privacy noise + subsampled Gram (paper §IV future work)
# ---------------------------------------------------------------------------

def perturb_eigenvectors(v: jax.Array, sigma: float, rng: jax.Array,
                         renormalize: bool = True) -> jax.Array:
    """Additive Gaussian noise on the SHARED eigenvectors (the only thing
    that leaves a user) — the extra privacy layer the paper's §IV names as
    future work.  ``v (d, k)`` or ``(N, d, k)``; columns are re-normalized
    so the projection magnitudes stay comparable.

    Robustness is benchmarked in ``benchmarks/bench_robustness.py``:
    clustering survives sigma up to ~0.1 (columns are unit-norm).
    """
    noise = sigma * jax.random.normal(rng, v.shape, dtype=jnp.float32)
    out = v.astype(jnp.float32) + noise
    if renormalize:
        norms = jnp.linalg.norm(out, axis=-2, keepdims=True)
        out = out / jnp.maximum(norms, EPS)
    return out.astype(v.dtype)


def subsample_rows(features: np.ndarray, max_rows: int,
                   seed: int = 0) -> np.ndarray:
    """Nystrom-style row subsampling: the Gram estimate from ``max_rows``
    uniformly-sampled rows is an unbiased second-moment estimator, cutting
    the Eq.-1 cost from O(n d^2) to O(max_rows d^2) for n >> d regimes."""
    n = features.shape[0]
    if n <= max_rows:
        return features
    idx = np.random.default_rng(seed).choice(n, max_rows, replace=False)
    return features[idx]


# ---------------------------------------------------------------------------
# Step 5: symmetrization (Eq. 5)
# ---------------------------------------------------------------------------

def symmetrize(r: jax.Array) -> jax.Array:
    """``R = (r + r^T) / 2`` — the GPS-side average of the two directed views."""
    return (r + r.T) / 2.0


# ---------------------------------------------------------------------------
# End-to-end (any backend)
# ---------------------------------------------------------------------------

def similarity_matrix(features: jax.Array | Sequence[np.ndarray],
                      cfg: SimilarityConfig | None = None,
                      n_valid: jax.Array | None = None) -> jax.Array:
    """Full protocol on a padded user batch ``features (N, n, d)`` -> ``R (N, N)``.

    Accepts a list of per-user ``(n_i, d)`` arrays (ragged); they are
    zero-padded to the max ``n_i`` and the true counts are passed through.
    Thin wrapper over ``repro.core.engine.ProtocolEngine`` — the backend
    (dense / blockwise / shard_map) is chosen by ``cfg``.
    """
    from repro.core.engine import ProtocolEngine

    return ProtocolEngine(cfg).similarity(features, n_valid=n_valid)
