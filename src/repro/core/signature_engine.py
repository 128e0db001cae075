"""Device-resident signature ingest: raw data -> Gram -> top-k spectrum.

PRs 1-3 made the protocol, trainer and HAC cut device-resident, but the
pipeline still *started* on the host: per-user numpy ``feature_map``, a
materialized ``(N, n, d)`` feature stack, and a full ``jnp.linalg.eigh``
(O(d^3) per user) for signatures that only need ``top_k ~ 8`` eigenpairs.
The ``SignatureEngine`` moves the whole ingest onto the device:

  * **Fused featurize -> Gram.**  All four Phi maps
    (``repro.data.features``) run as jit-able jnp, vmapped over users.
  * **Row-chunk streaming.**  ``chunk_rows > 0`` accumulates
    ``G_i += Phi(X_chunk)^T Phi(X_chunk)`` online, so the peak working
    set is O(N * chunk * m) raw rows + the O(N * d'^2) Gram stack — the
    ``(N, n, d')`` feature stack never exists, making peak memory
    independent of n.  The ``pallas`` backend fuses project + accumulate
    into one ``kernels/featurize_gram`` pass (bf16 compute / fp32
    accumulate via ``compute_dtype="bf16"``).
  * **Batched top-k subspace iteration.**  ``topk_spectrum`` replaces the
    full ``eigh`` with orthogonal iteration + Rayleigh-Ritz on the PSD
    Gram stack: O(d^2 (k+oversample) iters) per user instead of O(d^3),
    batched over users as pure matmul/QR work.  ``eig="eigh"`` is the
    exact fallback switch, and ``subspace_residual`` detects
    non-convergence via the relative eigen-residual norm.

Backend selection mirrors the ``ProtocolEngine``/``ClusterEngine`` idiom:
``SignatureConfig.backend`` is ``"jnp"`` (reference jnp maths),
``"pallas"`` (fused kernel chunks) or ``"shard_map"`` (the user axis is
sharded — the engine's chunk step is reused inside
``ProtocolEngine.run_raw``'s sharded body, which owns the collectives).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import similarity as sim
from repro.data import features as feat

__all__ = ["SignatureConfig", "SignatureEngine", "SIGNATURE_BACKENDS",
           "EIG_METHODS", "topk_spectrum", "subspace_residual"]

SIGNATURE_BACKENDS = ("jnp", "pallas", "shard_map")
EIG_METHODS = ("subspace", "eigh")
_COMPUTE_DTYPES = ("fp32", "bf16")


@dataclasses.dataclass(frozen=True)
class SignatureConfig:
    """How raw user shards become ``(lam, V, G)`` signatures.

    Attributes:
      backend: ``"jnp"`` | ``"pallas"`` | ``"shard_map"`` — same idiom as
        ``SimilarityConfig.backend``.  ``pallas`` runs the fused
        ``kernels/featurize_gram`` project+accumulate kernel per chunk;
        ``shard_map`` marks the config for the sharded raw protocol
        (``ProtocolEngine.run_raw`` owns the mesh and collectives).
      chunk_rows: ``0`` ingests each user's rows in one pass; ``> 0``
        streams row-chunks of this size with online Gram accumulation —
        peak working set independent of n.
      eig: ``"subspace"`` (batched top-k orthogonal iteration,
        O(d^2 k iters)) or ``"eigh"`` (exact full decomposition, O(d^3)).
      subspace_iters: orthogonal-iteration G-applications, QR-ed every
        second one (error contracts like (lam_{p+1}/lam_k)^iters; Ritz
        values converge at the square).
      oversample: extra iterated columns beyond ``top_k`` — sharpens
        convergence on tight spectra for the cost of O(d * oversample).
      check: verify subspace convergence on every ingest —
        ``signatures()`` AND the ``ProtocolEngine.run_raw`` paths
        (including shard_map) raise ``RuntimeError`` when the relative
        eigen-residual exceeds ``resid_tol``.
      resid_tol: max relative eigen-residual the convergence check
        accepts before declaring non-convergence.
      compute_dtype: ``"fp32"`` exact path, or ``"bf16"`` matmul inputs
        with fp32 accumulation (kernel and jnp paths alike).
      mesh_axis: mesh axis users are sharded over (shard_map backend).
    """

    backend: str = "jnp"
    chunk_rows: int = 0
    eig: str = "subspace"
    subspace_iters: int = 20
    oversample: int = 8
    check: bool = False
    resid_tol: float = 1e-3
    compute_dtype: str = "fp32"
    mesh_axis: str = "data"

    def __post_init__(self):
        if self.backend not in SIGNATURE_BACKENDS:
            raise ValueError(f"backend must be one of {SIGNATURE_BACKENDS}, "
                             f"got {self.backend!r}")
        if self.chunk_rows < 0:
            raise ValueError(f"chunk_rows must be >= 0, "
                             f"got {self.chunk_rows}")
        if self.eig not in EIG_METHODS:
            raise ValueError(f"eig must be one of {EIG_METHODS}, "
                             f"got {self.eig!r}")
        if self.subspace_iters < 0:
            raise ValueError(f"subspace_iters must be >= 0, "
                             f"got {self.subspace_iters}")
        if self.oversample < 0:
            raise ValueError(f"oversample must be >= 0, "
                             f"got {self.oversample}")
        if self.resid_tol <= 0:
            raise ValueError(f"resid_tol must be positive, "
                             f"got {self.resid_tol}")
        if self.compute_dtype not in _COMPUTE_DTYPES:
            raise ValueError(f"compute_dtype must be one of "
                             f"{_COMPUTE_DTYPES}, got {self.compute_dtype!r}")


# ---------------------------------------------------------------------------
# Batched top-k spectrum: subspace (orthogonal) iteration vs eigh
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k",))
def _eigh_topk(grams: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Exact fallback: the SAME ``sim.spectrum`` primitive the
    pre-featurized engine uses, vmapped over the stack."""
    return jax.vmap(lambda g: sim.spectrum(g, k))(grams)


@partial(jax.jit, static_argnames=("k", "p", "iters", "seed"))
def _subspace_topk(grams: jax.Array, k: int, p: int, iters: int, seed: int
                   ) -> tuple[jax.Array, jax.Array]:
    n, d, _ = grams.shape
    q0 = jax.random.normal(jax.random.PRNGKey(seed), (d, p), jnp.float32)
    q0, _ = jnp.linalg.qr(q0)
    q = jnp.broadcast_to(q0, (n, d, p))

    # ``iters`` counts G-applications; re-orthogonalize every SECOND one
    # (G is PSD: two multiplies between QRs square the per-step column
    # growth, which fp32 absorbs easily, while halving the batched-QR
    # cost — the dominant non-matmul term on CPU).
    def body(_, q):
        z = grams @ (grams @ q)                     # (N, d, p) batched
        q, _ = jnp.linalg.qr(z)
        return q

    q = jax.lax.fori_loop(0, iters // 2, body, q)
    if iters % 2:
        q, _ = jnp.linalg.qr(grams @ q)
    # Rayleigh-Ritz on the iterated subspace: the (p, p) projected problem
    # costs O(p^3) << O(d^3) and upgrades eigenvalue accuracy to the
    # square of the subspace angle.
    gq = grams @ q
    b = jnp.einsum("ndp,ndq->npq", q, gq)
    b = (b + jnp.swapaxes(b, -1, -2)) / 2.0
    lam_b, w_b = jnp.linalg.eigh(b)                 # ascending
    lam = jnp.maximum(lam_b[..., ::-1], 0.0)[..., :k]
    v = (q @ w_b[..., ::-1])[..., :k]
    return lam, v


def topk_spectrum(grams: jax.Array, top_k: int, *, method: str = "subspace",
                  iters: int = 20, oversample: int = 8, seed: int = 0
                  ) -> tuple[jax.Array, jax.Array]:
    """Top-k eigenpairs of a PSD Gram stack ``(N, d, d)``, descending.

    Returns ``(lam (N, k), V (N, d, k))``.  ``method="subspace"`` runs
    batched orthogonal iteration on ``k + oversample`` columns and falls
    through to the exact ``eigh`` whenever the iterated subspace would
    cover (nearly) the whole space anyway — including ``top_k = d``.
    """
    if method not in EIG_METHODS:
        raise ValueError(f"method must be one of {EIG_METHODS}, "
                         f"got {method!r}")
    d = grams.shape[-1]
    k = min(top_k or d, d)
    p = min(k + oversample, d)
    if method == "eigh" or p >= d:
        return _eigh_topk(grams, k)
    return _subspace_topk(grams, k, p, iters, seed)


@jax.jit
def subspace_residual(grams: jax.Array, lam: jax.Array, v: jax.Array
                      ) -> jax.Array:
    """Relative eigen-residual ``max_k ||G v_k - lam_k v_k|| / lam_1``
    per user — the non-convergence detector for the subspace iteration
    (exact eigenpairs score ~float-eps; a stalled iteration does not).
    """
    r = grams @ v - v * lam[..., None, :]           # (N, d, k)
    num = jnp.linalg.norm(r, axis=-2)               # (N, k)
    scale = jnp.maximum(lam[..., :1], 1e-12)
    return jnp.max(num / scale, axis=-1)


# ---------------------------------------------------------------------------
# Chunked featurize -> Gram accumulation (the streaming step)
# ---------------------------------------------------------------------------

def _project_inputs(x_chunk: jax.Array, mask: jax.Array | None,
                    params: dict, fcfg: feat.FeatureConfig
                    ) -> tuple[jax.Array, jax.Array | None]:
    """Reduce any Phi kind to ``(z, w)`` with chunk Gram ``(z w)^T (z w)``
    (``w=None`` means identity) — the form the fused kernel consumes.
    The nonlinear conv front-end runs here in jnp; masking commutes with
    the trailing linear projection, so invalid rows contribute zero.
    ``mask=None`` means every row is valid (no masking pass)."""

    def masked(z):
        return z if mask is None else z * mask

    if fcfg.kind == "identity":
        return masked(x_chunk), None
    if fcfg.kind == "random_projection":
        return masked(x_chunk), params["w"]
    if fcfg.kind == "pca":
        return masked(x_chunk - params["mu"]), params["basis"]
    z = jax.vmap(
        lambda xc: feat._random_conv_features(xc, params["w1"],
                                              params["w2"], fcfg.image_hw)
    )(x_chunk)
    return masked(z), params.get("w_rp")


@partial(jax.jit,
         static_argnames=("fcfg", "backend", "compute_dtype",
                          "apply_mask"))
def _chunk_gram_accum(acc: jax.Array, x_chunk: jax.Array,
                      n_valid: jax.Array, start: jax.Array, params: dict,
                      fcfg: feat.FeatureConfig, backend: str,
                      compute_dtype: str, apply_mask: bool = True
                      ) -> jax.Array:
    """One streaming step: ``acc (N, d', d') += Phi(chunk)^T Phi(chunk)``.

    ``x_chunk (N, c, m)`` raw rows starting at global row ``start``; rows
    at or beyond each user's ``n_valid`` are masked to zero AFTER Phi
    (identical to zero-padding the featurized stack, for every kind
    including the affine ``pca``).  ``apply_mask=False`` skips the
    O(N*c*m) mask pass — only valid when the caller KNOWS every chunk
    row is a true data row.  Shared by all three backends — the
    shard_map raw protocol calls it per local shard.
    """
    x_chunk = x_chunk.astype(jnp.float32)
    if apply_mask:
        rows = start + jnp.arange(x_chunk.shape[1])
        mask = (rows[None, :] < n_valid[:, None]
                ).astype(jnp.float32)[..., None]
    else:
        mask = None
    z, w = _project_inputs(x_chunk, mask, params, fcfg)
    if backend == "pallas":
        from repro.kernels.featurize_gram import ops as fg_ops
        from repro.kernels.gram import ops as gram_ops

        if w is None:
            zc = z.astype(jnp.bfloat16) if compute_dtype == "bf16" else z
            g = jax.lax.map(lambda zi: gram_ops.gram_matrix(zi), zc)
        else:
            g = jax.lax.map(
                lambda zi: fg_ops.featurize_gram(
                    zi, w, compute_dtype=compute_dtype), z)
        return acc + g
    # Mirror the kernel's mixed precision exactly: bf16 matmul INPUTS
    # (projection and Gram alike), fp32 accumulation via
    # preferred_element_type.  The fp32 path uses the plain batched
    # matmul (fastest XLA:CPU lowering — one flattened GEMM).
    if compute_dtype == "bf16":
        z = z.astype(jnp.bfloat16)
        if w is not None:
            f = jnp.einsum("ncm,md->ncd", z, w.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
            f = f.astype(jnp.bfloat16)
        else:
            f = z
    else:
        f = z @ w if w is not None else z
    return acc + jnp.einsum("ncd,nce->nde", f, f,
                            preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class SignatureEngine:
    """One object that owns raw-data ingest: Phi, Gram streaming, top-k.

    ``feature_cfg`` fixes the shared Phi (pass the ``pca`` probe set via
    ``probe=`` — the config only pins its digest); ``cfg`` picks the
    execution strategy.  ``grams``/``signatures`` are the single-host
    entry points; the shard_map backend defers to
    ``ProtocolEngine.run_raw``, which reuses this engine's chunk step
    inside its sharded body.
    """

    def __init__(self, feature_cfg: feat.FeatureConfig,
                 cfg: SignatureConfig | None = None,
                 probe: np.ndarray | None = None):
        if not isinstance(feature_cfg, feat.FeatureConfig):
            raise TypeError("feature_cfg must be a FeatureConfig, got "
                            f"{type(feature_cfg).__name__}")
        self.feature_cfg = feature_cfg
        self.cfg = cfg or SignatureConfig()
        self._probe = probe
        self._params: dict[int, dict] = {}

    def params_for(self, m: int) -> dict:
        """Phi parameters for input dim ``m``, cached per engine AS
        DEVICE ARRAYS — so the per-chunk jit never re-uploads the
        projection matrices."""
        if m not in self._params:
            self._params[m] = {
                k: jnp.asarray(v)
                for k, v in feat.phi_params(self.feature_cfg, m,
                                            probe=self._probe).items()}
        return self._params[m]

    def out_dim(self, m: int) -> int:
        return feat.phi_out_dim(self.feature_cfg, m, probe=self._probe)

    def prepare(self, raw, n_valid=None) -> tuple[np.ndarray, jax.Array]:
        """Normalize raw input to ``(padded (N, n, m), n_valid (N,))``.

        Ragged lists of per-user ``(n_i, m)`` arrays are zero-padded ON
        THE HOST (``sim.prepare_user_batch(device=False)``) so the
        streaming path device-puts one row-chunk at a time.
        """
        return sim.prepare_user_batch(raw, n_valid, device=False)

    # -- ingest stages ------------------------------------------------------

    def accumulate_grams(self, raw, nv: jax.Array,
                         assume_full: bool = False) -> jax.Array:
        """The streaming core: ``raw (N, n, m)`` -> Grams ``(N, d', d')``.

        Streams ``chunk_rows`` rows at a time: each chunk is featurized
        and folded into the fp32 accumulator, then dies — the
        ``(N, n, d')`` feature stack never exists.  Works on host numpy
        (one row-chunk is device-put per step), on device arrays, and on
        traced values (``ProtocolEngine.run_raw`` calls this inside its
        shard_map body with the local user shard).

        ``assume_full=True`` declares every user's count equal to n, so
        the O(N*c*m) ragged mask pass is elided for chunks that lie
        entirely inside the data (the zero-padded tail chunk, if any, is
        still masked — ``pca``'s affine Phi needs it).
        """
        if isinstance(raw, jax.core.Tracer) or isinstance(
                nv, jax.core.Tracer):
            # inside a shard_map/jit trace: spans are host-side and would
            # record trace time, not run time — instrument nothing here
            return self._accumulate_grams(raw, nv, assume_full)
        n = raw.shape[1]
        chunk = min(self.cfg.chunk_rows or n, n)
        with obs.span("signature.accumulate_grams",
                      n_users=raw.shape[0], backend=self.cfg.backend,
                      chunks=-(-n // max(chunk, 1))) as sp:
            return sp.sync(self._accumulate_grams(raw, nv, assume_full))

    def _accumulate_grams(self, raw, nv: jax.Array,
                          assume_full: bool = False) -> jax.Array:
        n_users, n, m = raw.shape
        d_out = self.out_dim(m)
        params = self.params_for(m)
        chunk_backend = "pallas" if self.cfg.backend == "pallas" else "jnp"
        chunk = min(self.cfg.chunk_rows or n, n)
        acc = jnp.zeros((n_users, d_out, d_out), jnp.float32)
        prev = None
        for s in range(0, n, chunk):
            x_c = jnp.asarray(raw[:, s:s + chunk])
            padded_tail = x_c.shape[1] < chunk
            if padded_tail:                # square off the last chunk so
                x_c = jnp.pad(               # one compiled step serves all
                    x_c, ((0, 0), (0, chunk - x_c.shape[1]), (0, 0)))
            acc = _chunk_gram_accum(acc, x_c, nv,
                                    jnp.asarray(s, jnp.float32), params,
                                    self.feature_cfg, chunk_backend,
                                    self.cfg.compute_dtype,
                                    apply_mask=(not assume_full
                                                or padded_tail))
            # Bound the async dispatch queue to a 2-chunk window
            # (double-buffering): without this, jax enqueues EVERY chunk
            # transfer before the first step runs and the whole raw
            # array is simultaneously live — peak memory silently scales
            # with n, which is exactly what streaming must prevent.
            # (No-op under tracing: the shard_map body has no queue.)
            if prev is not None and not isinstance(prev, jax.core.Tracer):
                prev.block_until_ready()
            prev = acc
        return acc / jnp.maximum(nv, 1.0)[:, None, None]

    def grams(self, raw, n_valid=None) -> jax.Array:
        """Per-user Grams ``(N, d', d')`` straight from raw shards."""
        if self.cfg.backend == "shard_map":
            raise ValueError(
                "the shard_map signature backend runs inside "
                "ProtocolEngine.run_raw (it owns the mesh); use backend "
                "'jnp'/'pallas' for direct grams()")
        full = (n_valid is None
                and isinstance(raw, (jax.Array, np.ndarray)))
        raw, nv = self.prepare(raw, n_valid)
        return self.accumulate_grams(raw, nv, assume_full=full)

    def verify_convergence(self, resid: jax.Array) -> None:
        """Raise ``RuntimeError`` if any user's relative eigen-residual
        exceeds ``cfg.resid_tol`` (host sync — call outside jit)."""
        worst = float(jnp.max(resid))
        if not worst < self.cfg.resid_tol:
            raise RuntimeError(
                f"top-k subspace iteration did not converge: max "
                f"relative residual {worst:.2e} > tol "
                f"{self.cfg.resid_tol:.2e} — raise subspace_iters/"
                f"oversample or set eig='eigh'")

    def signatures(self, raw, n_valid=None, top_k: int = 8,
                   check: bool | None = None
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Raw shards -> ``(lam (N, k), V (N, d', k), G (N, d', d'))``.

        ``lam``/``V`` are what users share (upload unchanged at O(k*d));
        ``G`` stays device-resident for cross-projection.  ``check``
        (default ``cfg.check``) verifies subspace convergence via the
        relative residual norm and raises ``RuntimeError`` above
        ``cfg.resid_tol``.
        """
        with obs.span("signature.signatures", top_k=top_k,
                      backend=self.cfg.backend) as sp:
            g = self.grams(raw, n_valid)
            lam, v = topk_spectrum(g, top_k, method=self.cfg.eig,
                                   iters=self.cfg.subspace_iters,
                                   oversample=self.cfg.oversample)
            sp.sync((lam, v))
        if self.cfg.check if check is None else check:
            self.verify_convergence(subspace_residual(g, lam, v))
        return lam, v, g
