"""Backend-pluggable one-shot protocol engine (paper Algorithm 2).

The ``ProtocolEngine`` is the single entry point for the similarity
protocol: signature computation (Eq. 1-2), exchange, relevance (Eq. 3-4)
and symmetrization (Eq. 5).  ``oneshot.one_shot_clustering``,
``similarity.similarity_matrix``, ``distributed.distributed_similarity``,
the benchmarks and ``repro.launch.protocol`` all route through it; the
backend is picked by ``SimilarityConfig``, not by call-site forking:

  backend      | execution
  -------------|----------------------------------------------------------
  "jnp"        | single host, reference jnp maths
  "pallas"     | single host, Pallas kernels for Gram / cross-projection
  "shard_map"  | users sharded over a mesh axis; the paper's star-topology
               | message pattern becomes two all_gathers (signatures, rows)

Orthogonally, ``block_users > 0`` turns on **blockwise streaming** for the
single-host backends: users are processed in tiles, per-tile Grams are
eigendecomposed and discarded, and cross-projection against the running
signature table is Gram-free (``||G_i v|| = ||F_i^T (F_i v)|| / n_i``,
fused in ``repro.kernels.gram_project`` on the Pallas path).  Peak memory
drops from O(N * d^2) to O(block_users * d^2) + the O(N * d * k) signature
table — exactly what each user receives over the air anyway — so
multi-thousand-user similarity fits on one host.

``landmarks = m > 0`` instead turns on the **Nystrom-sketched** flat
path: every user is scored only against m << N landmark signatures via
the ``kernels/assign`` projector-affinity scorer (``C (N, m)``), and the
full similarity is completed from the landmark block, ``R ~= C W^+ C^T``
with ``W = C[landmark_rows]`` — O(N * m) scored entries instead of
O(N^2).  The sketched similarity approximates the (PSD, unit-diagonal)
projector-affinity kernel ``A[i, j] = ||V_j^T V_i||_F^2 / k`` rather
than the eigenvalue-ratio relevance of Eq. 3-4; both order same-task
pairs above cross-task pairs, and the Nystrom completion is exact at
m = N.  Landmark sets are nested (prefixes of one fixed seeded
permutation), so the approximation error is monotone non-increasing
in m.

``run_raw`` is the RAW-DATA entry point: callers hand per-user raw shards
plus a ``FeatureConfig`` instead of pre-featurized arrays, and the
``SignatureEngine`` (``core/signature_engine.py``) runs featurize -> Gram
-> top-k spectrum on-device (row-chunk streaming, fused Pallas kernel,
batched subspace iteration instead of the O(d^3) ``eigh``) before the
relevance stage — raw data to R without the host Phi loop or the
``(N, n, d)`` feature stack.  Under the shard_map backend the user axis
of the raw shards is itself sharded over the mesh.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import similarity as sim
from repro.core import signature_engine as sig

__all__ = ["ProtocolEngine", "ProtocolResult", "BACKENDS", "make_user_mesh",
           "landmark_indices"]

BACKENDS = ("jnp", "pallas", "shard_map")


def make_user_mesh(axis_name: str = "data") -> Mesh:
    """A 1-D mesh over all local devices for user sharding (tests/demos)."""
    devs = np.asarray(jax.devices())
    return Mesh(devs, (axis_name,))


@dataclasses.dataclass(frozen=True)
class ProtocolResult:
    """Everything the protocol produces before clustering.

    ``lam``/``v`` are the shared per-user signatures (what each user
    uploaded) — every backend returns them so the serving layer
    (``core.membership_engine``) can build its cluster directory without
    re-running any protocol stage.
    """

    relevance: jax.Array          # (N, N) directed r(i, j)
    similarity: jax.Array         # (N, N) symmetrized R
    n_users: int
    d: int
    top_k: int
    lam: jax.Array | None = None  # (N, k) shared spectra
    v: jax.Array | None = None    # (N, d, k) shared eigenvectors


# ---------------------------------------------------------------------------
# Dense path: one jit, full (N, d, d) Gram stack (fast for modest N)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("top_k", "impl"))
def _dense_protocol(features, n_valid, top_k, eig_floor, impl):
    grams = sim.batched_gram(features, n_valid, impl=impl)
    lam, v = jax.vmap(lambda g: sim.spectrum(g, top_k))(grams)
    r = sim.relevance_matrix(grams, lam, v, eig_floor, impl=impl)
    return r, sim.symmetrize(r), lam, v


# ---------------------------------------------------------------------------
# Blockwise streaming path: tiles of users, Gram-free cross-projection
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("top_k", "impl"))
def _tile_signatures(features, n_valid, top_k, impl):
    """One tile's shared signatures; the (block, d, d) Grams die here."""
    grams = sim.batched_gram(features, n_valid, impl=impl)
    return jax.vmap(lambda g: sim.spectrum(g, top_k))(grams)


@partial(jax.jit, static_argnames=("top_k", "impl"))
def _tile_rows(features, n_valid, lam_tile, v_flat, eig_floor, top_k, impl):
    """Relevance rows for one user tile against the full signature table.

    ``v_flat (d, N_pad * k)`` stacks every user's eigenvectors column-wise,
    so one matmul pair per user projects ALL signatures at once —
    ``||G_i v|| = ||F_i^T (F_i v)|| / n_i`` (no (d, d) Gram).
    """

    def one(args):
        f, nv, lam_i = args
        if impl == "pallas":
            from repro.kernels.gram_project import ops as gp_ops

            lam_hat = gp_ops.gram_project(f, v_flat, n_valid=nv)
        else:
            from repro.kernels.gram_project.ref import gram_project_ref

            lam_hat = gram_project_ref(f, v_flat, n_valid=nv)
        lam_hat = lam_hat.reshape(-1, top_k)                 # (N_pad, k)
        return jax.vmap(
            lambda lh: sim.relevance(lam_i, lh, eig_floor))(lam_hat)

    return jax.lax.map(one, (features, n_valid, lam_tile))


# ---------------------------------------------------------------------------
# Landmark/Nystrom-sketched path: O(N * m) scored entries, m << N
# ---------------------------------------------------------------------------

def landmark_indices(n: int, m: int) -> np.ndarray:
    """``m`` deterministic landmark user ids out of ``n``, NESTED: every
    set is a prefix of one fixed seeded permutation, so the set for any
    ``m' > m`` contains the set for ``m`` and Nystrom error can only
    shrink as landmarks are added.  A uniform permutation rather than an
    index-stride scheme: federated rosters commonly interleave tasks
    over user id (round-robin), where any stride-aligned pick collapses
    onto a single task and the sketch misses whole clusters."""
    if not 0 < m <= n:
        raise ValueError(f"need 0 < m <= n, got m={m}, n={n}")
    return np.random.default_rng(0x5EED).permutation(n)[:m].astype(np.int32)


@jax.jit
def _nystroem_complete(c: jax.Array, w: jax.Array) -> jax.Array:
    """``R ~= C W^+ C^T`` from the scored columns ``C (N, m)`` and the
    landmark-landmark block ``W (m, m)``, symmetrized + clipped to the
    affinity range (pinv noise can leave tiny negatives / > 1 spill)."""
    r = c @ jnp.linalg.pinv(w, rtol=1e-6) @ c.T
    return jnp.clip(sim.symmetrize(r), 0.0, 1.0)


# ---------------------------------------------------------------------------
# shard_map path: the paper's message pattern on TPU collectives
# ---------------------------------------------------------------------------

def _sharded_protocol(features, n_valid, *, axis: str, top_k: int,
                      eig_floor: float, impl: str):
    """shard_map body.  ``features (N_local, n, d)`` per device.

      paper                               | here
      ------------------------------------|-------------------------------
      user i broadcasts V_i to all users  | all_gather of (k, d) blocks
      user i uploads row r(i, .) to GPS   | all_gather of relevance rows
      GPS symmetrizes R, runs HAC         | every device holds R; HAC runs
                                          | host-side on the tiny N x N R
    """
    # Phase 1: local spectral signatures (no communication).
    grams = sim.batched_gram(features, n_valid, impl=impl)        # (Nl,d,d)
    lam, v = jax.vmap(lambda g: sim.spectrum(g, top_k))(grams)

    # Phase 2: signature exchange == paper's "share V_i".  The spectra
    # ride along (tiny (Nl, k) blocks) so the GPS-side serving directory
    # can be built straight from the gathered signatures.
    v_all = jax.lax.all_gather(v, axis, tiled=True)               # (N, d, k)
    lam_all = jax.lax.all_gather(lam, axis, tiled=True)           # (N, k)

    # Phase 3: local relevance rows — row i uses MY gram + spectrum
    # against EVERY user's eigenvectors (Algorithm 2 lines 7-12).
    r_rows = sim.relevance_matrix(grams, lam, v_all, eig_floor,
                                  impl=impl)                      # (Nl, N)

    # Phase 4: GPS assembly == all_gather of rows + symmetrize.
    r_full = jax.lax.all_gather(r_rows, axis, tiled=True)         # (N, N)
    return r_full, sim.symmetrize(r_full), lam_all, v_all


# ---------------------------------------------------------------------------
# Raw-data path: SignatureEngine ingest -> relevance (no host Phi stage)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("top_k", "impl", "eig", "iters",
                                   "oversample", "check"))
def _raw_finish(grams, top_k, eig_floor, impl, eig, iters, oversample,
                check):
    """Gram stack -> (r, R, resid, lam, v) in one jit: top-k spectrum
    (subspace iteration by default — no O(d^3) eigh) + relevance +
    symmetrize.  The per-user eigen-residual is only computed when the
    caller will ``check`` it (``resid`` is ``None`` otherwise)."""
    lam, v = sig.topk_spectrum(grams, top_k, method=eig, iters=iters,
                               oversample=oversample)
    resid = sig.subspace_residual(grams, lam, v) if check else None
    r = sim.relevance_matrix(grams, lam, v, eig_floor, impl=impl)
    return r, sim.symmetrize(r), resid, lam, v


def _sharded_raw_protocol(x, nv, *, axis: str, engine, top_k: int,
                          eig_floor: float, impl: str,
                          assume_full: bool = False):
    """shard_map body for the raw entry point: each device featurizes its
    own user shard (the SAME ``SignatureEngine.accumulate_grams`` row-chunk
    streaming the single-host path runs), extracts top-k signatures
    locally, then the same two all_gathers as the pre-featurized path
    (signatures, rows)."""
    grams = engine.accumulate_grams(x, nv, assume_full=assume_full)
    lam, v = sig.topk_spectrum(grams, top_k, method=engine.cfg.eig,
                               iters=engine.cfg.subspace_iters,
                               oversample=engine.cfg.oversample)
    v_all = jax.lax.all_gather(v, axis, tiled=True)               # (N, d, k)
    lam_all = jax.lax.all_gather(lam, axis, tiled=True)           # (N, k)
    r_rows = sim.relevance_matrix(grams, lam, v_all, eig_floor,
                                  impl=impl)                      # (Nl, N)
    r_full = jax.lax.all_gather(r_rows, axis, tiled=True)         # (N, N)
    if engine.cfg.check:
        resid = sig.subspace_residual(grams, lam, v)              # (Nl,)
        return (r_full, sim.symmetrize(r_full),
                jax.lax.all_gather(resid, axis, tiled=True),
                lam_all, v_all)
    return (r_full, sim.symmetrize(r_full), jnp.zeros((0,), jnp.float32),
            lam_all, v_all)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class ProtocolEngine:
    """One object that owns the whole one-shot protocol.

    ``cfg.backend`` selects the execution strategy; ``cfg.block_users``
    selects dense vs streaming on the single-host backends.  A ``mesh`` is
    only consulted by the shard_map backend (defaults to a 1-D mesh over
    all local devices).
    """

    def __init__(self, cfg: sim.SimilarityConfig | None = None,
                 mesh: Mesh | None = None):
        cfg = cfg or sim.SimilarityConfig()
        if cfg.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {cfg.backend!r}")
        if cfg.block_users < 0:
            raise ValueError(f"block_users must be >= 0, got "
                             f"{cfg.block_users}")
        if cfg.block_users and cfg.backend == "shard_map":
            raise ValueError("blockwise streaming (block_users > 0) is a "
                             "single-host mode; the shard_map backend "
                             "already tiles users over devices")
        if cfg.landmarks and cfg.backend == "shard_map":
            raise ValueError("the landmark-sketched path (landmarks > 0) "
                             "is a single-host mode; shard_map computes "
                             "exact relevance rows per device")
        self.cfg = cfg
        self.mesh = mesh

    @property
    def impl(self) -> str:
        """Kernel implementation: the pallas backend forces Pallas kernels."""
        return "pallas" if self.cfg.backend == "pallas" else self.cfg.impl

    def _top_k(self, d: int) -> int:
        """Effective signature width: ``0`` means all d, and a Gram only has
        d eigenpairs however large ``cfg.top_k`` is."""
        return min(self.cfg.top_k or d, d)

    def prepare(self, features, n_valid=None
                ) -> tuple[jax.Array, jax.Array]:
        """Normalize any accepted input form to ``(padded, n_valid)``.

        Ragged lists of ``(n_i, d)`` arrays are zero-padded via
        ``sim.pad_ragged``; padded arrays get a full-length ``n_valid``
        unless the true counts are supplied.
        """
        return sim.prepare_user_batch(features, n_valid, device=True)

    # -- protocol stages ----------------------------------------------------

    def signatures(self, features, n_valid=None
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Per-user ``(lam (N, k), V (N, d, k), G (N, d, d))`` — dense only.

        ``lam``/``V`` are what users share; ``G`` stays on-device and is
        exposed for robustness studies (e.g. perturbed-eigenvector sweeps).
        Materializing every Gram is inherently dense, so non-dense configs
        are rejected rather than silently run dense.
        """
        if self.cfg.backend == "shard_map" or self.cfg.block_users:
            raise ValueError(
                "signatures() materializes the full (N, d, d) Gram stack "
                "and is only available on the dense single-host config "
                f"(got backend={self.cfg.backend!r}, "
                f"block_users={self.cfg.block_users})")
        feats, nv = self.prepare(features, n_valid)
        grams = sim.batched_gram(feats, nv, impl=self.impl)
        lam, v = jax.vmap(
            lambda g: sim.spectrum(g, self._top_k(feats.shape[-1])))(grams)
        return lam, v, grams

    def relevance_and_similarity(self, features, n_valid=None
                                 ) -> tuple[jax.Array, jax.Array]:
        """Run the full protocol -> ``(r (N, N) directed, R symmetrized)``."""
        feats, nv = self.prepare(features, n_valid)
        return self._dispatch(feats, nv)[:2]

    def similarity(self, features, n_valid=None) -> jax.Array:
        """``R (N, N)`` — the matrix the GPS feeds to HAC."""
        return self.relevance_and_similarity(features, n_valid)[1]

    def run(self, features, n_valid=None) -> ProtocolResult:
        with obs.span("protocol.run", backend=self.cfg.backend):
            feats, nv = self.prepare(features, n_valid)
            r, big_r, lam, v = self._dispatch(feats, nv)
        n_users, _, d = feats.shape
        return ProtocolResult(relevance=r, similarity=big_r,
                              n_users=n_users, d=d, top_k=self._top_k(d),
                              lam=lam, v=v)

    # -- raw-data entry point ----------------------------------------------

    def _signature_engine(self, feature_cfg, signature_cfg, probe
                          ) -> "sig.SignatureEngine":
        """Build the ingest engine, deriving its backend from the protocol
        backend when not given and rejecting conflicting combinations."""
        if signature_cfg is None:
            signature_cfg = sig.SignatureConfig(backend=self.cfg.backend,
                                                mesh_axis=self.cfg.mesh_axis)
        if ((signature_cfg.backend == "shard_map")
                != (self.cfg.backend == "shard_map")):
            raise ValueError(
                f"signature backend {signature_cfg.backend!r} conflicts "
                f"with protocol backend {self.cfg.backend!r}: shard_map "
                "ingest runs inside the sharded protocol — use both or "
                "neither")
        if (signature_cfg.backend == "shard_map"
                and signature_cfg.mesh_axis != self.cfg.mesh_axis):
            raise ValueError(
                f"signature mesh_axis {signature_cfg.mesh_axis!r} "
                f"conflicts with protocol mesh_axis "
                f"{self.cfg.mesh_axis!r}: the raw shard_map pipeline "
                "shards users over ONE axis")
        return sig.SignatureEngine(feature_cfg, signature_cfg, probe=probe)

    def run_raw(self, raw, feature_cfg, n_valid=None, probe=None,
                signature_cfg: "sig.SignatureConfig | None" = None
                ) -> ProtocolResult:
        """Full protocol from RAW user shards: ``raw (N, n, m)`` (or a
        ragged list of ``(n_i, m)``) + a ``FeatureConfig`` -> ``(r, R)``.

        The ``SignatureEngine`` ingests on-device (streamed featurize ->
        Gram, batched top-k subspace iteration); the relevance stage then
        runs on the resulting ``(N, d', d')`` Gram stack in the same jit.
        Pass the ``pca`` probe set via ``probe=``.  ``block_users``
        streaming belongs to the pre-featurized path (it never holds the
        Gram stack, which raw relevance needs) and is rejected here.
        """
        if self.cfg.block_users:
            raise ValueError(
                "run_raw computes relevance on the (N, d', d') Gram stack "
                "and does not support block_users streaming; stream the "
                "ROW axis instead via SignatureConfig.chunk_rows")
        if self.cfg.landmarks:
            raise ValueError(
                "run_raw computes exact relevance on the Gram stack and "
                "does not support the landmark sketch; featurize first "
                "and use run() with landmarks > 0")
        engine = self._signature_engine(feature_cfg, signature_cfg, probe)
        full = (n_valid is None
                and isinstance(raw, (jax.Array, np.ndarray)))
        raw, nv = engine.prepare(raw, n_valid)
        n_users, _, m = raw.shape
        d_out = engine.out_dim(m)
        top_k = self._top_k(d_out)
        with obs.span("protocol.run_raw", backend=self.cfg.backend,
                      n_users=n_users) as sp:
            if self.cfg.backend == "shard_map":
                r, big_r, resid, lam, v = self._run_raw_shard_map(
                    engine, raw, nv, top_k, full)
            else:
                grams = engine.accumulate_grams(raw, nv, assume_full=full)
                r, big_r, resid, lam, v = _raw_finish(
                    grams, top_k, self.cfg.eig_floor, self.impl,
                    engine.cfg.eig, engine.cfg.subspace_iters,
                    engine.cfg.oversample, engine.cfg.check)
            sp.sync((r, big_r, lam, v))
        if engine.cfg.check:
            engine.verify_convergence(resid)
        return ProtocolResult(relevance=r, similarity=big_r,
                              n_users=n_users, d=d_out, top_k=top_k,
                              lam=lam, v=v)

    def similarity_from_raw(self, raw, feature_cfg, n_valid=None,
                            probe=None, signature_cfg=None) -> jax.Array:
        """``R (N, N)`` straight from raw shards — see ``run_raw``."""
        return self.run_raw(raw, feature_cfg, n_valid=n_valid, probe=probe,
                            signature_cfg=signature_cfg).similarity

    def _run_raw_shard_map(self, engine, raw, nv, top_k: int,
                           assume_full: bool = False):
        axis = self.cfg.mesh_axis
        mesh = self.mesh or make_user_mesh(axis)
        n_users = raw.shape[0]
        axis_size = mesh.shape[axis]
        if n_users % axis_size:
            raise ValueError(
                f"n_users={n_users} not divisible by mesh axis {axis!r}"
                f" of size {axis_size}")
        engine.params_for(raw.shape[-1])      # fit Phi OUTSIDE the trace
        body = partial(_sharded_raw_protocol, axis=axis, engine=engine,
                       top_k=top_k, eig_floor=self.cfg.eig_floor,
                       impl=self.impl, assume_full=assume_full)
        spec_in = P(axis)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(spec_in, spec_in),
                           out_specs=(P(), P(), P(), P(), P()),
                           check_vma=False)
        with mesh:
            # straight from the host into per-device user shards: staging
            # the whole raw stack on the default device first would need
            # all N users' rows in ONE device's memory
            raw = jax.device_put(raw, NamedSharding(mesh, P(axis)))
            nv = jax.device_put(nv, NamedSharding(mesh, P(axis)))
            return jax.jit(fn)(raw, nv)

    def _dispatch(self, feats: jax.Array, nv: jax.Array):
        """Backend dispatch on already-``prepare``d inputs ->
        ``(r, R, lam, v)``."""
        mode = ("shard_map" if self.cfg.backend == "shard_map"
                else "landmarks" if self.cfg.landmarks
                else "blockwise" if self.cfg.block_users else "dense")
        with obs.span("protocol.dispatch", mode=mode,
                      backend=self.cfg.backend, impl=self.impl,
                      n_users=feats.shape[0]) as sp:
            if self.cfg.backend == "shard_map":
                out = self._run_shard_map(feats, nv)
            elif self.cfg.landmarks:
                out = self._run_landmarks(feats, nv)
            elif self.cfg.block_users:
                out = self._run_blockwise(feats, nv)
            else:
                out = _dense_protocol(feats, nv,
                                      self._top_k(feats.shape[-1]),
                                      self.cfg.eig_floor, self.impl)
            sp.sync(out)
        if obs.enabled():
            obs.count("protocol.dispatches", mode=mode)
        return out

    # -- backends -----------------------------------------------------------

    def _run_blockwise(self, feats: jax.Array, nv: jax.Array):
        n_users, n, d = feats.shape
        block = min(self.cfg.block_users, n_users)
        top_k = self._top_k(d)
        pad = (-n_users) % block
        if pad:
            # Phantom users (zero features, n_valid 1) square off the last
            # tile so every tile jit-compiles once; their rows/cols are
            # sliced away below.
            feats = jnp.concatenate(
                [feats, jnp.zeros((pad, n, d), feats.dtype)])
            nv = jnp.concatenate([nv, jnp.ones((pad,), nv.dtype)])
        n_total = n_users + pad

        # Pass 1 — signature table, one tile at a time.  O(block * d^2)
        # live Grams; the table itself is O(N * d * k), the same payload
        # every user downloads in the paper's exchange.
        lam_tiles, v_tiles = [], []
        for s in range(0, n_total, block):
            lam_t, v_t = _tile_signatures(feats[s:s + block],
                                          nv[s:s + block], top_k, self.impl)
            lam_tiles.append(lam_t)
            v_tiles.append(v_t)
        lam_all = jnp.concatenate(lam_tiles)                  # (N_tot, k)
        v_all = jnp.concatenate(v_tiles)                      # (N_tot, d, k)
        v_flat = jnp.transpose(v_all, (1, 0, 2)).reshape(d, -1)

        # Pass 2 — relevance rows, tile by tile, Gram-free.
        rows = []
        for s in range(0, n_total, block):
            rows.append(_tile_rows(feats[s:s + block], nv[s:s + block],
                                   lam_all[s:s + block], v_flat,
                                   self.cfg.eig_floor, top_k, self.impl))
        r = jnp.concatenate(rows)[:n_users, :n_users]
        return (r, sim.symmetrize(r), lam_all[:n_users], v_all[:n_users])

    def _run_landmarks(self, feats: jax.Array, nv: jax.Array):
        """Nystrom-sketched flat path -> ``(R, R, lam, v)``.

        Pass 1 streams the signature table exactly like the blockwise
        path (per-tile Grams die young).  Pass 2 scores every user
        against the m landmark PROJECTORS ``V_j V_j^T`` through the
        ``kernels/assign`` scorer — ``C[i, j] = ||V_j^T V_i||_F^2 / k``,
        O(N * m) entries — and ``_nystroem_complete`` fills in the rest.
        The sketched similarity is already symmetric, so the directed
        ``r`` slot returns the same matrix.
        """
        n_users, _, d = feats.shape
        m = self.cfg.landmarks
        if m >= n_users:
            raise ValueError(
                f"landmarks={m} must be < n_users={n_users}: the sketch "
                "only pays when m << N — drop landmarks to 0 and run the "
                "exact dense path instead")
        top_k = self._top_k(d)
        tile = min(2048, n_users)
        lam_tiles, v_tiles = [], []
        for s in range(0, n_users, tile):
            lam_t, v_t = _tile_signatures(feats[s:s + tile],
                                          nv[s:s + tile], top_k, self.impl)
            lam_tiles.append(lam_t)
            v_tiles.append(v_t)
        lam_all = jnp.concatenate(lam_tiles)              # (N, k)
        v_all = jnp.concatenate(v_tiles)                  # (N, d, k)

        idx = landmark_indices(n_users, m)
        v_land = v_all[idx]
        protos = jnp.einsum("mdk,mek->mde", v_land, v_land)   # (m, d, d)
        if self.impl == "pallas":
            from repro.kernels.assign import ops as assign_ops

            score = partial(assign_ops.assign, protos=protos)
        else:
            from repro.kernels.assign.ref import assign_ref

            score = jax.jit(partial(assign_ref, protos=protos))
        cols = [score(v_all[s:s + tile])[0]
                for s in range(0, n_users, tile)]
        c = jnp.concatenate(cols)                         # (N, m)
        big_r = _nystroem_complete(c, c[idx])
        return big_r, big_r, lam_all, v_all

    def _run_shard_map(self, feats: jax.Array, nv: jax.Array):
        axis = self.cfg.mesh_axis
        mesh = self.mesh or make_user_mesh(axis)
        n_users = feats.shape[0]
        axis_size = mesh.shape[axis]
        if n_users % axis_size:
            raise ValueError(
                f"n_users={n_users} not divisible by mesh axis {axis!r}"
                f" of size {axis_size}")
        top_k = self._top_k(feats.shape[-1])
        body = partial(_sharded_protocol, axis=axis, top_k=top_k,
                       eig_floor=self.cfg.eig_floor, impl=self.impl)
        spec_in = P(axis)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(spec_in, spec_in),
                           out_specs=(P(), P(), P(), P()),  # replicated
                           check_vma=False)
        with mesh:
            feats = jax.device_put(feats, NamedSharding(mesh, P(axis)))
            nv = jax.device_put(nv, NamedSharding(mesh, P(axis)))
            return jax.jit(fn)(feats, nv)
