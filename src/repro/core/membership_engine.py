"""Online cluster-identity serving — the MembershipEngine.

The paper's protocol estimates every cluster identity once, with all N
users present; a single newcomer would force a full O(N^2) protocol
re-run.  This module is the serving-side answer: after
``one_shot_clustering`` the GPS keeps a compact device-resident **cluster
directory** — per-cluster signature prototypes ``P_t = mean_{i in t}
V_i V_i^T`` plus the member spectra table — and decides a newcomer's
cluster identity from its existing ``(k x d)`` signature upload alone, in
O(T * k * d^2) per arrival, with zero training rounds.  IFCA-style
frameworks need a per-round loss probe against every cluster model; here
the signature the user already shared IS the probe.

Engine idiom mirrors ``ProtocolEngine``/``ClusterEngine``/
``SignatureEngine`` — one object, a config-selected backend:

  backend   | execution
  ----------|------------------------------------------------------------
  "numpy"   | host reference: np.einsum affinities, host lifecycle
  "jnp"     | jitted directory ops; one dispatch per arrival wave
  "pallas"  | the same program with the fused ``kernels/assign``
            | project + trace + argmax kernel (bf16 / fp32 accumulate)

Lifecycle on top of assignment:

  * ``assign``   — batched wave: affinities vs prototypes, labels +
                   confidence margins; low-margin / low-affinity arrivals
                   land in the ``unassigned`` bucket (label -1).
  * ``admit``    — append signatures to the table, update prototypes by
                   streaming mean.
  * ``evict``    — churn: masked removal + prototype down-date.
  * ``recluster``— drift trigger: when the unassigned fraction or the
                   prototype-shift norm trips the configured threshold,
                   re-run HAC over the CURRENT table via the
                   ``ClusterEngine`` (reused verbatim) on a
                   signature-only relevance matrix, then relabel to
                   maximize continuity with the previous directory.

The signature-only relevance uses the rank-k reconstruction
``G_i ~ V_i diag(lam_i) V_i^T`` — exactly the data users shared — so
``lamhat = ||diag(lam_i) (V_i^T v_j)||`` needs no private Grams and the
GPS can re-cluster without another protocol round.

**Robust prototypes (dirty-data serving).**  The plain mean projector has
breakdown point 0: one Byzantine signature upload (no norm check is
possible on an adversarial client) steers a whole cluster's directory
entry arbitrarily far.  ``MembershipConfig.aggregator`` selects a
resistant statistic over the member projectors ``V_i V_i^T``:

  aggregator | statistic                         | breakdown point
  -----------|-----------------------------------|----------------------
  "mean"     | streaming mean (the paper's)      | 0
  "trimmed"  | coordinate-wise trimmed mean,     | ``trim_frac``
             | ``trim_frac`` cut from each end   |
  "medians"  | coordinate-wise median-of-means   | ~``n_clean_groups/2``
             | over ``mom_groups`` member groups |

The resistant modes cannot be maintained by the O(1) streaming
admit/evict down-date (order statistics do not decompose), so those
paths fall back to a windowed recompute over the live table — the clean
"mean" path keeps its streaming update and its latency.  The drift
statistic has a matching robust variant: ``drift_stat="median"`` trips
the re-cluster trigger on the *median* per-cluster prototype shift
instead of the max, so one poisoned prototype cannot force re-cluster
thrash.  Corruption generators for exercising all of this live in
``repro.data.synthetic`` (``CorruptionSpec``) and the scenario matrix in
``repro.launch.membership``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import similarity as sim
from repro.core.cluster_engine import ClusterConfig, ClusterEngine
from repro.core.engine import make_user_mesh
from repro.kernels import quant
from repro.kernels.assign.ref import assign_ref

__all__ = ["MembershipConfig", "MembershipEngine", "MembershipState",
           "AssignResult", "MEMBERSHIP_BACKENDS", "signature_relevance"]

MEMBERSHIP_BACKENDS = ("numpy", "jnp", "pallas")
AGGREGATORS = ("mean", "trimmed", "medians")
DRIFT_STATS = ("max", "median")
UNASSIGNED = -1


@dataclasses.dataclass(frozen=True)
class MembershipConfig:
    """Configuration of the online membership layer.

    Attributes:
      backend: "numpy" (host reference), "jnp" (jitted device directory)
        or "pallas" (fused ``kernels/assign`` arrival kernel).
      capacity: signature-table slots; ``0`` sizes the directory at
        2x the seed population on ``from_oneshot``/``seed``.
      affinity_floor: arrivals whose best affinity falls below this land
        in the unassigned bucket (label -1).  Affinities live in [0, 1].
      margin_floor: arrivals whose best-minus-second margin falls below
        this are unassigned — the outlier/drift statistic.
      recluster_unassigned_frac: drift trigger — re-cluster when the
        unassigned fraction of the table exceeds this.
      recluster_proto_shift: drift trigger — re-cluster when any
        prototype's relative Frobenius shift since the last (re)cluster
        exceeds this.
      eig_floor: relevance eigenvalue floor for the signature-only
        re-cluster similarity (same semantics as ``SimilarityConfig``).
      aggregator: prototype statistic over member projectors — "mean"
        (streaming, breakdown point 0), "trimmed" (coordinate-wise
        trimmed mean, resists up to a ``trim_frac`` fraction of
        Byzantine members per cluster) or "medians" (coordinate-wise
        median-of-means over ``mom_groups`` member groups).  The
        resistant modes recompute prototypes from the live table on
        admit/evict (windowed recompute) instead of the streaming
        update.
      trim_frac: per-end trim fraction for ``aggregator="trimmed"``,
        in [0, 0.5).
      mom_groups: member-group count for ``aggregator="medians"``; the
        statistic resists corruption while fewer than half the occupied
        groups contain a poisoned member.
      drift_stat: "max" trips ``recluster_proto_shift`` on the worst
        per-cluster prototype shift (the PR-5 statistic); "median" on
        the median shift — robust to a single poisoned prototype.
      linkage: HAC linkage handed to the ``ClusterEngine`` on re-cluster.
      compute_dtype: pallas assign kernel precision — "bf16" matmul
        inputs with fp32 accumulation (default) or exact "fp32".
      directory_dtype: storage dtype of the prototype table — "f32"
        (exact), "bf16" (2x memory cut) or "int8" (4x, symmetric
        per-prototype scales from ``kernels.quant``).  The pallas
        backend dequantizes inside the assign kernel's epilogue; the
        jnp/numpy paths dequantize before scoring.  Streaming
        admit/evict updates dequant -> update -> requant, so the table
        never needs a resident f32 copy.
      interpret: Pallas interpret-mode override (default: lowered on
        TPU/GPU, interpret on CPU via ``kernels.dispatch``), consulted
        by the pallas backend only.
    """

    backend: str = "numpy"
    capacity: int = 0
    affinity_floor: float = 0.0
    margin_floor: float = 0.0
    recluster_unassigned_frac: float = 0.25
    recluster_proto_shift: float = 0.75
    eig_floor: float = 1e-6
    aggregator: str = "mean"
    trim_frac: float = 0.1
    mom_groups: int = 5
    drift_stat: str = "max"
    linkage: str = "average"
    compute_dtype: str = "bf16"
    directory_dtype: str = "f32"
    interpret: bool | None = None

    def __post_init__(self):
        if self.backend not in MEMBERSHIP_BACKENDS:
            raise ValueError(f"backend must be one of "
                             f"{MEMBERSHIP_BACKENDS}, got {self.backend!r}")
        if self.capacity < 0:
            raise ValueError(f"capacity must be >= 0, got {self.capacity}")
        if not 0.0 < self.recluster_unassigned_frac <= 1.0:
            raise ValueError(f"recluster_unassigned_frac must be in "
                             f"(0, 1], got {self.recluster_unassigned_frac}")
        if self.recluster_proto_shift <= 0:
            raise ValueError(f"recluster_proto_shift must be positive, "
                             f"got {self.recluster_proto_shift}")
        if self.eig_floor <= 0:
            raise ValueError(f"eig_floor must be positive, "
                             f"got {self.eig_floor}")
        if self.aggregator not in AGGREGATORS:
            raise ValueError(f"aggregator must be one of {AGGREGATORS}, "
                             f"got {self.aggregator!r}")
        if not 0.0 <= self.trim_frac < 0.5:
            raise ValueError(f"trim_frac must be in [0, 0.5), "
                             f"got {self.trim_frac}")
        if self.mom_groups < 1:
            raise ValueError(f"mom_groups must be >= 1, "
                             f"got {self.mom_groups}")
        if self.drift_stat not in DRIFT_STATS:
            raise ValueError(f"drift_stat must be one of {DRIFT_STATS}, "
                             f"got {self.drift_stat!r}")
        if self.compute_dtype not in ("fp32", "bf16"):
            raise ValueError(f"compute_dtype must be 'fp32' or 'bf16', "
                             f"got {self.compute_dtype!r}")
        if self.directory_dtype not in quant.DIRECTORY_DTYPES:
            raise ValueError(f"directory_dtype must be one of "
                             f"{quant.DIRECTORY_DTYPES}, "
                             f"got {self.directory_dtype!r}")


@dataclasses.dataclass(frozen=True)
class MembershipState:
    """The cluster directory: signature table + prototypes.

    Slots are fixed at ``capacity``; ``valid`` marks occupied ones and
    ``labels`` holds cluster ids (``-1`` = unassigned bucket / empty
    slot).  ``protos0``/``counts`` snapshot the prototypes at the last
    (re)cluster — the reference the drift statistic measures against.
    Arrays are jnp on the device backends, numpy on the reference.

    ``protos``/``protos0`` live in ``MembershipConfig.directory_dtype``
    (f32 exact, bf16 or int8 quantized); ``proto_scales`` /
    ``proto0_scales`` carry the per-prototype symmetric int8 scales
    (``None`` for f32/bf16).  ``directory_bytes`` is the resident
    serving-directory footprint the quantized dtypes shrink.
    """

    lam: jax.Array | np.ndarray        # (cap, k) member spectra
    v: jax.Array | np.ndarray          # (cap, d, k) member eigenvectors
    labels: jax.Array | np.ndarray     # (cap,) i32, -1 = unassigned/empty
    valid: jax.Array | np.ndarray      # (cap,) bool
    protos: jax.Array | np.ndarray     # (T, d, d) directory-dtype table
    counts: jax.Array | np.ndarray     # (T,) members per cluster
    protos0: jax.Array | np.ndarray    # (T, d, d) snapshot at last cluster
    n_clusters: int
    n_reclusters: int = 0
    proto_scales: jax.Array | np.ndarray | None = None   # (T,) int8 scales
    proto0_scales: jax.Array | np.ndarray | None = None

    @property
    def capacity(self) -> int:
        return int(self.lam.shape[0])

    @property
    def directory_bytes(self) -> int:
        """Resident bytes of the serving directory (table + scales)."""
        return quant.directory_nbytes(self.protos, self.proto_scales)

    @property
    def protos_f32(self) -> jax.Array | np.ndarray:
        """The dequantized ``(T, d, d)`` prototype view (f32)."""
        return quant.dequantize_directory(self.protos, self.proto_scales)

    @property
    def n_members(self) -> int:
        return int(np.asarray(self.valid).sum())

    @property
    def n_unassigned(self) -> int:
        va, lb = np.asarray(self.valid), np.asarray(self.labels)
        return int((va & (lb < 0)).sum())


@dataclasses.dataclass(frozen=True)
class AssignResult:
    """One arrival wave's verdict: labels (-1 = unassigned), the full
    affinity rows, and the confidence margins."""

    labels: jax.Array | np.ndarray     # (B,) i32
    affinity: jax.Array | np.ndarray   # (B, T)
    margin: jax.Array | np.ndarray     # (B,)


# ---------------------------------------------------------------------------
# Device directory primitives (shared by the jnp and pallas backends)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_clusters",))
def _protos_from_table(v, labels, valid, *, n_clusters: int):
    """Per-cluster mean projector from the live table rows."""
    member = ((labels[:, None] == jnp.arange(n_clusters)[None])
              & valid[:, None]).astype(jnp.float32)          # (cap, T)
    counts = member.sum(axis=0)
    outer = jnp.einsum("cdk,cek->cde", v, v)                 # (cap, d, d)
    protos = jnp.einsum("ct,cde->tde", member, outer)
    return protos / jnp.maximum(counts, 1.0)[:, None, None], counts


@partial(jax.jit, static_argnames=("n_clusters", "aggregator", "trim_frac",
                                   "mom_groups"))
def _protos_from_table_robust(v, labels, valid, *, n_clusters: int,
                              aggregator: str, trim_frac: float,
                              mom_groups: int):
    """Resistant per-cluster prototype statistics over member projectors.

    "trimmed": per coordinate of the flattened ``V_i V_i^T``, drop the
    ``floor(m * trim_frac)`` smallest and largest member values and
    average the rest — bounded influence for up to a ``trim_frac``
    fraction of Byzantine members per cluster.

    "medians": members are split round-robin (by live-slot rank) into
    ``mom_groups`` groups; the prototype is the coordinate-wise median
    of the group means — resists corruption while fewer than half the
    occupied groups are poisoned.

    Order statistics do not stream, so this is the *windowed recompute*
    the resistant admit/evict paths pay; one ``lax.map`` over clusters
    keeps peak memory at one (cap, d*d) sort per cluster.
    """
    cap, d, _k = v.shape
    member = (labels[:, None] == jnp.arange(n_clusters)[None]) \
        & valid[:, None]                                     # (cap, T)
    counts = member.sum(axis=0).astype(jnp.float32)
    outer = jnp.einsum("cdk,cek->cde", v, v).reshape(cap, d * d)

    def trimmed(mem_t):
        m = mem_t.sum().astype(jnp.int32)
        g = jnp.floor(m.astype(jnp.float32) * trim_frac).astype(jnp.int32)
        # non-members sort to the top end; kept ranks stay below m - g
        s = jnp.sort(jnp.where(mem_t[:, None], outer, jnp.inf), axis=0)
        rank = jnp.arange(cap, dtype=jnp.int32)[:, None]
        keep = (rank >= g) & (rank < m - g)
        kept = jnp.where(keep, s, 0.0)                       # inf never kept
        return kept.sum(axis=0) / jnp.maximum(m - 2 * g, 1)

    def medians(mem_t):
        rank = jnp.cumsum(mem_t) - 1                         # rank among live
        gid = jnp.where(mem_t, rank % mom_groups, mom_groups)
        onehot = (gid[:, None] == jnp.arange(mom_groups)[None]
                  ).astype(jnp.float32)                      # (cap, G)
        gcnt = onehot.sum(axis=0)                            # (G,)
        gsum = onehot.T @ jnp.where(mem_t[:, None], outer, 0.0)
        gmean = gsum / jnp.maximum(gcnt, 1.0)[:, None]
        nv = (gcnt > 0).sum().astype(jnp.int32)
        s = jnp.sort(jnp.where((gcnt > 0)[:, None], gmean, jnp.inf), axis=0)
        lo = jnp.clip((nv - 1) // 2, 0, mom_groups - 1)
        hi = jnp.clip(nv // 2, 0, mom_groups - 1)
        med = (jnp.take(s, lo, axis=0) + jnp.take(s, hi, axis=0)) / 2.0
        return jnp.where(nv > 0, med, 0.0)

    one = trimmed if aggregator == "trimmed" else medians
    protos = jax.lax.map(one, member.T)                      # (T, d*d)
    return protos.reshape(n_clusters, d, d).astype(jnp.float32), counts


def _apply_floors(labels, best, margin, affinity_floor, margin_floor):
    """The unassigned-bucket rule, shared by every device verdict path
    (the numpy backend keeps an independent host reference on purpose —
    backend agreement is parity-TESTED, not shared-by-construction)."""
    out = (best < affinity_floor) | (margin < margin_floor)
    return jnp.where(out, UNASSIGNED, labels).astype(jnp.int32)


def _verdict_from_affinity(aff, affinity_floor, margin_floor):
    """``(B, T)`` affinity rows -> ``(labels, margin)`` with floor
    bucketing — same argmax/margin semantics as ``assign_ref`` and the
    fused kernel, for callers that already hold the affinity rows (the
    sharded directory path)."""
    labels = jnp.argmax(aff, axis=1).astype(jnp.int32)
    best = jnp.max(aff, axis=1)
    if aff.shape[1] == 1:
        margin = best
    else:
        cols = jnp.arange(aff.shape[1], dtype=jnp.int32)
        margin = best - jnp.max(
            jnp.where(cols[None] == labels[:, None], -jnp.inf, aff),
            axis=1)
    return _apply_floors(labels, best, margin, affinity_floor,
                         margin_floor), margin


def _assign_device(v_wave, protos, counts, affinity_floor, margin_floor,
                   *, scales=None, impl: str, compute_dtype: str,
                   interpret: bool | None):
    # NOT jitted at this level: the pallas path resolves tile sizes
    # through the tuning cache (a host-side lookup) before its own jit.
    if impl == "pallas":
        from repro.kernels.assign import ops as assign_ops

        aff, labels, margin = assign_ops.assign(
            v_wave, protos, counts > 0, compute_dtype=compute_dtype,
            interpret=interpret, scales=scales)
        return _finish_assign_device(labels, aff, margin, affinity_floor,
                                     margin_floor)
    return _assign_device_ref(v_wave, protos, counts, scales,
                              affinity_floor, margin_floor)


@jax.jit
def _finish_assign_device(labels, aff, margin, affinity_floor, margin_floor):
    labels = _apply_floors(labels, jnp.max(aff, axis=1), margin,
                           affinity_floor, margin_floor)
    return labels, aff, margin


@jax.jit
def _assign_device_ref(v_wave, protos, counts, scales, affinity_floor,
                       margin_floor):
    protos = quant.dequantize_directory(protos, scales)
    aff, labels, margin = assign_ref(v_wave, protos, counts > 0)
    labels = _apply_floors(labels, jnp.max(aff, axis=1), margin,
                           affinity_floor, margin_floor)
    return labels, aff, margin


@jax.jit
def _wave_outer_sums(v_wave, labels, n_clusters_arr):
    """Per-cluster sums of admitted ``V V^T`` (unassigned rows drop out
    through the one-hot, exactly like the ``stack_layout`` scatter)."""
    t = n_clusters_arr.shape[0]
    onehot = (labels[:, None] == jnp.arange(t)[None]).astype(jnp.float32)
    outer = jnp.einsum("bdk,bek->bde", v_wave, v_wave)
    return jnp.einsum("bt,bde->tde", onehot, outer), onehot.sum(axis=0)


@partial(jax.jit, static_argnames=("sign",))
def _proto_update(protos, counts, delta, m, *, sign: float):
    """Streaming-mean prototype update: admit (+1) or evict (-1)."""
    new_counts = jnp.maximum(counts + sign * m, 0.0)
    num = protos * counts[:, None, None] + sign * delta
    upd = num / jnp.maximum(new_counts, 1.0)[:, None, None]
    return jnp.where((new_counts > 0)[:, None, None], upd,
                     jnp.zeros_like(upd)), new_counts


# Canonical home is ``core.similarity`` (the hierarchy global stage uses
# it too); re-exported here because it is directory-serving API surface.
signature_relevance = sim.signature_relevance


def _match_labels(new_labels: np.ndarray, old_labels: np.ndarray,
                  n_clusters: int) -> np.ndarray:
    """Greedy-overlap relabeling of a fresh cut onto the previous
    directory ids, so serving continuity survives a re-cluster (HAC cut
    ids are arbitrary).  Host-side — re-clusters are rare events.
    Canonical implementation: ``core.hierarchy.greedy_match_labels``."""
    from repro.core.hierarchy import greedy_match_labels

    return greedy_match_labels(new_labels, old_labels, n_clusters)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class MembershipEngine:
    """One object that owns online cluster-identity serving.

    Functional core, stateful shell: every lifecycle operation is a pure
    transition on a ``MembershipState``; the engine holds the current
    directory in ``self.state`` and replaces it in place, so a serving
    loop is ``engine.assign(...) -> engine.admit(...) ->
    engine.maybe_recluster()``.
    """

    def __init__(self, cfg: MembershipConfig | None = None):
        self.cfg = cfg or MembershipConfig()
        self.state: MembershipState | None = None

    # -- construction -------------------------------------------------------

    @classmethod
    def from_oneshot(cls, result, cfg: MembershipConfig | None = None,
                     capacity: int | None = None) -> "MembershipEngine":
        """Build the cluster directory from a ``OneShotResult``.

        The one-shot protocol already produced everything the directory
        needs: the per-user signatures (``result.lam``, ``result.v`` —
        the same ``(k x d)`` blocks users uploaded) and the GPS labels.
        """
        if getattr(result, "lam", None) is None or result.v is None:
            raise ValueError(
                "OneShotResult carries no signatures (lam/v) — run "
                "one_shot_clustering from this repo version, which "
                "returns them on every backend")
        eng = cls(cfg)
        labels = np.asarray(result.labels)
        eng.seed(result.lam, result.v, labels,
                 n_clusters=int(labels.max()) + 1, capacity=capacity)
        return eng

    def seed(self, lam, v, labels, n_clusters: int,
             capacity: int | None = None) -> MembershipState:
        """Initialize the directory from seed signatures + labels."""
        lam = np.asarray(lam, np.float32)
        v = np.asarray(v, np.float32)
        labels = np.asarray(labels, np.int32)
        n, k = lam.shape
        d = v.shape[1]
        cap = capacity or self.cfg.capacity or 2 * n
        if cap < n:
            raise ValueError(f"capacity {cap} < seed population {n}")
        if not 1 <= n_clusters:
            raise ValueError(f"n_clusters must be >= 1, got {n_clusters}")
        lam_t = np.zeros((cap, k), np.float32)
        v_t = np.zeros((cap, d, k), np.float32)
        lab_t = np.full((cap,), UNASSIGNED, np.int32)
        valid = np.zeros((cap,), bool)
        lam_t[:n], v_t[:n], lab_t[:n], valid[:n] = lam, v, labels, True
        if self.on_device:
            lam_t, v_t = jnp.asarray(lam_t), jnp.asarray(v_t)
            lab_t, valid = jnp.asarray(lab_t), jnp.asarray(valid)
        protos, counts = self._rebuild_protos(v_t, lab_t, valid, n_clusters)
        table, scales = self._quantize(protos)
        self.state = MembershipState(
            lam=lam_t, v=v_t, labels=lab_t, valid=valid, protos=table,
            counts=counts, protos0=table, n_clusters=n_clusters,
            proto_scales=scales, proto0_scales=scales)
        if obs.enabled():
            obs.gauge("directory_bytes", self.state.directory_bytes)
            obs.event("seed", n_members=n, n_clusters=n_clusters,
                      capacity=cap, backend=self.cfg.backend)
        return self.state

    @property
    def on_device(self) -> bool:
        return self.cfg.backend != "numpy"

    def _require_state(self) -> MembershipState:
        if self.state is None:
            raise ValueError("directory is empty — seed() or "
                             "from_oneshot() first")
        return self.state

    def _quantize(self, protos):
        """f32 prototypes -> (directory-dtype table, scales | None)."""
        return quant.quantize_directory(protos, self.cfg.directory_dtype)

    @staticmethod
    def _dequantize(st: MembershipState):
        return quant.dequantize_directory(st.protos, st.proto_scales)

    def _rebuild_protos(self, v, labels, valid, n_clusters: int):
        agg = self.cfg.aggregator
        if self.on_device:
            if agg == "mean":
                return _protos_from_table(v, labels, valid,
                                          n_clusters=n_clusters)
            return _protos_from_table_robust(
                v, labels, valid, n_clusters=n_clusters, aggregator=agg,
                trim_frac=self.cfg.trim_frac,
                mom_groups=self.cfg.mom_groups)
        if agg != "mean":
            return self._np_robust_protos(v, labels, valid, n_clusters)
        member = ((np.asarray(labels)[:, None] == np.arange(n_clusters))
                  & np.asarray(valid)[:, None]).astype(np.float32)
        counts = member.sum(axis=0)
        outer = np.einsum("cdk,cek->cde", v, v)
        protos = (np.einsum("ct,cde->tde", member, outer)
                  / np.maximum(counts, 1.0)[:, None, None])
        return protos.astype(np.float32), counts.astype(np.float32)

    def _np_robust_protos(self, v, labels, valid, n_clusters: int):
        """Host reference of the resistant aggregators — an independent
        implementation on purpose (backend agreement is parity-TESTED,
        not shared-by-construction, same contract as ``assign``)."""
        v = np.asarray(v, np.float32)
        labels, valid = np.asarray(labels), np.asarray(valid)
        d = v.shape[1]
        protos = np.zeros((n_clusters, d, d), np.float32)
        counts = np.zeros((n_clusters,), np.float32)
        for t in range(n_clusters):
            mem = np.flatnonzero((labels == t) & valid)
            counts[t] = len(mem)
            if not len(mem):
                continue
            outers = np.einsum("cdk,cek->cde", v[mem], v[mem]
                               ).reshape(len(mem), d * d)
            m = len(mem)
            if self.cfg.aggregator == "trimmed":
                g = int(np.floor(m * self.cfg.trim_frac))
                flat = np.sort(outers, axis=0)[g:m - g].mean(axis=0)
            else:                                            # medians
                gid = np.arange(m) % self.cfg.mom_groups
                gmeans = np.stack(
                    [outers[gid == j].mean(axis=0)
                     for j in range(self.cfg.mom_groups)
                     if (gid == j).any()])
                flat = np.median(gmeans, axis=0)
            protos[t] = flat.reshape(d, d)
        return protos, counts

    # -- assignment ---------------------------------------------------------

    def assign(self, lam, v) -> AssignResult:
        """Batched arrival wave -> labels + affinities + margins.

        ``lam (B, k)`` rides along for the subsequent ``admit`` (it is
        what the newcomer uploaded); the affinity itself needs only
        ``v (B, d, k)``.  One dispatch per wave on the device backends.
        """
        st = self._require_state()
        t0 = obs.now()
        with obs.span("membership.assign", backend=self.cfg.backend) as sp:
            res = self._assign(st, v)
            # labels alone gate the whole one-dispatch wave program, so
            # blocking on them times the full device computation without
            # paying three separate readiness walks
            sp.sync(res.labels)
        if obs.enabled():
            obs.observe("assign_latency_us", (obs.now() - t0) * 1e6)
            obs.count("membership.assign_waves")
            # compare on the host: a jnp == here would be a full jax
            # dispatch per wave, dwarfing the rest of the telemetry
            labels_np = np.asarray(res.labels)
            obs.event("assign_wave", n=int(labels_np.shape[0]),
                      n_unassigned=int((labels_np == UNASSIGNED).sum()),
                      backend=self.cfg.backend)
        return res

    def _assign(self, st: MembershipState, v) -> AssignResult:
        if self.on_device:
            labels, aff, margin = _assign_device(
                jnp.asarray(v, jnp.float32), st.protos, st.counts,
                self.cfg.affinity_floor, self.cfg.margin_floor,
                scales=st.proto_scales,
                impl=("pallas" if self.cfg.backend == "pallas" else "jnp"),
                compute_dtype=self.cfg.compute_dtype,
                interpret=self.cfg.interpret)
            return AssignResult(labels=labels, affinity=aff, margin=margin)
        v = np.asarray(v, np.float32)
        k = v.shape[-1]
        protos = self._dequantize(st)
        aff = np.einsum("bdk,tde,bek->bt", v, protos, v) / k
        aff = np.where(st.counts > 0, aff, -np.inf)
        labels = aff.argmax(axis=1).astype(np.int32)
        best = aff.max(axis=1)
        if st.n_clusters == 1:
            margin = best.copy()
        else:
            cols = np.arange(st.n_clusters)
            margin = best - np.where(cols[None] == labels[:, None],
                                     -np.inf, aff).max(axis=1)
        out = (best < self.cfg.affinity_floor) | \
              (margin < self.cfg.margin_floor)
        labels = np.where(out, UNASSIGNED, labels).astype(np.int32)
        return AssignResult(labels=labels, affinity=aff, margin=margin)

    def assign_sharded(self, lam, v, mesh=None,
                       axis: str = "data") -> AssignResult:
        """``assign`` with the DIRECTORY sharded over a mesh axis: each
        device scores the wave against its local prototype shard, one
        all_gather assembles the ``(B, T)`` affinity rows, and the
        argmax/margin/floor logic runs replicated — bitwise the same
        verdict as the single-device path.  ``T`` must divide the axis.
        """
        st = self._require_state()
        if not self.on_device:
            raise ValueError("assign_sharded needs a device backend "
                             "('jnp'/'pallas'); numpy is host-only")
        mesh = mesh or make_user_mesh(axis)
        n_dev = mesh.shape[axis]
        if st.n_clusters % n_dev:
            raise ValueError(f"n_clusters={st.n_clusters} not divisible "
                             f"by mesh axis {axis!r} of size {n_dev}")
        floors = (self.cfg.affinity_floor, self.cfg.margin_floor)

        def body(v_wave, protos, counts):
            k = v_wave.shape[-1]
            aff_l = jnp.einsum("bdk,tde,bek->bt", v_wave, protos,
                               v_wave) / k                  # (B, T_local)
            aff_l = jnp.where((counts > 0)[None, :], aff_l, -jnp.inf)
            aff = jnp.moveaxis(
                jax.lax.all_gather(aff_l.T, axis, tiled=True), 0, 1)
            labels, margin = _verdict_from_affinity(aff, *floors)
            return labels, aff, margin

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(), P(axis), P(axis)),
                           out_specs=(P(), P(), P()), check_vma=False)
        with mesh:
            v_w = jax.device_put(jnp.asarray(v, jnp.float32),
                                 NamedSharding(mesh, P()))
            # dequantize before sharding: the per-shard einsum path has no
            # in-kernel dequant epilogue, and scales would need their own
            # matching shard layout
            protos = jax.device_put(jnp.asarray(self._dequantize(st)),
                                    NamedSharding(mesh, P(axis)))
            counts = jax.device_put(st.counts, NamedSharding(mesh, P(axis)))
            labels, aff, margin = jax.jit(fn)(v_w, protos, counts)
        return AssignResult(labels=labels, affinity=aff, margin=margin)

    # -- lifecycle ----------------------------------------------------------

    def _free_slots(self, n: int) -> np.ndarray:
        st = self._require_state()
        free = np.flatnonzero(~np.asarray(st.valid))
        if len(free) < n:
            raise ValueError(
                f"directory full: {n} arrivals but only {len(free)} free "
                f"slots of {st.capacity} — grow MembershipConfig.capacity")
        return free[:n].astype(np.int32)

    def admit(self, lam, v, labels) -> np.ndarray:
        """Append an assigned wave to the table (streaming-mean prototype
        update; unassigned rows join the table but no prototype).
        Resistant aggregators cannot down-/up-date order statistics in
        O(1), so they pay a windowed recompute over the live table
        instead.  Returns the occupied slot indices (for ``evict``)."""
        with obs.span("membership.admit") as sp:
            slots = self._admit(lam, v, labels)
            sp.sync(self.state.protos)
        if obs.enabled():
            st = self.state
            obs.count("membership.admits", len(slots))
            obs.gauge("directory_bytes", st.directory_bytes)
            obs.event("admit", n=len(slots), slots=slots,
                      n_members=int(st.n_members))
        return slots

    def _admit(self, lam, v, labels) -> np.ndarray:
        st = self._require_state()
        lam = np.asarray(lam, np.float32)
        slots = self._free_slots(lam.shape[0])
        labels = np.asarray(labels, np.int32)
        streaming = self.cfg.aggregator == "mean"
        if self.on_device:
            v_w = jnp.asarray(v, jnp.float32)
            lab_w = jnp.asarray(labels)
            sl = jnp.asarray(slots)
            lam_t = st.lam.at[sl].set(jnp.asarray(lam))
            v_t = st.v.at[sl].set(v_w)
            lab_t = st.labels.at[sl].set(lab_w)
            valid = st.valid.at[sl].set(True)
            if streaming:
                delta, m = _wave_outer_sums(v_w, lab_w, st.counts)
                protos, counts = _proto_update(self._dequantize(st),
                                               st.counts, delta, m,
                                               sign=1.0)
            else:
                protos, counts = self._rebuild_protos(v_t, lab_t, valid,
                                                      st.n_clusters)
            table, scales = self._quantize(protos)
            self.state = dataclasses.replace(
                st, lam=lam_t, v=v_t, labels=lab_t, valid=valid,
                protos=table, counts=counts, proto_scales=scales)
            return slots
        v = np.asarray(v, np.float32)
        lam_t, v_t = st.lam.copy(), st.v.copy()
        lab_t, valid = st.labels.copy(), st.valid.copy()
        lam_t[slots], v_t[slots], lab_t[slots], valid[slots] = \
            lam, v, labels, True
        if streaming:
            protos, counts = self._np_proto_shift(st, v, labels, +1.0)
        else:
            protos, counts = self._rebuild_protos(v_t, lab_t, valid,
                                                  st.n_clusters)
        table, scales = self._quantize(protos)
        self.state = dataclasses.replace(
            st, lam=lam_t, v=v_t, labels=lab_t, valid=valid,
            protos=table, counts=counts, proto_scales=scales)
        return slots

    def evict(self, slots) -> None:
        """Masked removal of table slots (churn): free the rows and
        down-date the prototypes by the departing members' projectors."""
        with obs.span("membership.evict") as sp:
            self._evict(slots)
            sp.sync(self.state.protos)
        if obs.enabled():
            st = self.state
            obs.count("membership.evicts", len(np.asarray(slots)))
            obs.gauge("directory_bytes", st.directory_bytes)
            obs.event("evict", n=len(np.asarray(slots)),
                      slots=np.asarray(slots),
                      n_members=int(st.n_members))

    def _evict(self, slots) -> None:
        st = self._require_state()
        slots = np.asarray(slots, np.int32)
        if len(np.unique(slots)) != len(slots):
            # a repeated slot would down-date the prototype twice for one
            # departure, silently corrupting the streaming mean
            raise ValueError(f"duplicate slots in evict: {slots.tolist()}")
        occupied = np.asarray(st.valid)[slots]
        if not occupied.all():
            raise ValueError(f"evicting empty slots "
                             f"{slots[~occupied].tolist()}")
        labels_out = np.asarray(st.labels)[slots]
        streaming = self.cfg.aggregator == "mean"
        if self.on_device:
            sl = jnp.asarray(slots)
            lab_t = st.labels.at[sl].set(UNASSIGNED)
            valid = st.valid.at[sl].set(False)
            if streaming:
                delta, m = _wave_outer_sums(st.v[sl],
                                            jnp.asarray(labels_out),
                                            st.counts)
                protos, counts = _proto_update(self._dequantize(st),
                                               st.counts, delta, m,
                                               sign=-1.0)
            else:
                protos, counts = self._rebuild_protos(st.v, lab_t, valid,
                                                      st.n_clusters)
            table, scales = self._quantize(protos)
            self.state = dataclasses.replace(
                st, labels=lab_t, valid=valid,
                protos=table, counts=counts, proto_scales=scales)
            return
        lab_t, valid = st.labels.copy(), st.valid.copy()
        lab_t[slots], valid[slots] = UNASSIGNED, False
        if streaming:
            protos, counts = self._np_proto_shift(
                st, np.asarray(st.v)[slots], labels_out, -1.0)
        else:
            protos, counts = self._rebuild_protos(st.v, lab_t, valid,
                                                  st.n_clusters)
        table, scales = self._quantize(protos)
        self.state = dataclasses.replace(st, labels=lab_t, valid=valid,
                                         protos=table, counts=counts,
                                         proto_scales=scales)

    def _np_proto_shift(self, st: MembershipState, v: np.ndarray,
                        labels: np.ndarray, sign: float):
        onehot = (labels[:, None] == np.arange(st.n_clusters)
                  ).astype(np.float32)
        outer = np.einsum("bdk,bek->bde", v, v)
        delta = np.einsum("bt,bde->tde", onehot, outer)
        m = onehot.sum(axis=0)
        counts = np.maximum(st.counts + sign * m, 0.0)
        num = self._dequantize(st) * st.counts[:, None, None] + sign * delta
        protos = np.where((counts > 0)[:, None, None],
                          num / np.maximum(counts, 1.0)[:, None, None],
                          0.0).astype(np.float32)
        return protos, counts.astype(np.float32)

    # -- drift statistics + re-cluster --------------------------------------

    def drift_stats(self) -> dict:
        """The two trigger statistics: unassigned fraction of the live
        table and the relative prototype Frobenius shift since the last
        (re)cluster — the worst per-cluster shift by default, the median
        under ``drift_stat="median"`` (one poisoned prototype then
        cannot trip re-cluster thrash on its own)."""
        st = self._require_state()
        n = max(st.n_members, 1)
        p = np.asarray(quant.dequantize_directory(st.protos,
                                                  st.proto_scales))
        p0 = np.asarray(quant.dequantize_directory(st.protos0,
                                                   st.proto0_scales))
        shift = np.linalg.norm((p - p0).reshape(st.n_clusters, -1), axis=1)
        base = np.maximum(
            np.linalg.norm(p0.reshape(st.n_clusters, -1), axis=1), 1e-6)
        rel = shift / base
        stat = (np.median(rel) if self.cfg.drift_stat == "median"
                else rel.max())
        stats = {
            "unassigned_frac": st.n_unassigned / n,
            "proto_shift": float(stat),
            "proto_shift_max": float(rel.max()),
            "n_members": st.n_members,
            "n_reclusters": st.n_reclusters,
        }
        if obs.enabled():
            obs.gauge("unassigned_frac", stats["unassigned_frac"])
            obs.gauge("proto_shift", stats["proto_shift"])
        return stats

    def should_recluster(self) -> bool:
        s = self.drift_stats()
        return (s["unassigned_frac"] > self.cfg.recluster_unassigned_frac
                or s["proto_shift"] > self.cfg.recluster_proto_shift)

    def recluster(self, force: bool = False) -> bool:
        """Drift-triggered incremental re-cluster: HAC over the CURRENT
        table (unassigned bucket included) on the signature-only
        relevance matrix, via the ``ClusterEngine`` — numpy reference on
        the numpy backend, device NN-chain otherwise.  New cut ids are
        greedily matched onto the previous labels for serving
        continuity.  Returns whether a re-cluster ran."""
        if not force:
            stats = self.drift_stats()
            tripped = (
                stats["unassigned_frac"] > self.cfg.recluster_unassigned_frac
                or stats["proto_shift"] > self.cfg.recluster_proto_shift)
            if not tripped:
                return False
            obs.event("drift_trip", **stats)
        st = self._require_state()
        live = np.flatnonzero(np.asarray(st.valid))
        if len(live) < st.n_clusters:
            raise ValueError(f"cannot cut {st.n_clusters} clusters from "
                             f"{len(live)} members")
        lam_m = jnp.asarray(np.asarray(st.lam)[live])
        v_m = jnp.asarray(np.asarray(st.v)[live])
        big_r = signature_relevance(lam_m, v_m, self.cfg.eig_floor)
        cengine = ClusterEngine(ClusterConfig(
            backend="numpy" if self.cfg.backend == "numpy" else "jnp",
            linkage=self.cfg.linkage))
        with obs.span("membership.recluster", n_members=len(live)) as sp:
            fresh = np.asarray(cengine.labels(big_r, st.n_clusters))
            matched = _match_labels(fresh, np.asarray(st.labels)[live],
                                    st.n_clusters)
            lab_t = np.asarray(st.labels).copy()
            lab_t[live] = matched
            labels = jnp.asarray(lab_t) if self.on_device else lab_t
            protos, counts = self._rebuild_protos(st.v, labels, st.valid,
                                                  st.n_clusters)
            table, scales = self._quantize(protos)
            sp.sync((labels, table, counts))
        self.state = dataclasses.replace(
            st, labels=labels, protos=table, counts=counts,
            protos0=table, n_reclusters=st.n_reclusters + 1,
            proto_scales=scales, proto0_scales=scales)
        if obs.enabled():
            before = np.asarray(st.labels)[live]
            obs.count("recluster_events")
            obs.event("recluster", n_members=len(live), forced=bool(force),
                      label_agreement=float((matched == before).mean()),
                      n_reclusters=int(self.state.n_reclusters))
        return True

    def maybe_recluster(self) -> bool:
        """The serve-loop hook: re-cluster iff a drift trigger tripped."""
        return self.recluster(force=False)
