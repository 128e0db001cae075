"""MT-HFL training loop (paper Algorithm 1) — fused and reference paths.

Given per-user datasets and a cluster assignment (from the one-shot
algorithm, the random baseline, or the oracle), run:

  for each global round r in [G]:
    for each LPS t in [T]:                 # clusters
      for each local round:
        every client runs ``local_steps`` optimizer steps from the LPS model
        LPS FedAvg-aggregates its clients
    GPS averages the COMMON layers across LPSs, broadcasts back

The model is pluggable via a ``TaskModel`` bundle (init/loss/accuracy +
common-layer predicate), so the same trainer drives the paper's CNN/MLP and
the transformer zoo.

Two executions of the same semantics:

* **Fused** (default when the per-cluster models stack): all clusters are
  padded into one ``(T, C_max, ...)`` super-stack with a membership mask,
  ``masked_lps_round`` is vmapped over the cluster axis, local rounds run
  under ``lax.scan``, and the GPS common-layer average folds into the same
  program — ONE jit dispatch per global round (``cfg.scan_rounds`` makes it
  one for the whole run).  ``cfg.backend = "shard_map"`` shards the cluster
  axis over a device mesh (empty padding clusters square off the axis), the
  same backend-selection idiom as ``core/engine.py``.
* **Reference** (``fused=False``, or automatic fallback when cluster models
  do not stack): the retained host loop over clusters — the parity oracle
  for ``tests/test_trainer_parity.py`` and the baseline for
  ``benchmarks/bench_trainer.py``.

Both paths draw batches from the SAME per-cluster key streams, derived from
``cfg.seed`` and the cluster's (sorted) member user ids — never from a
shared mutable RNG — so results are independent of cluster iteration order
and the two paths train on bit-identical batches.

Masking rules (identical in both paths): an empty cluster never trains, has
weight 0 in the GPS average (it still receives the common broadcast), and
reports NaN accuracy / train loss; a misassigned user still trains against
the wrong cluster head (exactly the degradation the paper measures).
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.fed import client as fed_client
from repro.fed import hierarchy as hier
from repro.fed import partition as part

PyTree = Any

__all__ = ["TaskModel", "MTHFLConfig", "MTHFLHistory", "train_mthfl",
           "TRAINER_BACKENDS"]

TRAINER_BACKENDS = ("jnp", "shard_map")


@dataclasses.dataclass(frozen=True)
class TaskModel:
    """Everything the trainer needs to know about one task's model."""

    init: Callable[[jax.Array], PyTree]
    loss_fn: Callable[[PyTree, dict], jax.Array]
    accuracy: Callable[[PyTree, np.ndarray, np.ndarray], float]
    is_common: part.PathPred


@dataclasses.dataclass(frozen=True)
class MTHFLConfig:
    global_rounds: int = 10
    local_rounds: int = 2          # LPS-level FedAvg rounds per global round
    local_steps: int = 10          # client optimizer steps per local round
    batch_size: int = 32
    client: fed_client.ClientConfig = fed_client.ClientConfig()
    seed: int = 0
    backend: str = "jnp"           # fused execution: jnp | shard_map
    mesh_axis: str = "clusters"    # mesh axis the cluster dim shards over
    scan_rounds: bool = False      # fused: lax.scan the GLOBAL rounds too
    dropout_frac: float = 0.0      # per-global-round straggler/dropout rate


@dataclasses.dataclass
class MTHFLHistory:
    """Per-global-round, per-cluster test accuracy + mean train loss.

    Empty (memberless) clusters are NaN in both columns.  ``fused`` records
    which execution path produced the history.
    """

    accuracy: np.ndarray           # (G, T)
    train_loss: np.ndarray         # (G, T)
    labels: np.ndarray             # (N,) cluster assignment used
    fused: bool = False


# ---------------------------------------------------------------------------
# Shared setup: cluster membership, label remapping, per-cluster key streams
# ---------------------------------------------------------------------------

def _cluster_base_key(seed: int, member_uids: Sequence[int],
                      t: int) -> jax.Array:
    """Per-cluster PRNG stream root.

    Derived from ``seed`` and the SORTED member user ids, so the stream a
    group of users trains under is invariant to how clusters happen to be
    numbered (determinism under cluster relabeling); an empty cluster falls
    back to its index, which only seeds its unused init params.
    """
    key = jax.random.PRNGKey(seed)
    if len(member_uids):
        for uid in sorted(int(u) for u in member_uids):
            key = jax.random.fold_in(key, uid + 1)
    else:
        key = jax.random.fold_in(key, 0)
        key = jax.random.fold_in(key, t)
    return key


@dataclasses.dataclass
class _ClusterSetup:
    members: list[list]            # per-cluster member UserData lists
    datasets: list[list[tuple]]    # per-cluster [(x, y_local)] pairs
    uids: list[list[int]]
    n_samples: list[list[int]]
    cluster_weights: list[float]   # total samples; 0.0 for empty clusters
    init_keys: list[jax.Array]
    data_keys: list[jax.Array]
    cluster_classes: list[list[int]]


def _setup_clusters(users, labels: np.ndarray, n_clusters: int, seed: int,
                    cluster_classes) -> _ClusterSetup:
    # Per-user training labels remapped to the assigned cluster's class
    # list.  Each LPS t is dedicated to one task; under random clustering
    # misplaced users train against the wrong head, which is the
    # degradation the paper's baseline exhibits.  If the caller does not
    # pin ``cluster_classes``, infer them from the majority task of each
    # cluster's members.
    members = [[u for u, l in zip(users, labels) if l == t]
               for t in range(n_clusters)]
    if cluster_classes is None:
        inferred: list[list[int]] = []
        for t in range(n_clusters):
            counts: dict[tuple, int] = {}
            for u in members[t]:
                key_t = tuple(u.task_classes)
                counts[key_t] = counts.get(key_t, 0) + 1
            inferred.append(list(max(counts, key=counts.get)) if counts
                            else list(range(10)))
        cluster_classes = inferred
    else:
        cluster_classes = [list(c) for c in cluster_classes]

    def local_y(u, t):
        lut = {c: i for i, c in enumerate(cluster_classes[t])}
        return np.asarray([lut.get(int(c), 0) for c in u.y], dtype=np.int32)

    datasets = [[(u.x, local_y(u, t)) for u in members[t]]
                for t in range(n_clusters)]
    base = [_cluster_base_key(seed, [u.user_id for u in members[t]], t)
            for t in range(n_clusters)]
    return _ClusterSetup(
        members=members,
        datasets=datasets,
        uids=[[int(u.user_id) for u in members[t]]
              for t in range(n_clusters)],
        n_samples=[[int(u.n) for u in members[t]] for t in range(n_clusters)],
        cluster_weights=[float(sum(u.n for u in members[t]))
                         for t in range(n_clusters)],
        init_keys=[jax.random.fold_in(k, 0) for k in base],
        data_keys=[jax.random.fold_in(k, 1) for k in base],
        cluster_classes=cluster_classes,
    )


def _stackable(params_list: Sequence[PyTree]) -> bool:
    """True iff every cluster's params share structure, shapes and dtypes —
    the precondition for the ``(T, ...)`` super-stack."""
    ref = jax.tree.structure(params_list[0])
    ref_leaves = [(l.shape, l.dtype) for l in jax.tree.leaves(params_list[0])]
    for p in params_list[1:]:
        if jax.tree.structure(p) != ref:
            return False
        if [(l.shape, l.dtype) for l in jax.tree.leaves(p)] != ref_leaves:
            return False
    return True


# ---------------------------------------------------------------------------
# Fused path: one device-resident program per global round (or per run)
# ---------------------------------------------------------------------------

def _round_body(p_stack, g, x, y, n_per, uids, mask, dkeys, cluster_w,
                part_rate, *,
                loss_fn, optimizer, clip_norm, steps, batch_size,
                local_rounds, is_common, axis):
    """One GLOBAL round, traceable: scan local rounds (each local round =
    masked LPS round vmapped over the cluster axis), then the in-jit GPS
    common-layer average.  ``axis`` names the mesh axis when the cluster
    dim is sharded under shard_map.

    ``part_rate`` is a TRACED dropout scalar: a per-global-round keyed
    participation draw (``fed_client.participation_mask``) folds into
    the existing membership-mask weighting, so stragglers/dropouts cost
    no retrace — at rate 0.0 the mask is untouched and the program is
    bit-identical to the no-dropout one.  A fully-dropped cluster keeps
    its params (``masked_lps_round``'s empty-mask path) and reports a
    NaN round loss, exactly like an empty cluster."""

    def local_round(p, l):
        def per_cluster(p_t, dk, x_t, y_t, n_t, uid_t, m_t):
            rk_g = jax.random.fold_in(dk, g)
            m_eff = m_t * fed_client.participation_mask(rk_g, uid_t,
                                                        part_rate)
            rk = jax.random.fold_in(rk_g, l)
            return fed_client.masked_lps_round(
                p_t, x_t, y_t, n_t, uid_t, m_eff, rk, loss_fn, optimizer,
                clip_norm, steps, batch_size)

        return jax.vmap(per_cluster)(p, dkeys, x, y, n_per, uids, mask)

    p_stack, losses = jax.lax.scan(local_round, p_stack,
                                   jnp.arange(local_rounds))
    mean_loss = jnp.mean(losses, axis=0)                     # (T,)
    p_stack = hier.gps_aggregate_stacked(p_stack, cluster_w, is_common,
                                         axis=axis)
    return p_stack, mean_loss


def _run_scanned(p_stack, x, y, n_per, uids, mask, dkeys, cluster_w,
                 part_rate, *, global_rounds, **kw):
    """The whole run in one program: scan ``_round_body`` over the global
    rounds, emitting each round's params for host-side evaluation."""

    def body(p, g):
        p, loss = _round_body(p, g, x, y, n_per, uids, mask, dkeys,
                              cluster_w, part_rate, **kw)
        return p, (loss, p)

    _, (losses, stacks) = jax.lax.scan(body, p_stack,
                                       jnp.arange(global_rounds))
    return losses, stacks                                    # (G, T), (G,T,…)


_STATICS = ("loss_fn", "optimizer", "clip_norm", "steps", "batch_size",
            "local_rounds", "is_common")

_fused_global_round = partial(jax.jit, static_argnames=_STATICS)(
    partial(_round_body, axis=None))
_fused_run = partial(jax.jit, static_argnames=_STATICS + ("global_rounds",))(
    partial(_run_scanned, axis=None))


@functools.lru_cache(maxsize=64)
def _sharded_round_fn(mesh: Mesh, axis: str, statics_vals: tuple):
    """shard_map + jit of one global round, cached so repeated train calls
    with the same mesh/model bundle reuse the compiled program (Mesh and
    the static values hash by value / identity)."""
    statics = dict(zip(_STATICS, statics_vals))
    spec_c = P(axis)
    return jax.jit(jax.shard_map(
        partial(_round_body, **statics, axis=axis), mesh=mesh,
        in_specs=(spec_c, P()) + (spec_c,) * 7 + (P(),),
        out_specs=(spec_c, spec_c), check_vma=False))


@functools.lru_cache(maxsize=64)
def _sharded_run_fn(mesh: Mesh, axis: str, statics_vals: tuple,
                    global_rounds: int):
    statics = dict(zip(_STATICS, statics_vals))
    spec_c = P(axis)
    return jax.jit(jax.shard_map(
        partial(_run_scanned, **statics, axis=axis,
                global_rounds=global_rounds),
        mesh=mesh, in_specs=(spec_c,) * 8 + (P(),),
        out_specs=(P(None, axis), P(None, axis)), check_vma=False))


def _pad_clusters(stacks: PyTree, n_pad: int) -> PyTree:
    """Append ``n_pad`` dummy clusters (first cluster repeated) so the
    cluster axis divides the mesh; their mask/weights are zeroed by the
    caller so they never train and never contribute to the GPS average."""
    if n_pad == 0:
        return stacks
    return jax.tree.map(
        lambda l: jnp.concatenate(
            [l, jnp.repeat(l[:1], n_pad, axis=0)], axis=0), stacks)


def _train_fused(users, labels, models, eval_sets, cfg: MTHFLConfig,
                 setup: _ClusterSetup, lps_params: list[PyTree],
                 mesh: Mesh | None) -> MTHFLHistory:
    n_clusters = len(models)
    c_max = max(1, max(len(m) for m in setup.members))
    all_members = [u for ms in setup.members for u in ms]
    n_max = max(1, max((int(u.n) for u in all_members), default=1))
    sample_shape = (all_members[0].x.shape[1:] if all_members else (1,))

    # Membership layout of the super-stack comes from the label vector via
    # jnp ops instead of host python loops (train_mthfl's entry asarray is
    # the one remaining host sync — member bookkeeping needs it).  The
    # slot order matches _setup_clusters' member lists (stable original
    # user order), so the ragged x/y copies below land in the same cells.
    labels_dev = jnp.asarray(labels, jnp.int32)
    rows, slot, mask = part.stack_layout(labels_dev, n_clusters, c_max)
    uid_all = jnp.asarray([int(u.user_id) for u in users], jnp.int32)
    n_all = jnp.asarray([float(u.n) for u in users], jnp.float32)
    uid_stack = jnp.zeros((n_clusters, c_max), jnp.int32
                          ).at[rows, slot].set(uid_all)
    n_stack = jnp.ones((n_clusters, c_max), jnp.float32  # pads: n=1, masked
                       ).at[rows, slot].set(n_all)

    x_np = np.zeros((n_clusters, c_max, n_max) + tuple(sample_shape),
                    np.float32)
    y_np = np.zeros((n_clusters, c_max, n_max), np.int32)
    for t in range(n_clusters):
        for c, ((x, y), n) in enumerate(zip(setup.datasets[t],
                                            setup.n_samples[t])):
            x_np[t, c, :n] = x
            y_np[t, c, :n] = y

    p_stack = jax.tree.map(lambda *ls: jnp.stack(ls), *lps_params)
    data = dict(x=jnp.asarray(x_np), y=jnp.asarray(y_np),
                n_per=n_stack, uids=uid_stack,
                mask=mask,
                dkeys=jnp.stack(setup.data_keys),
                cluster_w=jnp.asarray(setup.cluster_weights, jnp.float32))
    statics = dict(loss_fn=models[0].loss_fn,
                   optimizer=fed_client._make_opt(cfg.client),
                   clip_norm=cfg.client.clip_norm, steps=cfg.local_steps,
                   batch_size=cfg.batch_size, local_rounds=cfg.local_rounds,
                   is_common=models[0].is_common)

    n_pad = 0
    if cfg.backend == "shard_map":
        axis = cfg.mesh_axis
        mesh = mesh or Mesh(np.asarray(jax.devices()), (axis,))
        n_dev = mesh.shape[axis]
        n_pad = (-n_clusters) % n_dev
        p_stack = _pad_clusters(p_stack, n_pad)
        data = {k: _pad_clusters(v, n_pad) for k, v in data.items()}
        # Padding clusters must be inert: no members, no GPS weight.
        data["mask"] = data["mask"].at[n_clusters:].set(0.0)
        data["cluster_w"] = data["cluster_w"].at[n_clusters:].set(0.0)
        # Shard the cluster axis NOW: round outputs come back with this
        # sharding, so placing the inputs up front keeps every round on one
        # compiled signature (no host->device reshard between rounds).
        shard_c = NamedSharding(mesh, P(axis))
        p_stack = jax.device_put(p_stack, shard_c)
        data = {k: jax.device_put(v, shard_c) for k, v in data.items()}
        statics_vals = tuple(statics[k] for k in _STATICS)
        round_fn = _sharded_round_fn(mesh, axis, statics_vals)
        run_fn = _sharded_run_fn(mesh, axis, statics_vals,
                                 cfg.global_rounds)
    else:
        body_statics = {k: statics[k] for k in _STATICS}
        round_fn = partial(_fused_global_round, **body_statics)
        run_fn = partial(_fused_run, **body_statics,
                         global_rounds=cfg.global_rounds)

    # Dropout rate rides as a TRACED scalar (replicated under shard_map):
    # changing it between runs re-dispatches, never retraces.
    part_rate = jnp.asarray(cfg.dropout_frac, jnp.float32)
    args = (data["x"], data["y"], data["n_per"], data["uids"], data["mask"],
            data["dkeys"], data["cluster_w"], part_rate)

    acc_hist = np.zeros((cfg.global_rounds, n_clusters))
    loss_hist = np.zeros((cfg.global_rounds, n_clusters))
    empty = [not setup.members[t] for t in range(n_clusters)]

    def eval_round(g, stack):
        for t in range(n_clusters):
            if empty[t]:
                acc_hist[g, t] = np.nan
                continue
            p_t = jax.tree.map(lambda l: l[t], stack)
            ex, ey = eval_sets[t]
            acc_hist[g, t] = models[t].accuracy(p_t, ex, ey)

    if cfg.scan_rounds:
        with obs.span("trainer.scan_rounds",
                      rounds=cfg.global_rounds) as sp:
            losses, stacks = run_fn(p_stack, *args)
            sp.sync((losses, stacks))
        loss_hist[:] = np.asarray(losses)[:, :n_clusters]
        for g in range(cfg.global_rounds):
            eval_round(g, jax.tree.map(lambda l: l[g], stacks))
    else:
        with obs.span("trainer.rounds", rounds=cfg.global_rounds) as sp:
            for g in range(cfg.global_rounds):
                p_stack, loss = round_fn(p_stack, jnp.asarray(g, jnp.int32),
                                         *args)
                loss_hist[g] = np.asarray(loss)[:n_clusters]
                eval_round(g, p_stack)
            sp.sync(p_stack)

    return MTHFLHistory(accuracy=acc_hist, train_loss=loss_hist,
                        labels=labels, fused=True)


# ---------------------------------------------------------------------------
# Reference path: the retained host loop (parity oracle + bench baseline)
# ---------------------------------------------------------------------------

def _train_reference(users, labels, models, eval_sets, cfg: MTHFLConfig,
                     setup: _ClusterSetup, lps_params: list[PyTree]
                     ) -> MTHFLHistory:
    n_clusters = len(models)
    acc_hist = np.zeros((cfg.global_rounds, n_clusters))
    loss_hist = np.zeros((cfg.global_rounds, n_clusters))
    any_weight = sum(setup.cluster_weights) > 0

    for g in range(cfg.global_rounds):
        for t in range(n_clusters):
            if not setup.datasets[t]:
                loss_hist[g, t] = np.nan
                continue
            p = lps_params[t]
            rk_g = jax.random.fold_in(setup.data_keys[t], g)
            # Same keyed per-round participation draw as the fused path;
            # dropped clients keep weight 0 in the FedAvg and are
            # excluded from the round loss.
            pmask = np.asarray(fed_client.participation_mask(
                rk_g, setup.uids[t], cfg.dropout_frac))
            if pmask.sum() == 0:               # whole cluster dropped
                loss_hist[g, t] = np.nan
                continue
            ns = jnp.asarray(setup.n_samples[t], jnp.float32) \
                * jnp.asarray(pmask)
            round_losses = []
            for l in range(cfg.local_rounds):
                rk = jax.random.fold_in(rk_g, l)
                batches = fed_client.make_keyed_batch_stack(
                    setup.datasets[t], setup.uids[t], rk, cfg.batch_size,
                    cfg.local_steps)
                p, losses = fed_client.fused_lps_round(
                    p, batches, ns, models[t].loss_fn, cfg.client)
                round_losses.append(
                    float(np.mean(np.asarray(losses)[pmask > 0])))
            lps_params[t] = p
            loss_hist[g, t] = float(np.mean(round_losses))
        # GPS round: average common layers, broadcast (empty clusters carry
        # weight 0; skipped entirely in the degenerate all-empty case).
        if any_weight:
            lps_params = hier.gps_aggregate(
                lps_params, setup.cluster_weights, models[0].is_common)
        for t in range(n_clusters):
            if not setup.datasets[t]:
                acc_hist[g, t] = np.nan
                continue
            ex, ey = eval_sets[t]
            acc_hist[g, t] = models[t].accuracy(lps_params[t], ex, ey)

    return MTHFLHistory(accuracy=acc_hist, train_loss=loss_hist,
                        labels=labels, fused=False)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def train_mthfl(users: Sequence,                      # list[UserData-like]
                labels: Sequence[int],
                models: Sequence[TaskModel],
                eval_sets: Sequence[tuple[np.ndarray, np.ndarray]],
                cfg: MTHFLConfig,
                cluster_classes: Sequence[Sequence[int]] | None = None,
                *,
                fused: bool | str = "auto",
                mesh: Mesh | None = None) -> MTHFLHistory:
    """Run Algorithm 1.

    ``users[i]`` needs ``.x (n_i, m)``, ``.n``, ``.user_id``, ``.y`` and
    ``.task_classes``; training labels are remapped to the head of the
    cluster the user is ASSIGNED to (misassigned users under random
    clustering train with the wrong head, which is exactly the degradation
    the paper measures).
    ``labels`` may be a host sequence or a device ``jax.Array`` straight
    from the ``ClusterEngine`` cut — the fused path derives the
    super-stack membership layout from it via ``partition.stack_layout``
    (one host sync remains for the ragged per-user data copies).
    ``models[t]`` / ``eval_sets[t]``: per-cluster model bundle and held-out
    (x, y_local) test set.

    ``fused``: ``"auto"`` (default) runs the fused super-stack program when
    every cluster's params stack (same structure/shapes/dtypes) and falls
    back to the reference loop otherwise; ``True`` requires stackability
    (raises if violated — the fused path also assumes the per-cluster
    ``loss_fn``/``is_common`` are replicas, and uses ``models[0]``'s);
    ``False`` forces the reference loop.  ``cfg.backend`` picks the fused
    execution (``"jnp"`` single jit, ``"shard_map"`` cluster axis sharded
    over ``mesh`` — defaults to a 1-D mesh over all local devices).
    """
    labels = np.asarray(labels)
    n_clusters = len(models)
    if cfg.backend not in TRAINER_BACKENDS:
        raise ValueError(f"cfg.backend must be one of {TRAINER_BACKENDS}, "
                         f"got {cfg.backend!r}")
    if not 0.0 <= cfg.dropout_frac < 1.0:
        raise ValueError("cfg.dropout_frac must be in [0, 1), got "
                         f"{cfg.dropout_frac!r}")
    setup = _setup_clusters(users, labels, n_clusters, cfg.seed,
                            cluster_classes)
    lps_params = [models[t].init(setup.init_keys[t])
                  for t in range(n_clusters)]

    can_fuse = _stackable(lps_params)
    if fused == "auto":
        use_fused = can_fuse
    elif fused:
        if not can_fuse:
            raise ValueError(
                "fused=True requires every cluster's params to stack — "
                "same structure, shapes and dtypes (got heterogeneous "
                "models); use fused='auto' to fall back to the reference "
                "loop")
        use_fused = True
    else:
        use_fused = False

    with obs.span("trainer.train_mthfl", fused=use_fused,
                  backend=cfg.backend, rounds=cfg.global_rounds):
        if use_fused:
            hist = _train_fused(users, labels, models, eval_sets, cfg,
                                setup, lps_params, mesh)
        else:
            hist = _train_reference(users, labels, models, eval_sets, cfg,
                                    setup, lps_params)
    if obs.enabled():
        obs.count("trainer.runs")
        obs.count("trainer.global_rounds", cfg.global_rounds)
    return hist
