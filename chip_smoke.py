"""Chip smoke test: the one-shot clustering -> MT-HFL trainer path on a TPU.

    python chip_smoke.py              # one chip: the whole main path
    python chip_smoke.py --chips 4    # four chips: the shard_map paths only

One chip runs, through the entry points users call
(``oneshot.one_shot_clustering``, ``MembershipEngine``,
``fed.trainer.train_mthfl``):

1. the raw-data protocol at N=1024 users x 256 rows of CIFAR width
   (m=3072), random projection to d=128, top-8 signatures, 10 tasks, on
   the Pallas backends and again on the jnp backends as the reference;
2. a membership wave of 256 newcomers scored against the directory the
   protocol seeded, Pallas against jnp;
3. the fused MT-HFL trainer on the paper CNN at full width (two CIFAR-like
   tasks, 32 clients each), labelled by its own one-shot run.

``--chips 4`` runs only the sharded paths and what they are compared with:
the shard_map raw protocol with N=4096 users over a 4-device mesh against
the single-device jnp protocol, and the trainer's shard_map backend with
four clusters over four devices against ``backend="jnp"``.

Data is random, made from ``--seed``.  Every phase checks its result
and the run reports every check; any failed check, a first device that
is not a TPU, or a device count other than ``--chips`` exits non-zero
without printing a result.  Printed wall
times are one cold run each, compilation included, and are not
measurements.  The last line of standard output is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

N_USERS = 1024          # protocol population (one chip)
N_USERS_4 = 4096        # protocol population (four chips)
ROWS = 256              # raw rows per user
RAW_DIM = 3072          # CIFAR 32x32x3
SIG_DIM = 128           # random-projection width
TOP_K = 8
N_TASKS = 10
TASK_RANK = 16          # rank of each task's subspace of the raw space
CHUNK_ROWS = 64         # streamed ingest chunk
WAVE = 256              # newcomers in the membership wave
R_ATOL = 1e-2           # |R_pallas - R_jnp| bound; R lives in [0, 1]
# Local optimiser of each trainer phase.  The one-chip trainer must learn
# in 3 rounds, which AdamW does and momentum SGD does not (it stays at the
# label prior).  The shard_map/jnp comparison runs momentum SGD: its two
# f32 CPU histories agree to 2.4e-7, where AdamW's part by 2.2e-3 because
# Adam's normalised step amplifies the programs' different summation
# orders (PERF.md).
LEARN = dict(lr=3e-3, optimizer="adamw", local_steps=30)
PARITY = dict(lr=0.05, optimizer="momentum", local_steps=10)
# shard_map vs jnp trainer loss bound.  A TPU f32 conv or matmul at default
# precision rounds its inputs to bf16 (relative step 2^-8), so on the chip
# the two programs part at bf16 scale, not at the 1e-5 of the f32 CPU test
# (test_trainer_parity).  Dropping the cross-device GPS average moves the
# loss by 0.40 at this size (PERF.md).
LOSS_ATOL = 1e-2
# A trained head must beat guessing: last-round held-out accuracy at least
# chance + ABOVE_CHANCE for every cluster.
ABOVE_CHANCE = 0.2

FAILED: list[str] = []


def check(ok, what: str) -> None:
    """Print one check and remember a failure: the run goes on so that one
    chip call reports every phase, and ``main`` then exits non-zero."""
    print(f"  check {'pass' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        FAILED.append(what)


@contextlib.contextmanager
def phase(name: str):
    print(f"[{name}]", flush=True)
    t0 = time.perf_counter()
    yield
    print(f"  wall {time.perf_counter() - t0:.2f} s "
          f"(cold single run, compile included)", flush=True)


def pipeline(backend: str, seed: int) -> dict:
    """Keyword arguments of ``one_shot_clustering`` for the raw path on
    one backend family: protocol, ingest and (device) HAC."""
    from repro.core.cluster_engine import ClusterConfig
    from repro.core.signature_engine import SignatureConfig
    from repro.core.similarity import SimilarityConfig
    from repro.data.features import FeatureConfig

    return dict(
        cfg=SimilarityConfig(top_k=TOP_K, backend=backend),
        cluster_cfg=ClusterConfig(
            backend="jnp" if backend == "shard_map" else backend),
        feature_cfg=FeatureConfig(kind="random_projection", d=SIG_DIM,
                                  seed=seed),
        signature_cfg=SignatureConfig(backend=backend,
                                      chunk_rows=CHUNK_ROWS))


def cluster_to_task(labels, task_ids):
    """Majority true task of each cluster."""
    import numpy as np

    labels, task_ids = np.asarray(labels), np.asarray(task_ids)
    return {int(c): int(np.bincount(task_ids[labels == c]).argmax())
            for c in np.unique(labels)}


def kernel_phase(seed: int) -> None:
    """Pallas runs lowered, and a compiled phase holds a Mosaic kernel."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch
    from repro.kernels.featurize_gram import ops as fg_ops

    with phase("kernels"):
        check(dispatch.resolve_interpret(None) is False,
              "Pallas resolves to lowered (not interpret) execution")
        x = jnp.zeros((CHUNK_ROWS, RAW_DIM), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(seed), (RAW_DIM, SIG_DIM))
        text = jax.jit(fg_ops.featurize_gram).lower(x, w).compile().as_text()
        check("tpu_custom_call" in text,
              f"compiled featurize_gram ({CHUNK_ROWS}x{RAW_DIM} @ "
              f"{RAW_DIM}x{SIG_DIM}) contains tpu_custom_call")


def protocol_phase(raw, task_ids, seed: int):
    """Raw protocol on the Pallas backends against the jnp reference."""
    import numpy as np

    from repro.core import clustering as clu
    from repro.core import oneshot

    n, rows, m = raw.shape
    print(f"  {n} users x {rows} rows x m={m} -> d={SIG_DIM}, top_k={TOP_K},"
          f" {N_TASKS} tasks, chunk_rows={CHUNK_ROWS}")
    out = {}
    for backend in ("pallas", "jnp"):
        with phase(f"protocol {backend}"):
            res = oneshot.one_shot_clustering(raw, N_TASKS,
                                              **pipeline(backend, seed))
            labels = np.asarray(res.labels)
        out[backend] = (res, labels)
    (res_p, lab_p), (res_j, lab_j) = out["pallas"], out["jnp"]
    acc = clu.clustering_accuracy(lab_p, task_ids)
    dr = float(np.abs(np.asarray(res_p.similarity)
                      - np.asarray(res_j.similarity)).max())
    check(acc == 1.0, f"pallas clustering accuracy {acc:.4f} == 1.0")
    check(np.array_equal(lab_p, lab_j), "pallas labels == jnp labels")
    check(dr <= R_ATOL, f"max |R_pallas - R_jnp| = {dr:.3e} <= {R_ATOL}")
    return res_p, lab_p


def membership_phase(res, seed_labels, seed_tasks, new_raw, new_tasks,
                     seed: int) -> None:
    """One arrival wave scored by the Pallas directory against jnp."""
    import numpy as np

    from repro.core.membership_engine import (MembershipConfig,
                                              MembershipEngine)
    from repro.core.signature_engine import SignatureConfig, SignatureEngine

    fcfg = pipeline("pallas", seed)["feature_cfg"]
    with phase(f"membership wave of {len(new_raw)}"):
        sig = SignatureEngine(fcfg, SignatureConfig(backend="pallas",
                                                    chunk_rows=CHUNK_ROWS))
        lam, v, _ = sig.signatures(new_raw, top_k=TOP_K)
        verdicts = {}
        for backend in ("pallas", "jnp"):
            eng = MembershipEngine.from_oneshot(
                res, MembershipConfig(backend=backend))
            verdicts[backend] = np.asarray(eng.assign(lam, v).labels)
    to_task = cluster_to_task(seed_labels, seed_tasks)
    got = np.asarray([to_task.get(int(c), -1) for c in verdicts["pallas"]])
    check(np.array_equal(verdicts["pallas"], verdicts["jnp"]),
          "pallas wave labels == jnp wave labels")
    check(np.array_equal(got, new_tasks),
          f"every newcomer joins its own task's cluster "
          f"({int((got == new_tasks).sum())}/{len(new_tasks)})")


def cnn_models(n_clusters: int):
    from repro.fed import partition as fpart
    from repro.fed import trainer as ftrainer
    from repro.models import cnn

    ccfg = cnn.PaperCNNConfig()          # c1=6 c2=16 fc 120/84, 10 classes
    model = ftrainer.TaskModel(
        init=partial(cnn.init, ccfg), loss_fn=cnn.loss_fn(ccfg),
        accuracy=partial(cnn.accuracy, ccfg),
        is_common=fpart.prefix_predicate(cnn.COMMON_PREFIXES))
    return [model] * n_clusters


def eval_sets(cluster_classes, task_of, seed: int, n: int = 60):
    """Held-out per-cluster test sets, labels local to the cluster head."""
    import numpy as np

    from repro.data import synthetic as syn

    out = []
    for classes in cluster_classes:
        x, y = syn.make_task_dataset(
            syn.CIFAR_LIKE, list(classes), n, seed=seed + 999,
            task_of_class={c: task_of[c] for c in classes})
        lut = {c: i for i, c in enumerate(classes)}
        out.append((x, np.asarray([lut[int(c)] for c in y], np.int32)))
    return out


def train_cfg(backend: str, seed: int, lr: float, optimizer: str,
              local_steps: int):
    from repro.fed import client as fclient
    from repro.fed import trainer as ftrainer

    return ftrainer.MTHFLConfig(
        global_rounds=3, local_rounds=1, local_steps=local_steps,
        batch_size=32, client=fclient.ClientConfig(lr=lr, optimizer=optimizer),
        backend=backend, seed=seed)


def check_learned(hist, classes, what: str) -> None:
    """Every cluster's last-round held-out accuracy beats chance."""
    for t, cls in enumerate(classes):
        acc, floor = float(hist.accuracy[-1, t]), 1 / len(cls) + ABOVE_CHANCE
        check(acc >= floor, f"{what} cluster {t} ({len(cls)} classes) "
                            f"accuracy {acc:.4f} >= {floor:.4f}")


def trainer_phase(seed: int, users_per_task: int = 32,
                  rows: int = 400) -> None:
    """Paper CIFAR layout clustered by its own one-shot run, then three
    rounds of the fused trainer on the full-width paper CNN."""
    import numpy as np

    from repro.core import clustering as clu
    from repro.core import oneshot
    from repro.data import partition as dpart
    from repro.fed import trainer as ftrainer

    users = dpart.paper_cifar_two_task(
        n_per_user=rows, seed=seed, users_per_task=(users_per_task,) * 2)
    tasks = [u.task_id for u in users]
    print(f"  {len(users)} clients x ~{rows} images (m={RAW_DIM}), "
          f"2 tasks, paper CNN, 3 rounds x {LEARN['local_steps']} local "
          f"steps of {LEARN['optimizer']}, batch 32")
    with phase("trainer one-shot labels"):
        res = oneshot.one_shot_clustering([u.x for u in users], 2,
                                          **pipeline("pallas", seed))
        labels = np.asarray(res.labels)
    acc = clu.clustering_accuracy(labels, tasks)
    check(acc == 1.0, f"one-shot accuracy on the trainer's clients "
                      f"{acc:.4f} == 1.0")
    to_task = cluster_to_task(labels, tasks)
    classes = [tuple(dpart.CIFAR_TASKS[to_task[t]]) for t in range(2)]
    task_of = {c: t for t, cs in dpart.CIFAR_TASKS.items() for c in cs}
    with phase("trainer fused jnp"):
        hist = ftrainer.train_mthfl(
            users, labels, cnn_models(2), eval_sets(classes, task_of, seed),
            train_cfg("jnp", seed, **LEARN), cluster_classes=classes,
            fused=True)
    loss = hist.train_loss
    print(f"  train loss per round x cluster: {np.round(loss, 4).tolist()}")
    print(f"  test accuracy per round x cluster: "
          f"{np.round(hist.accuracy, 4).tolist()}")
    check(hist.fused, "the fused super-stack program ran")
    check(np.isfinite(loss).all(), "losses are finite")
    check(bool((loss[-1] < loss[0]).all()),
          "each cluster's loss decreases from round 1 to round 3")
    check_learned(hist, classes, "fused jnp")


def sharded_trainer_inputs(seed: int, users_per_task: int = 8,
                           rows: int = 400):
    """Four CIFAR-like tasks of 8 clients each, one cluster per task:
    ``(users, labels, classes, eval_sets)`` for ``train_mthfl``."""
    import numpy as np

    from repro.data import partition as dpart
    from repro.data import synthetic as syn

    tasks = {0: (0, 1), 1: (8, 9), 2: (2, 3, 4), 3: (5, 6, 7)}
    specs = [dpart.UserSpec(user_id=t * users_per_task + i, task_id=t,
                            majority_labels=tasks[t], minority_labels=(),
                            n_majority=rows, n_minority=0)
             for t in tasks for i in range(users_per_task)]
    users = dpart.federated_split(syn.CIFAR_LIKE, tasks, specs, seed=seed)
    labels = np.asarray([u.task_id for u in users])
    classes = [tasks[t] for t in range(4)]
    task_of = {c: t for t, cs in tasks.items() for c in cs}
    return users, labels, classes, eval_sets(classes, task_of, seed)


def sharded_trainer_phase(seed: int) -> None:
    """Four clusters over four devices (shard_map) against one device.
    Only losses are compared: these few momentum steps leave held-out
    accuracy at the label prior (the one-chip phase checks learning)."""
    import numpy as np

    from repro.fed import trainer as ftrainer

    users, labels, classes, evals = sharded_trainer_inputs(seed)
    print(f"  {len(users)} clients, 4 clusters, paper CNN, 3 rounds x "
          f"{PARITY['local_steps']} local steps of {PARITY['optimizer']}")
    hists = {}
    for backend in ("shard_map", "jnp"):
        with phase(f"trainer fused {backend}"):
            hists[backend] = ftrainer.train_mthfl(
                users, labels, cnn_models(4), evals,
                train_cfg(backend, seed, **PARITY), cluster_classes=classes,
                fused=True)
        print(f"  {backend} loss per round x cluster: "
              f"{hists[backend].train_loss.tolist()}")
        print(f"  {backend} accuracy per round x cluster: "
              f"{hists[backend].accuracy.tolist()}")
    a, b = hists["shard_map"], hists["jnp"]
    dl = float(np.abs(a.train_loss - b.train_loss).max())
    check(np.isfinite(a.train_loss).all(), "shard_map losses are finite")
    check(bool((a.train_loss[-1] < a.train_loss[0]).all()),
          "each shard_map cluster's loss decreases from round 1 to round 3")
    check(dl <= LOSS_ATOL, f"max |loss shard_map - jnp| = {dl:.3e} "
                           f"<= {LOSS_ATOL}")


def peak_bytes(devices) -> list[int]:
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]


def sharded_protocol_phase(seed: int) -> None:
    """shard_map raw protocol, users over all devices, against the
    single-device jnp protocol."""
    import jax
    import numpy as np

    from repro.core import oneshot
    from repro.data import synthetic as syn

    devs = jax.devices()
    raw, task_ids = syn.make_task_feature_mixture(
        N_USERS_4, ROWS, RAW_DIM, N_TASKS, seed=seed, rank=TASK_RANK)
    print(f"  {N_USERS_4} users x {ROWS} rows x m={RAW_DIM} over "
          f"{len(devs)} devices")
    before = peak_bytes(devs)
    with phase("protocol shard_map"):
        res_s = oneshot.one_shot_clustering(raw, N_TASKS,
                                            **pipeline("shard_map", seed))
        lab_s = np.asarray(res_s.labels)
    after = peak_bytes(devs)
    shard = raw.nbytes // len(devs)
    print(f"  device peak bytes before/after: {before} / {after}")
    check(all(p >= shard for p in after),
          f"every device held its {shard} B user shard")
    check(after[0] < 2 * shard,
          f"device 0 peaked below two user shards ({2 * shard} B): the raw "
          f"stack was not staged there")
    with phase("protocol jnp (one device)"):
        res_j = oneshot.one_shot_clustering(raw, N_TASKS,
                                            **pipeline("jnp", seed))
        lab_j = np.asarray(res_j.labels)
    dr = float(np.abs(np.asarray(res_s.similarity)
                      - np.asarray(res_j.similarity)).max())
    check(np.array_equal(lab_s, lab_j), "shard_map labels == jnp labels")
    check(dr <= R_ATOL, f"max |R_shard_map - R_jnp| = {dr:.3e} <= {R_ATOL}")


def one_chip(seed: int) -> None:
    from repro.data import synthetic as syn

    kernel_phase(seed)
    with phase("data"):
        raw, tasks = syn.make_task_feature_mixture(
            N_USERS + WAVE, ROWS, RAW_DIM, N_TASKS, seed=seed,
            rank=TASK_RANK)
    res, labels = protocol_phase(raw[:N_USERS], tasks[:N_USERS], seed)
    membership_phase(res, labels, tasks[:N_USERS], raw[N_USERS:],
                     tasks[N_USERS:], seed)
    trainer_phase(seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = use_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    print(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
          f"{cache}; jax {jax.__version__}", flush=True)
    hits = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            hits["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    if args.chips == 4:
        sharded_trainer_phase(args.seed)
        sharded_protocol_phase(args.seed)
    else:
        one_chip(args.seed)
    print(f"persistent compile cache: {hits['hits']} hits, "
          f"{hits['misses']} misses", flush=True)
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}",
              file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
