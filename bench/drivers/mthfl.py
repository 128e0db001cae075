"""Traffic driver ``mthfl``: back-to-back MT-HFL training jobs.

Each operation is one call of the program's entry
``repro.fed.trainer.train_mthfl`` (fused), a whole job of
``global_rounds`` rounds over the clients the clusters hold, labelled by
the task oracle (the one-shot is measured in its own cells).  Clients'
images are made on the device from the seed and held on the host, as the
trainer takes them.  The model bundle is built once, so the trainer's
static ``loss_fn`` compiles once.

``train_samples_per_s`` is jobs x rounds x local rounds x local steps x
batch x clients over the window.  The trainer hands each round's
parameters to the bundle's ``accuracy`` for its held-out evaluation;
the bundle keeps them, and ``check`` compares the last job's first
``check_rounds`` rounds (losses, and each parameter leaf's change) with
the plain float32 reference.

Configuration keys (under ``"trainer"``): ``tasks`` (classes of each
task), ``clients_per_task``, ``images_per_client``, ``minority_frac``,
``eval_per_class``, ``image`` (the synthetic image family), ``cnn``,
``global_rounds``, ``local_rounds``, ``local_steps``, ``batch_size``,
``lr``, ``optimizer``, ``backend``, ``check_rounds``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen
from bench.harness import Check, Window, load_module


@dataclasses.dataclass
class Client:
    user_id: int
    task_id: int
    x: np.ndarray
    y: np.ndarray
    task_classes: tuple

    @property
    def n(self) -> int:
        return len(self.y)


def _split(total: int, parts: int) -> list[int]:
    return [total // parts + (i < total % parts) for i in range(parts)]


def make_clients(c: dict, seed: int):
    """Clients of every task (majority classes of their task, a minority
    from the other tasks), held-out sets per cluster, and the images on
    the device for the reference."""
    img, tasks = c["image"], [tuple(t) for t in c["tasks"]]
    n_classes = img["n_classes"]
    task_of = np.zeros(n_classes, np.int32)
    for t, cls in enumerate(tasks):
        task_of[list(cls)] = t
    k = datagen.key(seed)
    tb, cb, mu = datagen.class_structure(
        jax.random.fold_in(k, 21), jnp.asarray(task_of), m=img["m"],
        rank=img["rank"], n_classes=n_classes, n_tasks=len(tasks),
        mean_scale=img["mean_scale"])
    tb_of = tb[jnp.asarray(task_of)]
    rng = datagen.host_rng(seed, 22)
    n = c["images_per_client"]
    n_min = int(round(n * c["minority_frac"]))
    labels, owners = [], []
    for t, cls in enumerate(tasks):
        other = [x for x in range(n_classes) if task_of[x] != t]
        for _ in range(c["clients_per_task"]):
            y = np.concatenate(
                [np.repeat(cls, _split(n - n_min, len(cls))),
                 np.repeat(other, _split(n_min, len(other)))])
            labels.append(rng.permutation(y).astype(np.int32))
            owners.append(t)
    y_all = np.stack(labels)
    x_dev = datagen.class_images(
        jax.random.fold_in(k, 23), jnp.asarray(y_all), tb_of, cb, mu,
        img["task_scale"], img["class_scale"], img["noise"])
    x_host = np.asarray(x_dev)
    clients = [Client(user_id=i, task_id=t, x=x_host[i], y=y_all[i],
                      task_classes=tasks[t])
               for i, t in enumerate(owners)]
    e = c["eval_per_class"]
    evals = []
    for t, cls in enumerate(tasks):
        ye = np.repeat(np.arange(len(cls)), e).astype(np.int32)
        xe = datagen.class_images(
            jax.random.fold_in(k, 24 + t),
            jnp.asarray(np.asarray(cls)[ye])[None], tb_of, cb, mu,
            img["task_scale"], img["class_scale"], img["noise"])[0]
        evals.append((np.asarray(xe), ye))
    return clients, np.asarray(owners, np.int32), evals, x_dev, y_all


class Recorder:
    """The model bundle's hooks: keep what the trainer hands to ``init``
    and to ``accuracy`` (each round's parameters, per cluster)."""

    def __init__(self, cnn_cfg):
        from repro.models import cnn

        self.cnn, self.cfg = cnn, cnn_cfg
        self.inits: list = []
        self.rounds: list = []

    def reset(self):
        self.inits, self.rounds = [], []

    def init(self, key):
        p = self.cnn.init(self.cfg, key)
        self.inits.append(p)
        return p

    def accuracy(self, params, x, y):
        self.rounds.append(params)
        return self.cnn.accuracy(self.cfg, params, x, y)


def setup(ctx) -> dict:
    from repro.fed import client as fclient
    from repro.fed import partition as fpart
    from repro.fed import trainer as ftrainer
    from repro.models import cnn

    c = ctx.cfg["trainer"]
    clients, owners, evals, x_dev, y_all = make_clients(c, ctx.seed)
    cc = c["cnn"]
    ccfg = cnn.PaperCNNConfig(image_hw=tuple(cc["image_hw"]), c1=cc["c1"],
                              c2=cc["c2"], fc1=cc["fc1"], fc2=cc["fc2"],
                              n_classes=cc["n_classes"])
    rec = Recorder(ccfg)
    model = ftrainer.TaskModel(
        init=rec.init, loss_fn=cnn.loss_fn(ccfg), accuracy=rec.accuracy,
        is_common=fpart.prefix_predicate(cnn.COMMON_PREFIXES))
    n_tasks = len(c["tasks"])
    tcfg = ftrainer.MTHFLConfig(
        global_rounds=c["global_rounds"], local_rounds=c["local_rounds"],
        local_steps=c["local_steps"], batch_size=c["batch_size"],
        client=fclient.ClientConfig(lr=c["lr"], optimizer=c["optimizer"]),
        backend=c["backend"], seed=ctx.seed & 0x7FFFFFFF)
    state = {"c": c, "ctx": ctx, "clients": clients, "owners": owners,
             "evals": evals, "x_dev": x_dev, "y_all": y_all, "rec": rec,
             "models": [model] * n_tasks, "tcfg": tcfg, "hists": []}
    for _ in range(ctx.mix["warmup_ops"]):
        _job(state)
    state["hists"] = []
    return state


def _job(state):
    from repro.fed import trainer as ftrainer

    state["rec"].reset()
    hist = ftrainer.train_mthfl(
        state["clients"], state["owners"], state["models"], state["evals"],
        state["tcfg"], cluster_classes=[list(t) for t in state["c"]["tasks"]],
        fused=True)
    state["hists"].append(hist)
    return hist


def samples_per_job(c: dict) -> int:
    clients = c["clients_per_task"] * len(c["tasks"])
    return (c["global_rounds"] * c["local_rounds"] * c["local_steps"]
            * c["batch_size"] * clients)


def window(state, seconds: float) -> Window:
    trace = state["ctx"].trace
    jobs = 0
    t0 = time.perf_counter()
    while True:
        if trace:
            with jax.profiler.TraceAnnotation("bench.op"):
                _job(state)
        else:
            _job(state)
        jobs += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    rate = jobs * samples_per_job(state["c"]) / elapsed
    return Window(e2e={"train_samples_per_s": rate}, ops=jobs,
                  elapsed=elapsed, attempted=jobs, failed=0,
                  host={"train_samples_per_s": rate,
                        "rounds": jobs * state["c"]["global_rounds"]})


def _reference_clusters(state) -> list[dict]:
    c = state["c"]
    out = []
    for t, cls in enumerate(c["tasks"]):
        idx = np.flatnonzero(state["owners"] == t)
        lut = np.zeros(c["image"]["n_classes"], np.int32)
        lut[list(cls)] = np.arange(len(cls))
        out.append({"x": state["x_dev"][jnp.asarray(idx)],
                    "y": jnp.asarray(lut[state["y_all"][idx]]),
                    "n": np.full(len(idx), c["images_per_client"]),
                    "uids": idx})
    return out


def _reference(state, dtype: str):
    c, ctx = state["c"], state["ctx"]
    ref = load_module(ctx.bench_dir / "reference" / "mthfl.py")
    out = ref.train(_reference_clusters(state), state["tcfg"].seed,
                    c["cnn"], c["check_rounds"], c["local_steps"],
                    c["batch_size"], c["lr"], dtype=dtype)
    return ref, out


def _numbers(ref, r_out, losses_list, inits, rounds) -> dict:
    """Relative loss gaps (first round, and worst of the checked rounds)
    over every job, and leaf change-norm gaps (worst leaf after the first
    round, also over each leaf's own norm; worst and median leaf after the
    last checked round)."""
    r_init, r_hist, r_loss = r_out
    n_r, n_t = r_loss.shape
    rel = [np.abs(np.asarray(l)[:n_r] - r_loss) / r_loss
           for l in losses_list]
    out = {"loss_r1": max(float(np.max(x[0])) for x in rel),
           "loss_rel": max(float(np.max(x)) for x in rel)}

    def gaps(g, how=ref.change_gaps):
        return [how(inits[t], rounds[g][t], r_init[t], r_hist[g][t])
                for t in range(n_t)]

    out["dparam_r1"] = max(max(x) for x in gaps(0))
    last = gaps(n_r - 1)
    out["dparam_last"] = max(max(x) for x in last)
    out["dparam_last_med"] = max(float(np.median(x)) for x in last)
    out["dparam_own_r1"] = max(max(x) for x in gaps(0, ref.own_gaps))
    return out


def readings(state) -> dict:
    """Each job's first rounds' losses, and the last job's parameter
    changes, against the plain float32 reference."""
    ref, r_out = _reference(state, "f32")
    rec = state["rec"]
    n_t = len(state["c"]["tasks"])
    rounds = [rec.rounds[g * n_t:(g + 1) * n_t]
              for g in range(len(rec.rounds) // n_t)]
    return _numbers(ref, r_out, [h.train_loss for h in state["hists"]],
                    rec.inits, rounds)


def check(state) -> list[Check]:
    got = readings(state)
    return [Check(k, got[k], lim["limit"])
            for k, lim in state["ctx"].limits.items()]


def control(state) -> dict:
    """The reference in bfloat16 in the program's place."""
    ref, r_out = _reference(state, "f32")
    _, (l_init, l_hist, l_loss) = _reference(state, "bf16")
    return _numbers(ref, r_out, [l_loss], l_init, l_hist)
