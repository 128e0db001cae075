"""Traffic driver ``oneshot``: back-to-back one-shot clusterings of one
population whose raw shards sit in device memory.

Each operation is one call of the program's entry
``repro.core.oneshot.one_shot_clustering`` on the raw-data path (ingest,
top-k spectrum, relevance, HAC), from the raw stack to labels.
``oneshot_s`` is the window over the runs completed in it.  Every run's
signatures, ``R`` and labels are compared with the plain reference.

Configuration keys (``configs/<config>.json`` under ``"oneshot"``):
``n_users``, ``rows``, ``m``, ``n_tasks``, ``task_rank``, ``noise``,
``task_weights``, ``min_rows`` (``null``: every row slot holds data),
``d``, ``top_k``, ``chunk_rows``, ``eig_floor``, ``backends``
(``similarity``, ``signatures``, ``cluster``) and ``gen_block``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen
from bench.harness import Check, Window, load_module


def pipeline(c: dict, seed: int) -> dict:
    """Keyword arguments of ``one_shot_clustering`` for the raw path."""
    from repro.core.cluster_engine import ClusterConfig
    from repro.core.signature_engine import SignatureConfig
    from repro.core.similarity import SimilarityConfig
    from repro.data.features import FeatureConfig

    b = c["backends"]
    return dict(
        cfg=SimilarityConfig(top_k=c["top_k"], eig_floor=c["eig_floor"],
                             backend=b["similarity"]),
        cluster_cfg=ClusterConfig(backend=b["cluster"]),
        feature_cfg=FeatureConfig(kind="random_projection", d=c["d"],
                                  seed=seed),
        signature_cfg=SignatureConfig(backend=b["signatures"],
                                      chunk_rows=c["chunk_rows"]))


def make_population(c: dict, seed: int):
    """Raw rows ``(N, rows, m)`` on the device, valid counts (or ``None``)
    and each user's true task."""
    tasks = datagen.task_layout(seed, c["n_users"], c["task_weights"])
    raw = datagen.task_mixture(
        jax.random.fold_in(datagen.key(seed), 1), jnp.asarray(tasks),
        n_tasks=c["n_tasks"], rows=c["rows"], m=c["m"], rank=c["task_rank"],
        noise=c["noise"], block=c["gen_block"])
    counts = datagen.row_counts(seed, c["n_users"], c["rows"],
                                c["min_rows"])
    n_valid = None if counts is None else jnp.asarray(counts, jnp.float32)
    return jax.block_until_ready(raw), n_valid, tasks


def _run(state) -> object:
    from repro.core import oneshot

    res = oneshot.one_shot_clustering(
        state["raw"], state["c"]["n_tasks"], n_valid=state["n_valid"],
        **state["kw"])
    jax.block_until_ready((res.labels, res.similarity, res.lam, res.v))
    return res


def setup(ctx) -> dict:
    c = ctx.cfg["oneshot"]
    raw, n_valid, tasks = make_population(c, ctx.seed)
    state = {"c": c, "ctx": ctx, "raw": raw, "n_valid": n_valid,
             "tasks": tasks, "kw": pipeline(c, ctx.seed), "results": []}
    for _ in range(ctx.mix["warmup_ops"]):
        _run(state)
    return state


def window(state, seconds: float) -> Window:
    """Whole one-shots back to back until the first boundary after
    ``seconds``."""
    ann = (jax.profiler.TraceAnnotation if state["ctx"].trace else None)
    results = state["results"]
    t0 = time.perf_counter()
    while True:
        if ann:
            with ann("bench.op"):
                results.append(_run(state))
        else:
            results.append(_run(state))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    n = len(results)
    return Window(e2e={"oneshot_s": elapsed / n}, ops=n, elapsed=elapsed,
                  attempted=n, failed=0)


def _reference(state, dtype: str):
    c, ctx = state["c"], state["ctx"]
    ref_mod = load_module(ctx.bench_dir / "reference" / "oneshot.py")
    w = ref_mod.shared_projection(ctx.seed, c["m"], c["d"])
    return ref_mod, ref_mod.reference(state["raw"], state["n_valid"], w,
                                      c["top_k"], c["n_tasks"],
                                      c["eig_floor"], dtype=dtype)


def readings(state) -> dict:
    """Every run of the window against the plain float32 reference: the
    worst of each number over the runs."""
    ref_mod, ref = _reference(state, "f32")
    worst: dict[str, float] = {}
    for res in state["results"]:
        got = ref_mod.compare(np.asarray(res.lam), np.asarray(res.v),
                              np.asarray(res.similarity),
                              np.asarray(res.labels), ref)
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


def check(state) -> list[Check]:
    got = readings(state)
    return [Check(k, got[k], lim["limit"])
            for k, lim in state["ctx"].limits.items()]


def control(state) -> dict:
    """The control's readings: the reference one precision below the
    configuration's (``control_dtype``) in the program's place, compared
    with the float32 reference."""
    ref_mod, ref = _reference(state, "f32")
    _, low = _reference(state, state["c"]["control_dtype"])
    return ref_mod.compare(low["lam"], low["v"], low["big_r"],
                           low["labels"], ref)
