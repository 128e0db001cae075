"""Traffic driver ``arrivals``: newcomers join a seeded cluster directory
in open-loop waves, with equal churn.

Set-up seeds ``repro.core.membership_engine.MembershipEngine`` with the
signatures and task labels of the seed users, and makes the pool of
newcomer signatures (a newcomer uploads only its ``(k x d)`` signature)
once, cut into the waves of one cycle of the plan so that the window
slices nothing.  Wave ``w`` is due at ``w * wave_period_ms`` after the
window opens, whether or not the previous wave is done; it is served by
``assign`` then ``admit``, after which as many random live members as
were admitted are ``evict``-ed and the serving loop's drift statistics
are read.  A newcomer's latency runs from its wave's due time to its
admission being done, so a late wave carries its wait.

Mix keys: ``wave_sizes`` (alternating), ``wave_period_ms``,
``warmup_cycles``, ``trace_seconds``.  Configuration keys (under
``"membership"``): ``seed_users``, ``pool``, ``capacity``, ``rows``,
``min_rows``, ``m``, ``d``, ``top_k``, ``n_tasks``, ``task_weights``,
``task_rank``, ``noise``, ``gen_block``, ``engine`` (keyword arguments of
``MembershipConfig``) and ``control`` (the engine settings of the
control).
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen
from bench.harness import Check, Window, load_module


def _signatures(c: dict, seed: int, tag: int, n: int):
    """``(lam, v, tasks)`` of ``n`` users drawn for ``(seed, tag)``."""
    tasks = datagen.task_layout(seed + tag, n, c["task_weights"])
    counts = datagen.row_counts(seed + tag, n, c["rows"], c["min_rows"])
    counts = (np.full(n, c["rows"]) if counts is None else counts)
    k = datagen.key(seed)
    w = datagen.rp_matrix(jax.random.fold_in(k, 7), c["m"], c["d"])
    lam, v = datagen.signatures(
        jax.random.fold_in(k, 100), jax.random.fold_in(k, tag),
        jnp.asarray(tasks),
        jnp.asarray(counts, jnp.float32), w, n_tasks=c["n_tasks"],
        rows=c["rows"], m=c["m"], rank=c["task_rank"], noise=c["noise"],
        block=c["gen_block"], top_k=c["top_k"])
    return lam, v, tasks


@partial(jax.jit, static_argnames=("b",))
def _take(lam, v, at, b: int):
    return (jax.lax.dynamic_slice_in_dim(lam, at, b),
            jax.lax.dynamic_slice_in_dim(v, at, b))


def _engine(c: dict, seed_lam, seed_v, seed_tasks, overrides=None):
    from repro.core.membership_engine import (MembershipConfig,
                                              MembershipEngine)

    eng = MembershipEngine(MembershipConfig(**dict(c["engine"],
                                                   **(overrides or {}))))
    eng.seed(seed_lam, seed_v, seed_tasks, n_clusters=c["n_tasks"],
             capacity=c["capacity"])
    return eng


def setup(ctx) -> dict:
    c, mix = ctx.cfg["membership"], ctx.mix
    seed_lam, seed_v, seed_tasks = _signatures(c, ctx.seed, 1,
                                               c["seed_users"])
    pool_lam, pool_v, pool_tasks = _signatures(c, ctx.seed, 2, c["pool"])
    sizes = list(mix["wave_sizes"])
    cycle = c["pool"] // sum(sizes) * len(sizes)
    chunks, at = [], 0
    for i in range(cycle):
        b = sizes[i % len(sizes)]
        chunks.append((at, *_take(pool_lam, pool_v, jnp.int32(at), b)))
        at += b
    state = {"c": c, "ctx": ctx, "chunks": chunks, "sizes": sizes,
             "seed": (seed_lam, seed_v, seed_tasks),
             "pool": (pool_lam, pool_v, pool_tasks),
             "rng": datagen.host_rng(ctx.seed, 3),
             "live_slot": np.arange(c["seed_users"], dtype=np.int32),
             "live_id": np.arange(c["seed_users"], dtype=np.int32),
             "served": c["seed_users"], "waves": [], "got": [],
             "eng": _engine(c, seed_lam, seed_v, seed_tasks)}
    jax.block_until_ready(state["eng"].state.protos)
    for _ in range(mix["warmup_cycles"] * len(sizes)):
        _serve(state, state["eng"], record=True)
    return state


def _serve(state, eng, record: bool, wave_index: int | None = None):
    """One wave: assign + admit (returns when admission is done), then
    equal churn and the drift statistics.  Returns the time admission
    was done."""
    w = len(state["waves"]) if wave_index is None else wave_index
    at, lam, v = state["chunks"][w % len(state["chunks"])]
    res = eng.assign(lam, v)
    slots = eng.admit(lam, v, res.labels)
    jax.block_until_ready(eng.state.protos)
    t_done = time.perf_counter()
    b = len(slots)
    if wave_index is None:
        live_slot = np.concatenate([state["live_slot"], slots])
        live_id = np.concatenate([
            state["live_id"],
            np.arange(state["served"], state["served"] + b, dtype=np.int32)])
        state["served"] += b
        out = state["rng"].choice(len(live_slot), b, replace=False)
        keep = np.ones(len(live_slot), bool)
        keep[out] = False
        evict_slots, evict_ids = live_slot[out], live_id[out]
        state["live_slot"], state["live_id"] = live_slot[keep], live_id[keep]
        state["waves"].append({"arrive_src": np.arange(at, at + b,
                                                       dtype=np.int32),
                               "evict_id": evict_ids,
                               "evict_slots": evict_slots})
    else:
        evict_slots = state["waves"][w]["evict_slots"]
    eng.evict(evict_slots)
    eng.drift_stats()
    if record:
        state["got"].append((res.labels, res.affinity))
    return t_done


def window(state, seconds: float) -> Window:
    """Open loop: wave ``w`` is due ``w`` periods after the window opens;
    waves run until the first one that ends after ``seconds``."""
    period = state["ctx"].mix["wave_period_ms"] / 1e3
    trace = state["ctx"].trace
    eng = state["eng"]
    lat, late, service, sizes = [], [], [], []
    t0 = time.perf_counter()
    w = 0
    while True:
        due = t0 + w * period
        now = time.perf_counter()
        if now < due:
            if trace:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(due - now)
            else:
                time.sleep(due - now)
        start = time.perf_counter()
        if trace:
            with jax.profiler.TraceAnnotation("bench.wave"):
                done = _serve(state, eng, record=True)
        else:
            done = _serve(state, eng, record=True)
        b = len(state["waves"][-1]["arrive_src"])
        lat.append(done - due)
        late.append(start - due)
        service.append(done - start)
        sizes.append(b)
        w += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    per_newcomer = np.repeat(np.asarray(lat), sizes)
    p95 = float(np.percentile(per_newcomer, 95))
    n = int(sum(sizes))
    return Window(
        e2e={"assign_p95_ms": 1e3 * p95}, ops=w, elapsed=elapsed,
        attempted=n, failed=0,
        host={"wave_service_ms": 1e3 * float(np.median(service)),
              "late_ms_median": 1e3 * float(np.median(late)),
              "late_ms_last": 1e3 * late[-1],
              "assign_p50_ms": 1e3 * float(np.percentile(per_newcomer, 50)),
              "waves": w, "newcomers": n})


def _reference(state):
    c, ctx = state["c"], state["ctx"]
    ref_mod = load_module(ctx.bench_dir / "reference" / "arrivals.py")
    seed_lam, seed_v, seed_tasks = state["seed"]
    waves = state["waves"]
    padded = ref_mod.pad_waves(waves, max(state["sizes"]))
    labels, aff, protos, c = ref_mod.replay(
        seed_v, jnp.asarray(seed_tasks), state["pool"][1],
        *(jnp.asarray(padded[k]) for k in ("arrive_src", "arrive_mask",
                                           "evict_id", "evict_mask")),
        n_clusters=c["n_tasks"], n_members=state["served"] + 1)
    return ref_mod, tuple(np.asarray(a) for a in (labels, aff, protos, c))


def _numbers(state, got, eng) -> dict:
    ref_mod, ref = _reference(state)
    return ref_mod.compare(state["waves"], [g[0] for g in got],
                           [g[1] for g in got],
                           np.asarray(eng.state.protos_f32),
                           np.asarray(eng.state.counts), *ref)


def readings(state) -> dict:
    """Every wave's labels and affinities, and the final prototypes and
    counts, against the plain float32 replay."""
    got = _numbers(state, state["got"], state["eng"])
    if state["eng"].state.n_members != len(state["live_slot"]):
        got["label_mismatch"] = float("inf")        # lost or extra members
    return got


def check(state) -> list[Check]:
    got = readings(state)
    return [Check(k, got[k], lim["limit"])
            for k, lim in state["ctx"].limits.items()]


def control(state) -> dict:
    """The program's own lower-precision path (``control`` engine
    settings) driven through the same waves, against the same replay."""
    c = state["c"]
    eng = _engine(c, *state["seed"], overrides=c["control"])
    got = [None] * len(state["waves"])
    saved = state["got"]
    state["got"] = []
    try:
        for i in range(len(state["waves"])):
            _serve(state, eng, record=True, wave_index=i)
        got = state["got"]
    finally:
        state["got"] = saved
    return _numbers(state, got, eng)
