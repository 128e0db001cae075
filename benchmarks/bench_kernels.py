"""Kernel + engine micro-benchmarks: tuned vs default vs jnp.

Every Pallas kernel family is timed three ways at the same shape:

* ``jnp``     — the reference path (``ref.py``), the number to beat;
* ``default`` — the kernel under its PRE-tuning-era static 128 tiles
  (the PR-7 configuration; for ``assign`` this is the per-arrival
  ``lax.map`` kernel that PR 8 replaced);
* ``tuned``   — the kernel under ``kernels.tuning`` block resolution
  (autotune cache if populated, per-backend heuristics otherwise).

Off-accelerator the kernels execute in interpret mode, where wall time
measures the interpreter's per-grid-step cost — which is exactly what the
CPU heuristics minimize, so the ``gap_shrink`` column (default-gap /
tuned-gap vs jnp) is the honest figure of merit there: it shows how much
of the interpret-mode penalty the tile plan removed.  On TPU/GPU the same
grid runs lowered and ``tuned_vs_jnp`` is the headline.

``--tune`` runs the measured autotune sweep first (populating the cache
that ``REPRO_TUNE_CACHE`` persists); without it the heuristic defaults
are what "tuned" means.  Results land in ``--json``
(``benchmarks/results/bench_kernels.json``).

Also keeps two engine-level rows (streaming blockwise R; fused LPS round)
— whole-protocol numbers the kernel grid feeds into.

Standalone: ``PYTHONPATH=src:. python benchmarks/bench_kernels.py --quick``
(CI smoke: shrunken shapes, same code paths).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import similarity as sim
from repro.core.engine import ProtocolEngine
from repro.fed import client as fclient
from repro.fed import hierarchy as hier
from repro.kernels import tuning
from repro.kernels.assign import ops as assign_ops
from repro.kernels.assign.ref import assign_ref
from repro.kernels.eigproject import ops as proj_ops
from repro.kernels.eigproject.ref import project_norms_ref
from repro.kernels.featurize_gram import ops as fg_ops
from repro.kernels.featurize_gram.ref import featurize_gram_ref
from repro.kernels.gram import ops as gram_ops
from repro.kernels.gram.ref import gram_ref
from repro.kernels.gram_project import ops as gp_ops
from repro.kernels.gram_project.ref import gram_project_ref
from repro.kernels.linkage import ops as link_ops
from repro.kernels.linkage.ref import linkage_step_ref
from repro.models import mlp

# The pre-tuning-era static tile plans (what every kernel shipped with
# before the autotuner): uniform 128 tiles, no DMA double-buffering.
DEFAULT_BLOCKS = {
    "gram": {"block_n": 128, "block_d": 128},
    "gram_project": {"block_n": 128, "block_k": 128,
                     "double_buffer": False},
    "featurize_gram": {"block_n": 128, "double_buffer": False},
    "eigproject": {"block_u": 1, "block_c": 128},
    "linkage": {"block": 128},
    # pre-tuning chunking for the serving recurrences (bench_serve)
    "recurrent_scan": {"chunk": 16, "block_d": 128},
}


def _grid_candidates(kernel: str, **dims: int) -> list[dict]:
    """A small sweep grid around the heuristic default."""
    heur = tuning.heuristic_blocks(kernel, **dims)
    cands = [dict(heur), {**DEFAULT_BLOCKS.get(kernel, {})} or dict(heur)]
    for scale in (256, 512, 1024, 2048):
        cands.append({k: (min(v, scale) if isinstance(v, int) else v)
                      for k, v in heur.items()})
    seen, out = set(), []
    for c in cands:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


def _bench_family(name: str, shape_tag: str, ref_fn, pallas_fn, dims: dict,
                  tune: bool, records: list, n_iter: int = 5,
                  assert_shrink: float | None = None) -> str:
    """Time jnp vs default-tiles vs tuned-tiles; validate; record."""
    ref_out = np.asarray(jax.block_until_ready(ref_fn()))
    ref_us = common.time_us(lambda: jax.block_until_ready(ref_fn()),
                            n_iter=n_iter)

    def timed(blocks) -> tuple[float, bool]:
        out = np.asarray(jax.block_until_ready(pallas_fn(blocks)))
        ok = bool(np.allclose(out, ref_out, rtol=1e-3, atol=1e-2))
        us = common.time_us(
            lambda: jax.block_until_ready(pallas_fn(blocks)), n_iter=n_iter)
        return us, ok

    if tune:
        tuning.autotune(
            name, lambda blk: jax.block_until_ready(pallas_fn(blk)),
            _grid_candidates(name, **dims), **dims)
    default_us, default_ok = timed(DEFAULT_BLOCKS[name])
    tuned_blocks = tuning.get_blocks(name, **dims)
    tuned_us, tuned_ok = timed(tuned_blocks)

    gap_default = default_us / ref_us
    gap_tuned = tuned_us / ref_us
    shrink = gap_default / gap_tuned if gap_tuned else float("inf")
    if assert_shrink is not None:
        assert shrink >= assert_shrink, (
            f"{name}: tuned tiles shrank the vs-jnp gap only "
            f"{shrink:.1f}x (< {assert_shrink}x) at {shape_tag}")
    records.append({
        "kernel": name, "shape": shape_tag, "dims": dims,
        "jnp_us": round(ref_us, 1),
        "default_us": round(default_us, 1),
        "tuned_us": round(tuned_us, 1),
        "tuned_blocks": {k: v for k, v in tuned_blocks.items()},
        "gap_default_vs_jnp": round(gap_default, 2),
        "gap_tuned_vs_jnp": round(gap_tuned, 2),
        "gap_shrink": round(shrink, 2),
        "validates": bool(default_ok and tuned_ok),
        "tuned": tune,
    })
    return common.row(
        f"kernel_{name}_{shape_tag}", tuned_us,
        jnp_us=round(ref_us, 1), default_us=round(default_us, 1),
        gap_tuned_vs_jnp=round(gap_tuned, 2),
        gap_shrink_vs_default=round(shrink, 2),
        validates=bool(default_ok and tuned_ok))


def _bench_gram(rng, quick, tune, records):
    n, d = (512, 128) if quick else (4096, 256)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    return _bench_family(
        "gram", f"{n}x{d}", lambda: gram_ref(x),
        lambda blk: gram_ops.gram_matrix(x, block_n=blk["block_n"],
                                         block_d=blk["block_d"]),
        dict(n=n, d=d), tune, records)


def _bench_gram_project(rng, quick, tune, records):
    n, d, k = (512, 128, 128) if quick else (4096, 256, 256)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((d, k)), jnp.float32)
    return _bench_family(
        "gram_project", f"{n}x{d}x{k}", lambda: gram_project_ref(x, v),
        lambda blk: gp_ops.gram_project(
            x, v, block_n=blk["block_n"], block_k=blk["block_k"],
            double_buffer=blk.get("double_buffer", False)),
        dict(n=n, k=k), tune, records,
        assert_shrink=None if quick else 5.0)


def _bench_featurize_gram(rng, quick, tune, records):
    n, m, d = (512, 256, 128) if quick else (4096, 512, 256)
    x = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((m, d)) / np.sqrt(m), jnp.float32)
    return _bench_family(
        "featurize_gram", f"{n}x{m}x{d}",
        lambda: featurize_gram_ref(x, w),
        lambda blk: fg_ops.featurize_gram(
            x, w, block_n=blk["block_n"],
            double_buffer=blk.get("double_buffer", False)),
        dict(n=n), tune, records)


def _bench_eigproject(rng, quick, tune, records):
    d, k = (128, 64) if quick else (512, 256)
    g = jnp.asarray(rng.standard_normal((d, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((d, k)), jnp.float32)
    return _bench_family(
        "eigproject", f"{d}x{k}", lambda: project_norms_ref(g, v),
        lambda blk: proj_ops.project_norms(g, v, block_c=blk["block_c"]),
        dict(d=d, k=k), tune, records)


def _bench_linkage(rng, quick, tune, records):
    n = 1024 if quick else 8192
    ra = jnp.asarray(rng.standard_normal(n), jnp.float32)
    rb = jnp.asarray(rng.standard_normal(n), jnp.float32)
    mask = jnp.asarray((rng.random(n) > 0.2).astype(np.float32))

    def ref():
        return linkage_step_ref(ra, rb, 2.0, 3.0, mask)[0]

    return _bench_family(
        "linkage", f"n{n}", ref,
        lambda blk: link_ops.linkage_step(ra, rb, 2.0, 3.0, mask,
                                          block=blk["block"])[0],
        dict(n=n), tune, records)


def _bench_assign(rng, quick, tune, records):
    """The wave kernel vs the PR-7 per-arrival ``lax.map`` kernel vs jnp.

    ``default`` here is the REAL previous implementation
    (``assign_looped``), not just smaller tiles — the gap_shrink column
    measures the batched-matmul redesign plus the tile plan together.
    """
    b, d, k, t = (64, 32, 8, 8) if quick else (256, 32, 8, 16)
    v = jnp.asarray(rng.standard_normal((b, d, k)), jnp.float32)
    p = jnp.asarray(rng.standard_normal((t, d, d)), jnp.float32)
    dims = dict(b=b, d2=d * d)

    ref_out = np.asarray(jax.block_until_ready(assign_ref(v, p)[0]))
    ref_us = common.time_us(
        lambda: jax.block_until_ready(assign_ref(v, p)[0]))

    looped_us = common.time_us(
        lambda: jax.block_until_ready(assign_ops.assign_looped(v, p)[0]),
        n_iter=2)

    def wave(blocks):
        return assign_ops.assign(v, p, block_b=blocks["block_b"],
                                 block_d2=blocks["block_d2"])[0]

    if tune:
        tuning.autotune(
            "assign", lambda blk: jax.block_until_ready(wave(blk)),
            _grid_candidates("assign", **dims), **dims)
    blocks = tuning.get_blocks("assign", **dims)
    # Validate the fp32 path exactly; the timed path keeps the engine's
    # bf16 default, whose affinities drift but whose labels must agree.
    exact = np.asarray(jax.block_until_ready(
        assign_ops.assign(v, p, block_b=blocks["block_b"],
                          block_d2=blocks["block_d2"],
                          compute_dtype="fp32")[0]))
    labels = np.asarray(jax.block_until_ready(
        assign_ops.assign(v, p, block_b=blocks["block_b"],
                          block_d2=blocks["block_d2"])[1]))
    ref_labels = np.asarray(jax.block_until_ready(assign_ref(v, p)[1]))
    ok = (bool(np.allclose(exact, ref_out, rtol=1e-3, atol=1e-2))
          and bool((labels == ref_labels).all()))
    tuned_us = common.time_us(lambda: jax.block_until_ready(wave(blocks)))

    gap_default = looped_us / ref_us
    gap_tuned = tuned_us / ref_us
    shrink = gap_default / gap_tuned
    if not quick:
        assert shrink >= 5.0, (
            f"assign: wave kernel shrank the vs-jnp gap only "
            f"{shrink:.1f}x (< 5x)")
    records.append({
        "kernel": "assign", "shape": f"{b}x{d}x{k}x{t}", "dims": dims,
        "jnp_us": round(ref_us, 1),
        "default_us": round(looped_us, 1),
        "default_impl": "assign_looped (PR-7 per-arrival kernel)",
        "tuned_us": round(tuned_us, 1),
        "tuned_blocks": dict(blocks),
        "gap_default_vs_jnp": round(gap_default, 2),
        "gap_tuned_vs_jnp": round(gap_tuned, 2),
        "gap_shrink": round(shrink, 2),
        "validates": ok, "tuned": tune,
    })
    return common.row(
        f"kernel_assign_{b}x{d}x{k}x{t}", tuned_us,
        jnp_us=round(ref_us, 1), looped_us=round(looped_us, 1),
        gap_tuned_vs_jnp=round(gap_tuned, 2),
        gap_shrink_vs_looped=round(shrink, 2), validates=ok)


def _bench_engine_blockwise(rng, quick: bool) -> str:
    """Streaming R at a scale the dense path's Gram stack makes painful.

    Acceptance shape: N=2048 users, d=64, never materializing the
    (N, d, d) stack — peak Gram residency is block_users tiles.
    """
    n_users, n, d, k, block = ((256, 32, 64, 4, 64) if quick
                               else (2048, 32, 64, 4, 128))
    feats = jnp.asarray(rng.standard_normal((n_users, n, d)) * 0.3,
                        jnp.float32)
    cfg = sim.SimilarityConfig(top_k=k, block_users=block)
    eng = ProtocolEngine(cfg)
    result = {}

    def once():
        result["r"] = eng.similarity(feats).block_until_ready()

    us = common.time_us(once, n_iter=1, warmup=1)
    big_r = np.asarray(result["r"])
    return common.row(
        f"engine_blockwise_n{n_users}_d{d}", us,
        finite=bool(np.isfinite(big_r).all()),
        peak_gram_mb=round(block * d * d * 4 / 2**20, 2),
        dense_gram_mb=round(n_users * d * d * 4 / 2**20, 2))


def _bench_lps_round(rng, quick: bool) -> str:
    """Vectorized LPS round vs the seed per-client Python loop."""
    n_clients = 8 if quick else 32
    n_samples, m, steps, batch = 256, 64, 10, 32
    mcfg = mlp.PaperMLPConfig(m=m, hidden=32, n_classes=4)
    params = mlp.init(mcfg, jax.random.PRNGKey(0))
    loss_fn = mlp.loss_fn(mcfg)
    ccfg = fclient.ClientConfig(lr=0.05)
    xs = [rng.standard_normal((n_samples, m)).astype(np.float32)
          for _ in range(n_clients)]
    ys = [rng.integers(0, 4, n_samples).astype(np.int32)
          for _ in range(n_clients)]
    ns = [n_samples] * n_clients
    # One shared rng per path, same consumption order, so both paths train
    # on IDENTICAL batches and the speedup compares the same workload.
    loop_rng = np.random.default_rng(7)
    per_client = [fclient.make_batches(x, y, batch, steps, loop_rng)
                  for x, y in zip(xs, ys)]
    stacked = fclient.make_batch_stack(list(zip(xs, ys)), batch, steps,
                                       np.random.default_rng(7))

    def loop_round():
        client_params = []
        for b in per_client:
            new_p, _ = fclient.local_update(params, b, loss_fn, ccfg)
            client_params.append(new_p)
        return jax.block_until_ready(hier.lps_round(client_params, ns))

    def fused_round():
        new_p, _ = fclient.fused_lps_round(
            params, stacked, jnp.asarray(ns, jnp.float32), loss_fn, ccfg)
        return jax.block_until_ready(new_p)

    parity = all(
        np.allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
        for a, b in zip(jax.tree.leaves(loop_round()),
                        jax.tree.leaves(fused_round())))
    loop_us = common.time_us(loop_round, n_iter=3)
    fused_us = common.time_us(fused_round, n_iter=3)
    return common.row(
        f"lps_round_{n_clients}clients", fused_us,
        loop_us=round(loop_us, 1),
        speedup_vs_loop=round(loop_us / fused_us, 2),
        matches_loop=parity)


def run(quick: bool = False, tune: bool = False,
        json_path: str | None = None) -> list[str]:
    rng = np.random.default_rng(0)
    records: list[dict] = []
    rows = [
        _bench_gram(rng, quick, tune, records),
        _bench_eigproject(rng, quick, tune, records),
        _bench_gram_project(rng, quick, tune, records),
        _bench_featurize_gram(rng, quick, tune, records),
        _bench_linkage(rng, quick, tune, records),
        _bench_assign(rng, quick, tune, records),
        _bench_engine_blockwise(rng, quick),
        _bench_lps_round(rng, quick),
    ]
    if json_path:
        common.record_result(json_path, {
            "quick": quick, "tuned_sweep": tune,
            "tune_cache_file": str(tuning.cache_path() or ""),
            "grid": records,
        })
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: shrunken shapes, same code paths")
    ap.add_argument("--tune", action="store_true",
                    help="run the measured autotune sweep first (persists "
                         "when REPRO_TUNE_CACHE is set)")
    ap.add_argument("--json", default="benchmarks/results/bench_kernels.json",
                    help="where to record the tuned/default/jnp grid")
    args = ap.parse_args()
    for r in run(quick=args.quick, tune=args.tune, json_path=args.json):
        print(r, flush=True)
