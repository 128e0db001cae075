"""Roofline bench: per-kernel achieved-vs-roof measurements plus the
dry-run artifact table.

Part 1 (new, the tile-plan justification loop): for every Pallas kernel
family, resolve the tuned tile plan through ``kernels.tuning``, compute
the analytic roofline floor for that plan on the detected hardware
(``launch.roofline.kernel_roofline`` — bytes depend on how the plan
re-streams operands, so a bad plan shows up as a higher roof BEFORE any
timing), then time the kernel and record achieved vs roof.  On an
accelerator ``roof_frac`` is a utilization number; in interpret mode the
achieved time is dominated by the interpreter so the roof is reported as
the floor the same plan would hit lowered — the ``assign`` family also
gets an int8-directory row (itemsize 1) showing the memory-term drop the
quantized directory buys.

Part 2 (deliverable g, unchanged): reads the dry-run artifacts written by
``repro.launch.dryrun`` and emits one row per (arch x shape x mesh) with
the three roofline terms, the dominant bottleneck, and the useful-FLOPs
ratio.  Rows are omitted (with a notice) if the sweep has not produced
the artifact yet.

Standalone: ``PYTHONPATH=src:. python benchmarks/bench_roofline.py --quick``
(``--peak-flops`` overrides the detected compute roof, e.g. to model a
target part from a host).
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.kernels import dispatch, quant, tuning
from repro.kernels.assign import ops as assign_ops
from repro.kernels.eigproject import ops as proj_ops
from repro.kernels.featurize_gram import ops as fg_ops
from repro.kernels.gram import ops as gram_ops
from repro.kernels.gram_project import ops as gp_ops
from repro.kernels.linkage import ops as link_ops
from repro.launch import roofline as RL

DRYRUN_DIR = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def _kernel_cases(rng, quick: bool) -> list[dict]:
    """One case per kernel family: inputs, cost dims, and a runner that
    takes the resolved tile plan."""
    n, d, k = (512, 128, 64) if quick else (2048, 256, 128)
    m = 256 if quick else 512
    nl = 1024 if quick else 8192
    b, dd, t = (64, 32, 8) if quick else (256, 32, 16)

    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((d, k)), jnp.float32)
    xm = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((m, d)) / np.sqrt(m), jnp.float32)
    g = jnp.asarray(rng.standard_normal((d, d)), jnp.float32)
    ra = jnp.asarray(rng.standard_normal(nl), jnp.float32)
    rb = jnp.asarray(rng.standard_normal(nl), jnp.float32)
    mask = jnp.asarray((rng.random(nl) > 0.2).astype(np.float32))
    vw = jnp.asarray(rng.standard_normal((b, dd, 8)), jnp.float32)
    protos = jnp.asarray(rng.standard_normal((t, dd, dd)), jnp.float32)
    q8, sc8 = quant.quantize_directory(protos, "int8")

    cases = [
        dict(kernel="gram", tune_dims=dict(n=n, d=d),
             cost_dims=dict(n=n, d=d), itemsize=4,
             run=lambda blk: gram_ops.gram_matrix(
                 x, block_n=blk["block_n"], block_d=blk["block_d"])),
        dict(kernel="gram_project", tune_dims=dict(n=n, k=k),
             cost_dims=dict(n=n, d=d, k=k), itemsize=4,
             run=lambda blk: gp_ops.gram_project(
                 x, v, block_n=blk["block_n"], block_k=blk["block_k"],
                 double_buffer=blk.get("double_buffer", False))),
        dict(kernel="featurize_gram",
             tune_dims=dict(n=n, m=m, d=d, itemsize=4),
             cost_dims=dict(n=n, m=m, d=d), itemsize=4,
             run=lambda blk: fg_ops.featurize_gram(
                 xm, w, block_n=blk["block_n"],
                 double_buffer=blk.get("double_buffer", False))),
        dict(kernel="eigproject", tune_dims=dict(d=d, k=k),
             cost_dims=dict(d=d, k=k), itemsize=4,
             run=lambda blk: proj_ops.project_norms(
                 g, v, block_c=blk["block_c"])),
        dict(kernel="linkage", tune_dims=dict(n=nl),
             cost_dims=dict(n=nl), itemsize=4,
             run=lambda blk: link_ops.linkage_step(
                 ra, rb, 2.0, 3.0, mask, block=blk["block"])[0]),
        dict(kernel="assign", tune_dims=dict(b=b, d2=dd * dd),
             cost_dims=dict(b=b, d2=dd * dd, t=t), itemsize=4,
             run=lambda blk: assign_ops.assign(
                 vw, protos, block_b=blk["block_b"],
                 block_d2=blk["block_d2"])[0]),
        dict(kernel="assign", variant="int8",
             tune_dims=dict(b=b, d2=dd * dd),
             cost_dims=dict(b=b, d2=dd * dd, t=t), itemsize=1,
             run=lambda blk: assign_ops.assign(
                 vw, q8, scales=sc8, block_b=blk["block_b"],
                 block_d2=blk["block_d2"])[0]),
    ]
    return cases


def run_kernels(quick: bool, hw: RL.HardwareSpec,
                records: list[dict]) -> list[str]:
    rng = np.random.default_rng(1)
    rows = []
    interp = not dispatch.supports_lowering()
    for case in _kernel_cases(rng, quick):
        name = case["kernel"]
        tag = name + (f"_{case['variant']}" if "variant" in case else "")
        blocks = tuning.get_blocks(name, **case["tune_dims"])
        roof = RL.kernel_roofline(name, blocks, hw=hw,
                                  itemsize=case["itemsize"],
                                  **case["cost_dims"])
        us = common.time_us(
            lambda: jax.block_until_ready(case["run"](blocks)), n_iter=3)
        achieved_s = us * 1e-6
        records.append({
            "kernel": tag, "dims": case["cost_dims"],
            "blocks": dict(blocks), "hw": hw.name,
            "interpret": interp,
            "flops": roof["flops"], "bytes": roof["bytes"],
            "roof_s": roof["roof_s"], "bound": roof["bound"],
            "arithmetic_intensity": round(
                roof["arithmetic_intensity"], 3),
            "achieved_s": achieved_s,
            "roof_frac": (roof["roof_s"] / achieved_s
                          if achieved_s else 0.0),
        })
        rows.append(common.row(
            f"kernel_roof_{tag}", us,
            roof_us=round(roof["roof_s"] * 1e6, 2),
            bound=roof["bound"],
            intensity=round(roof["arithmetic_intensity"], 1),
            roof_frac=round(roof["roof_s"] / achieved_s, 4),
            interpret=interp))
    return rows


def run_dryrun_table(records: list[dict] | None = None) -> list[str]:
    rows = []
    files = sorted(DRYRUN_DIR.glob("*.json")) if DRYRUN_DIR.exists() else []
    if not files:
        return [common.row("roofline_no_artifacts", 0.0,
                           note="run repro.launch.dryrun first")]
    n_ok = n_fail = 0
    for f in files:
        r = json.loads(f.read_text())
        if r.get("status") != "ok":
            n_fail += 1
            rows.append(common.row(f"roofline_{f.stem}", 0.0, status="FAIL",
                                   error=r.get("error", "?")[:80]))
            continue
        n_ok += 1
        roof = r["roofline"]
        if records is not None:
            records.append({"artifact": f.stem, **roof})
        rows.append(common.row(
            f"roofline_{f.stem}", 0.0,
            compute_s=round(roof["compute_term_s"], 5),
            memory_s=round(roof["memory_term_s"], 5),
            collective_s=round(roof["collective_term_s"], 5),
            bottleneck=roof["bottleneck"],
            useful_flops_ratio=round(roof["useful_flops_ratio"], 3),
            hbm_gb=round(r["memory"].get("total_hbm_bytes", 0) / 2 ** 30, 2),
            compile_s=r.get("compile_s")))
    rows.append(common.row("roofline_summary", 0.0, ok=n_ok, fail=n_fail))
    return rows


def run(quick: bool = False, peak_flops: float | None = None,
        json_path: str | None = None) -> list[str]:
    hw = RL.detect_hardware(peak_flops=peak_flops)
    kernel_records: list[dict] = []
    dryrun_records: list[dict] = []
    rows = run_kernels(quick, hw, kernel_records)
    rows += run_dryrun_table(dryrun_records)
    if json_path:
        common.record_result(json_path, {
            "quick": quick, "hw": hw.name,
            "peak_flops": hw.peak_flops, "hbm_bw": hw.hbm_bw,
            "kernels": kernel_records,
            "dryrun_artifacts": dryrun_records,
        })
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: shrunken shapes, same code paths")
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="override the detected peak FLOP/s (model a "
                         "target part from a host)")
    ap.add_argument("--json", default="benchmarks/results/bench_roofline.json",
                    help="where to record the achieved-vs-roof grid")
    args = ap.parse_args()
    for r in run(quick=args.quick, peak_flops=args.peak_flops,
                 json_path=args.json):
        print(r, flush=True)
