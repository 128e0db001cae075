"""A copy of the benchmark's files with every configuration cut to a size
the CPU runs in seconds (Pallas kernels interpreted), for tests."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY = {
    "cifar10": {
        "oneshot": dict(n_users=32, rows=32, m=64, d=16, top_k=4,
                        chunk_rows=16, n_tasks=4, task_weights=[1] * 4,
                        task_rank=3, gen_block=8, min_rows=None),
        # the cell's local steps and batch, so that one round's update is
        # as large as the cell's and its control departs as far
        "trainer": dict(clients_per_task=2, images_per_client=40,
                        eval_per_class=4, global_rounds=3, local_steps=30,
                        batch_size=32, check_rounds=3),
    },
    "fmnist": {
        "oneshot": dict(n_users=32, rows=32, m=48, d=16, top_k=4,
                        chunk_rows=16, task_rank=3, gen_block=8,
                        min_rows=20),
        "membership": dict(seed_users=32, pool=64, capacity=128, rows=16,
                           min_rows=12, m=48, d=16, top_k=4, gen_block=16,
                           task_rank=3),
    },
}
TINY_MIX = {"arrivals": dict(wave_sizes=[4, 12], wave_period_ms=20.0,
                             trace_seconds=1),
            "oneshot": dict(trace_seconds=1), "mthfl": dict(trace_seconds=1)}


def tiny_copy(dst: Path) -> Path:
    """``dst`` with ``BENCHMARK.json`` and a shrunken ``bench/``; returns
    the copy's ``bench`` directory."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    for name, groups in TINY.items():
        p = dst / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(p.read_text())
        for group, vals in groups.items():
            cfg[group].update(vals)
        p.write_text(json.dumps(cfg))
    for name, vals in TINY_MIX.items():
        p = dst / "bench" / "traffic" / f"{name}.json"
        p.write_text(json.dumps(dict(json.loads(p.read_text()), **vals)))
    return dst / "bench"


def run_cell(bench_dir: Path, workload: str, seed: int = 5,
             seconds: float = 0.5, trace: int = 0, capsys=None) -> dict:
    """Drive a whole run on the CPU; returns the parsed last line."""
    from bench.harness import main

    rc = main(["--workload", workload, "--seed", str(seed), "--seconds",
               str(seconds), "--trace", str(trace)],
              bench_dir=bench_dir, require_tpu=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
