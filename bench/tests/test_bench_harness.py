"""The harness on the CPU: refusal without a chip, the result line, and
cells found by name from files alone."""
import json
import os
import subprocess
import sys

import pytest

from bench_tiny import ROOT, run_cell, tiny_copy

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("tiny"))


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_exits_nonzero_without_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cifar10.oneshot",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    (tmp_path / "bench").mkdir()
    for item in (ROOT / "bench").iterdir():
        if item.name not in ("tests", "__pycache__"):
            dst = tmp_path / "bench" / item.name
            if item.is_dir():
                import shutil
                shutil.copytree(item, dst)
            else:
                dst.write_bytes(item.read_bytes())
    (tmp_path / "BENCHMARK.json").write_bytes(
        (ROOT / "BENCHMARK.json").read_bytes())
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cifar10.oneshot",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", ["cifar10.oneshot", "fmnist.oneshot",
                                      "fmnist.arrivals", "cifar10.mthfl"])
def test_each_cell_runs_tiny_and_prints_the_contract(tiny, workload,
                                                     capsys):
    line = run_cell(tiny, workload, capsys=capsys)
    assert list(line) == KEYS
    assert line["correct"] is True
    bench = json.loads((tiny.parent / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) == e2e
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())


def test_traced_run_reads_span_metrics(tiny, capsys):
    line = run_cell(tiny, "fmnist.oneshot", trace=1, capsys=capsys)
    assert line["correct"] is True
    # no device plane on the CPU: only the span readers find something
    assert set(line["metrics"]) == {"ingest_ms", "hac_ms"}
    assert all(v["value"] > 0 for v in line["metrics"].values())


def test_new_config_mix_and_metric_are_files_only(tmp_path, capsys):
    bench_dir = tiny_copy(tmp_path)
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = json.loads((bench_dir / "configs" / "fmnist.json").read_text())
    cfg["oneshot"].update(n_tasks=2, task_weights=[1, 1], m=32)
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "burst.json").write_text(json.dumps(
        {"driver": "oneshot", "warmup_ops": 1, "trace_seconds": 1}))
    (bench_dir / "limits" / "toy.burst.json").write_text(
        (bench_dir / "limits" / "fmnist.oneshot.json").read_text())
    (bench_dir / "metrics" / "span_count.py").write_text(
        "def read(r):\n    return float(len(r.spans)) or None\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.burst", "config": "toy",
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "span_count", "unit": "spans",
                               "better": "lower", "source": "program_span",
                               "layer": "test", "moves": "oneshot_s",
                               "workloads": ["toy.burst"]})
    for m in bench["end_to_end"]:
        if m["name"] == "oneshot_s":
            m["workloads"].append("toy.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    line = run_cell(bench_dir, "toy.burst", trace=1, capsys=capsys)
    assert line["correct"] is True
    assert line["metrics"]["span_count"]["value"] > 0
    for p, data in before.items():
        assert p.read_bytes() == data
