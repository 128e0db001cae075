"""Every per-layer metric reader on a reading worked by hand, and on an
empty one, where it reads nothing."""
import json

import pytest

from bench_tiny import ROOT

from bench import costs, harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = harness.peaks_for("TPU v5 lite")


def _reading(workload, spans, device, host):
    _, cell, cfg, mix, limits = harness.resolve(ROOT / "bench", workload)
    ctx = harness.Ctx(workload=workload, seed=7, seconds=1.0, trace=True,
                      cfg=cfg, mix=mix, limits=limits,
                      bench_dir=ROOT / "bench")
    win = harness.Window(e2e={}, ops=2, elapsed=1.0, attempted=2, failed=0,
                         host=host)
    return harness.Reading(ctx=ctx, window=win, spans=spans, device=device,
                           peaks=PEAKS)


def _span(name, ms):
    return {"name": name, "dur_us": 1e3 * ms}


def _device(programs, busy=0.6, window=1.0, busy_in=None):
    return {"busy_s": busy, "window_s": window, "top_ops": [],
            "idle_gaps": [], "busy_in": busy_in or {},
            "programs": {k: {"count": c, "seconds": s}
                         for k, (c, s) in programs.items()}}


ONESHOT = dict(
    spans=[_span("oneshot.run", 500), _span("oneshot.run", 500),
           _span("signature.accumulate_grams", 30),
           _span("signature.accumulate_grams", 40),
           _span("cluster.hac", 50), _span("cluster.cut", 10),
           _span("cluster.hac", 50), _span("cluster.cut", 10)],
    device=_device({"jit__chunk_gram_accum": (8, 0.030),
                    "jit__raw_finish": (2, 0.800)},
                   busy_in={"signature.accumulate_grams": 0.040}),
    host={})

EXPECTED_ONESHOT = {
    "ingest_ms": 35.0, "hac_ms": 60.0, "relevance_ms": 400.0,
    "idle_share.oneshot": 40.0,
    # cifar10: 1024 x 256 rows of m=3072 to d=128; bytes bind ingest;
    # the device is busy 40 ms inside the ingest spans of the 2 runs
    "ingest_roofline": 100.0 * costs.least_seconds(
        *costs.ingest(1024 * 256, 3072, 128, 1024), PEAKS) / 0.020,
    "oneshot_roofline": 100.0 * costs.least_seconds(
        *costs.oneshot(1024 * 256, 1024, 3072, 128, 8), PEAKS) / 0.5,
}


def _cell_metrics(workload):
    return [m["name"] for m in harness.cell_metrics(BENCH, workload, True)]


def test_oneshot_metrics_by_hand():
    r = _reading("cifar10.oneshot", **ONESHOT)
    names = _cell_metrics("cifar10.oneshot")
    assert sorted(names) == sorted(EXPECTED_ONESHOT)
    for name in names:
        got = harness.load_module(ROOT / "bench" / "metrics"
                                  / f"{name}.py").read(r)
        assert got == pytest.approx(EXPECTED_ONESHOT[name]), name


def test_train_metrics_by_hand():
    r = _reading("cifar10.mthfl", spans=[],
                 device=_device({"jit__unknown": (20, 5.0),
                                 "jit_add": (7, 0.1)}, busy=5.5, window=10),
                 host={"train_samples_per_s": 1e5, "rounds": 20})
    cnn = r.ctx.cfg["trainer"]["cnn"]
    want = {"round_device_ms": 250.0, "idle_share.train": 45.0,
            "train_mfu": 100.0 * costs.cnn_train_flops(cnn) * 1e5
            / PEAKS["flops_bf16"]}
    for name in _cell_metrics("cifar10.mthfl"):
        got = harness.load_module(ROOT / "bench" / "metrics"
                                  / f"{name}.py").read(r)
        assert got == pytest.approx(want[name]), name
    # a round program that ran another number of times than the rounds
    # is not the fused round: the reading stands down
    r.window.host["rounds"] = 30
    assert harness.load_module(ROOT / "bench" / "metrics"
                               / "round_device_ms.py").read(r) is None


def test_arrivals_metrics_by_hand():
    r = _reading("fmnist.arrivals", spans=[],
                 device=_device({}, busy=0.004, window=4.0),
                 host={"waves": 100, "wave_service_ms": 15.0})
    want = {"assign_device_us": 40.0, "wave_service_ms": 15.0,
            "idle_share.arrivals": 99.9}
    for name in _cell_metrics("fmnist.arrivals"):
        got = harness.load_module(ROOT / "bench" / "metrics"
                                  / f"{name}.py").read(r)
        assert got == pytest.approx(want[name]), name


@pytest.mark.parametrize("workload", ["cifar10.oneshot", "fmnist.oneshot",
                                      "cifar10.mthfl", "fmnist.arrivals"])
def test_metrics_read_nothing_from_an_empty_reading(workload):
    r = _reading(workload, spans=[], device=None, host={})
    for name in _cell_metrics(workload):
        got = harness.load_module(ROOT / "bench" / "metrics"
                                  / f"{name}.py").read(r)
        assert got is None, name
