"""The trace reduction on events whose busy time, idle gaps and program
times are known by hand, on a small trace recorded on a TPU v5e, and on
a trace recorded here."""
import json
from pathlib import Path

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import xtrace  # noqa: E402

DATA = Path(__file__).parent / "data"
DEV = "/device:TPU:0"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def test_reduce_by_hand():
    events = [
        ev("/host:CPU", "python", "bench.window", 0, 1000),
        ev("/host:CPU", "python", "bench.op", 0, 600),
        ev("/host:CPU", "python", "bench.wait", 600, 400),
        ev(DEV, "XLA Modules", "jit_a(1)", 100, 200),
        ev(DEV, "XLA Modules", "jit_a(2)", 700, 100),
        ev(DEV, "XLA Modules", "jit_b(3)", 350, 150),
        ev(DEV, "XLA Ops", "fusion.1", 100, 200),
        ev(DEV, "XLA Ops", "fusion.2", 250, 100),   # overlaps fusion.1
        ev(DEV, "XLA Ops", "dot.3", 400, 100),
        ev(DEV, "XLA Ops", "fusion.1", 700, 100),
        ev(DEV, "XLA Ops", "late", 1500, 100),      # outside the window
    ]
    r = xtrace.reduce(events)
    # busy: [100, 350) + [400, 500) + [700, 800) = 450 ns of 1000
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["programs"]["jit_a"] == {"count": 2,
                                      "seconds": pytest.approx(300e-9)}
    assert r["programs"]["jit_b"]["seconds"] == pytest.approx(150e-9)
    ops = dict(r["top_ops"])
    assert ops["jit_a/fusion.1"] == pytest.approx(300e-9)
    assert ops["jit_b/dot.3"] == pytest.approx(100e-9)
    assert ops["jit_a/fusion.2"] == pytest.approx(100e-9)
    assert not any(k.endswith("late") for k in ops)
    gaps = dict(r["idle_gaps"])
    # a gap goes whole to the annotation at its midpoint: [0,100) and
    # [350,400) to bench.op, [500,700) and [800,1000) to bench.wait
    assert gaps["bench.op"] == pytest.approx(150e-9)
    assert gaps["bench.wait"] == pytest.approx(400e-9)
    # device busy inside each annotation, whatever program ran there
    assert r["busy_in"]["bench.op"] == pytest.approx(350e-9)
    assert r["busy_in"]["bench.wait"] == pytest.approx(100e-9)
    assert r["busy_in"]["bench.window"] == pytest.approx(450e-9)
    assert xtrace.program_seconds(r, (r"jit_a",)) == (2, pytest.approx(
        300e-9))
    assert xtrace.program_seconds(r, (r"nothing",)) is None


def test_reduce_without_device_ops_is_none():
    assert xtrace.reduce([ev("/host:CPU", "python", "bench.op", 0, 5)]) \
        is None


def test_recorded_tpu_trace():
    """Events of a short traced stretch of ``fmnist.arrivals`` on one TPU
    v5e, cut to a few waves; the expected numbers were read from the
    events by hand."""
    rec = json.loads((DATA / "tpu_arrivals_events.json").read_text())
    r = xtrace.reduce(rec["events"])
    exp = rec["expected"]
    assert r["window_s"] == pytest.approx(exp["window_s"])
    assert r["busy_s"] == pytest.approx(exp["busy_s"])
    for name, secs in exp["programs"].items():
        assert r["programs"][name]["seconds"] == pytest.approx(secs)


def test_load_events_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xtrace.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    paths = list(tmp_path.rglob("*.xplane.pb"))
    assert paths
    events = xtrace.load_events(str(paths[0]))
    assert any(e["name"] == xtrace.WINDOW for e in events)
    # the CPU backend has no device plane: nothing to reduce
    assert xtrace.reduce_dir(str(tmp_path)) is None
