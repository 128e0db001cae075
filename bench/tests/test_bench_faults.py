"""Each cell's comparison fails what it exists to catch: a whole run on
the CPU (the harness's look for a chip skipped) with the timed path
broken underneath comes out not correct, and so does each cell's control
(the plain reference one precision lower, or the program's own
lower-precision path) at a size a test run can hold."""
import json

import pytest

from bench_tiny import run_cell, tiny_copy

from bench import faults  # noqa: E402


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("tiny"))


FAULTS = [(w, f) for w, d in (("cifar10.oneshot", "oneshot"),
                                ("fmnist.oneshot", "oneshot"),
                                ("fmnist.arrivals", "arrivals"),
                                ("cifar10.mthfl", "mthfl"))
          for f in faults.BY_DRIVER[d]]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_broken_timed_path_is_not_correct(tiny, workload, fault,
                                          monkeypatch, capsys):
    fault(monkeypatch.setattr)
    line = run_cell(tiny, workload, capsys=capsys)
    assert line["correct"] is False, json.dumps(line["checks"])


@pytest.mark.parametrize("workload", ["cifar10.oneshot", "fmnist.oneshot",
                                      "fmnist.arrivals", "cifar10.mthfl"])
def test_control_is_not_correct(tiny, workload):
    from bench import harness

    _, _, cfg, mix, limits = harness.resolve(tiny, workload)
    ctx = harness.Ctx(workload=workload, seed=17, seconds=0.3, trace=False,
                      cfg=cfg, mix=mix, limits=limits, bench_dir=tiny)
    driver = harness.load_module(tiny / "drivers" / f"{mix['driver']}.py")
    state = driver.setup(ctx)
    driver.window(state, 0.3)
    got = driver.control(state)
    assert any(got[k] > lim["limit"] for k, lim in limits.items()), got
