"""The benchmark harness: one cell, one seed, one process.

    python3 bench/run.py --workload cifar10.oneshot --seed 7 --seconds 30 --trace 0

A cell ``<config>.<mix>`` is an entry of ``BENCHMARK.json``'s
``workloads``.  Everything that belongs to one configuration, one traffic
mix or one per-layer metric sits in a file of its own, found by name:

* ``configs/<config>.json``     the deployment's sizes, source and cuts;
* ``traffic/<mix>.json``        the traffic parameters; its ``driver`` key
                                names the general generator
                                ``drivers/<driver>.py`` that reads them;
* ``limits/<cell>.json``        each number that decides ``correct``, with
                                its limit and the readings it came from;
* ``metrics/<metric>.py``       one per-layer metric: what it reads and
                                its arithmetic (``read(reading)``);
* ``peaks.json``                the chip's peaks, keyed by device kind.

A driver module defines ``setup(ctx) -> state`` (data on the device and
every shape warmed up), ``window(state, seconds) -> Window`` (whole
operations back to back until the first boundary after ``seconds``) and
``check(state) -> list[Check]`` (what the window produced against the
plain reference under ``reference/``).

``--trace 0`` keeps ``repro.obs`` off and prints the cell's end-to-end
metrics.  ``--trace 1`` runs a shorter stretch (the mix's
``trace_seconds``) with ``repro.obs`` on and the JAX profiler recording,
and prints the cell's per-layer metrics.  Both check correctness.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and ``checks`` last: every number compared, with its limit.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell's files as loaded, and the run's knobs."""

    workload: str
    seed: int
    seconds: float
    trace: bool
    cfg: dict
    mix: dict
    limits: dict
    bench_dir: Path


@dataclasses.dataclass
class Window:
    """What a measured window did.

    ``e2e``: end-to-end metric values (only those the cell declares are
    printed).  ``ops``: whole operations completed.  ``elapsed``: seconds
    on the host clock from the first operation's start to the last one's
    end.  ``host``: extra host-clock readings per-layer metrics may use.
    """

    e2e: dict
    ops: int
    elapsed: float
    attempted: int
    failed: int
    host: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Check:
    """One number compared with the plain reference, and its limit:
    the check passes when ``value <= limit``."""

    name: str
    value: float
    limit: float

    def __post_init__(self):
        self.value = float(self.value)
        self.limit = float(self.limit)

    @property
    def ok(self) -> bool:
        return bool(math.isfinite(self.value) and self.value <= self.limit)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader reads in a traced run."""

    ctx: Ctx
    window: Window
    spans: list            # repro.obs span records of the traced stretch
    device: dict | None    # xtrace.reduce() of the profiler trace
    peaks: dict            # this chip's row of peaks.json


def load_module(path: Path, name: str | None = None):
    """Import a file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + "_".join(path.parts[-2:]).replace(".", "_")
        .replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def peaks_for(kind: str, bench_dir: Path = BENCH) -> dict:
    """The peaks row of ``device_kind``; an unlisted kind is an error."""
    table = load_json(bench_dir / "peaks.json")
    rows = table["devices"]
    if kind not in rows:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(listed: {sorted(rows)})")
    return rows[kind]


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` prints: its end-to-end metrics
    with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    def listed(m, default):
        return workload in m["workloads"] if "workloads" in m else default

    e2e = [m for m in bench["end_to_end"] if listed(m, True)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"] if listed(m, m["moves"] in names)]


def compile_counter() -> dict:
    """Counts of JAX traces and backend compiles from ``jax.monitoring``
    events, so the harness can show that none falls inside a window."""
    import jax

    counts = {"traces": 0, "compiles": 0, "cache_hits": 0}

    def on_duration(event: str, duration: float, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            counts["traces"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return counts


def use_checkout_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, so only a checkout's first run compiles."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def resolve(bench_dir: Path, workload: str) -> tuple[dict, ...]:
    """``BENCHMARK.json`` entry of the cell, its config, mix and limits."""
    bench = load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"cells: {sorted(cells)}")
    cell = cells[workload]
    cfg = load_json(bench_dir / "configs" / f"{cell['config']}.json")
    mix = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{workload}.json")
    return bench, cell, cfg, mix, limits


def traced_window(driver, state, ctx: Ctx, seconds: float, trace_dir: str):
    """The traced stretch: ``repro.obs`` on, spans annotated into the
    profiler's trace, ``bench.window`` around the whole stretch."""
    import jax
    from repro import obs

    obs.reset()
    obs.enable()
    obs.configure(profiler=True)
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            win = driver.window(state, seconds)
    finally:
        jax.profiler.stop_trace()
        spans = obs.trace_records()
        obs.configure(profiler=False)
        obs.disable()
    return win, spans


def main(argv=None, *, bench_dir: Path = BENCH,
         require_tpu: bool = True) -> int:
    t_start = time.perf_counter()
    args = parse_args(argv)
    bench, cell, cfg, mix, limits = resolve(bench_dir, args.workload)
    root = bench_dir.parent
    if require_tpu:
        use_checkout_cache(root)
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devs[0].platform!r}); "
              "nothing measured", file=sys.stderr)
        return 3
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chip(s), "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 3
    from bench import xtrace

    kind = devs[0].device_kind
    peaks = peaks_for(kind, bench_dir) if require_tpu else {}
    ctx = Ctx(workload=args.workload, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), cfg=cfg, mix=mix, limits=limits,
              bench_dir=bench_dir)
    driver = load_module(bench_dir / "drivers" / f"{mix['driver']}.py")
    counts = compile_counter()

    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    before = dict(counts)
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        win, spans = traced_window(driver, state, ctx,
                                   min(args.seconds, mix["trace_seconds"]),
                                   trace_dir)
    else:
        win, spans = driver.window(state, args.seconds), []
    in_window = {k: counts[k] - before[k] for k in ("traces", "compiles")}
    print(f"bench: setup {setup_s:.3f} s; window {win.elapsed:.3f} s, "
          f"{win.ops} ops; traces in window {in_window['traces']}, "
          f"compiles in window {in_window['compiles']}; persistent cache "
          f"hits {counts['cache_hits']}", file=sys.stderr, flush=True)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell["chips"]])

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result: dict = {}
    metrics = {}
    if args.trace:
        reduced = xtrace.reduce_dir(trace_dir, n_chips=cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        reading = Reading(ctx=ctx, window=win, spans=spans, device=reduced,
                          peaks=peaks)
        for m in cell_metrics(bench, args.workload, trace=True):
            reader = load_module(bench_dir / "metrics" / f"{m['name']}.py")
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["top_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        e2e = dict(win.e2e, setup_s=setup_s)
        for m in cell_metrics(bench, args.workload, trace=False):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    checks = driver.check(state)
    correct = all(c.ok for c in checks)
    for c in checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": correct, "attempted": win.attempted,
            "failed": win.failed, "metrics": metrics, "device": device}
    line.update(result)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    print(json.dumps(line), flush=True)
    return 0
