"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy and idle time over the traced window,
device time per jitted program and per operation, and the longest idle
gaps named by what the host was doing.

``load_events`` flattens the trace into plain event dicts; ``reduce``
works on those alone, so a small recorded trace kept as JSON
(``tests/data``) checks the arithmetic without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"            # host annotation around the stretch
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


def load_events(path: str) -> list[dict]:
    """Every event of an ``.xplane.pb`` as ``{plane, line, name, start_ns,
    dur_ns}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns})
    return out


def program_name(module: str) -> str:
    """``jit__raw_finish(123)`` -> ``jit__raw_finish``."""
    return _SUFFIX.sub("", module)


def op_name(op: str) -> str:
    """An operation event is named by its HLO text; keep the instruction's
    name: ``%fusion.7 = bf16[...] fusion(...)`` -> ``fusion.7``."""
    return op.split(" = ", 1)[0].lstrip("%")


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap_ns(a: list[tuple[float, float]],
                b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two sorted, disjoint interval
    lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _host_labeller(host: list[dict]):
    """``t -> name`` of the innermost host annotation covering ``t``.

    Annotations of one thread nest, so the innermost one covering ``t``
    is the covering one that started last."""
    host = sorted(host, key=lambda e: e["start_ns"])
    starts = [e["start_ns"] for e in host]

    def label(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        for ev in host[max(0, i - 64):i + 1][::-1]:
            if ev["start_ns"] + ev["dur_ns"] >= t:
                return ev["name"]
        return "host (no annotation)"

    return label


def _module_labeller(mods: list[dict]):
    """``op -> "<program>/"`` of the program running on the op's device
    plane when the op starts (``""`` if none)."""
    by_plane: dict[str, list[dict]] = defaultdict(list)
    for m in mods:
        by_plane[m["plane"]].append(m)
    starts = {}
    for plane, ms in by_plane.items():
        ms.sort(key=lambda m: m["start_ns"])
        starts[plane] = [m["start_ns"] for m in ms]

    def label(op: dict) -> str:
        ms = by_plane.get(op["plane"], [])
        i = bisect.bisect_right(starts.get(op["plane"], []),
                                op["start_ns"]) - 1
        if i >= 0 and ms[i]["start_ns"] + ms[i]["dur_ns"] >= op["start_ns"]:
            return program_name(ms[i]["name"]) + "/"
        return ""

    return label


def reduce(events: list[dict], n_chips: int = 1,
           annotations: tuple[str, ...] = ("bench.", "oneshot.", "protocol.",
                                           "signature.", "cluster.",
                                           "membership.", "trainer."),
           top: int = 10) -> dict | None:
    """Busy/idle and per-program device time of the traced window.

    The window is the ``bench.window`` host annotation (or, without one,
    the span of all device operations).  Busy is the union of the
    device's operation intervals inside it, averaged over the device
    planes that ran anything.  ``busy_in[name]`` is the busy time inside
    the host annotations of that name (the ``repro.obs`` spans, which
    block on the device before they close), whatever programs ran there.
    Returns ``None`` when no device operation was recorded.  An operation
    is named ``<program>/<instruction>``.
    """
    dev_ops = [e for e in events if e["plane"].startswith(DEVICE_PREFIX)
               and e["line"] == OPS_LINE]
    if not dev_ops:
        return None
    mods = [e for e in events if e["plane"].startswith(DEVICE_PREFIX)
            and e["line"] == MODULES_LINE]
    host = [e for e in events if not e["plane"].startswith(DEVICE_PREFIX)
            and e["name"].startswith(annotations)]
    win = [e for e in events if e["name"] == WINDOW]
    if win:
        lo = min(e["start_ns"] for e in win)
        hi = max(e["start_ns"] + e["dur_ns"] for e in win)
    else:
        lo = min(e["start_ns"] for e in dev_ops)
        hi = max(e["start_ns"] + e["dur_ns"] for e in dev_ops)
    window_ns = hi - lo
    planes = sorted({e["plane"] for e in dev_ops})[:n_chips]

    label = _host_labeller(host)
    spans: dict[str, list] = defaultdict(list)
    for e in host:
        spans[e["name"]].append((e["start_ns"], e["start_ns"] + e["dur_ns"]))
    spans = {k: _union(_clip(v, lo, hi)) for k, v in spans.items()}
    busy_ns, gaps, busy_in = 0.0, defaultdict(float), defaultdict(float)
    for plane in planes:
        iv = _union(_clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                           for e in dev_ops if e["plane"] == plane], lo, hi))
        busy_ns += sum(e - s for s, e in iv)
        for name, sp in spans.items():
            busy_in[name] += _overlap_ns(iv, sp) / 1e9 / len(planes)
        edges = [lo] + [x for se in iv for x in se] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps[label((s + e) / 2)] += (e - s) / 1e9 / len(planes)
    busy_ns /= len(planes)

    def clipped_seconds(ev):
        s, e = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
        return max(0.0, min(e, hi) - max(s, lo)) / 1e9

    in_module = _module_labeller(mods)
    ops, programs = defaultdict(float), {}
    for e in dev_ops:
        if e["plane"] in planes and clipped_seconds(e) > 0:
            ops[in_module(e) + op_name(e["name"])] += (clipped_seconds(e)
                                                       / len(planes))
    for e in mods:
        if e["plane"] not in planes or clipped_seconds(e) <= 0:
            continue
        p = programs.setdefault(program_name(e["name"]),
                                {"count": 0, "seconds": 0.0})
        p["count"] += 1
        p["seconds"] += clipped_seconds(e) / len(planes)

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]

    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "busy_in": dict(busy_in), "programs": programs,
            "top_ops": ranked(ops), "idle_gaps": ranked(gaps)}


def reduce_dir(trace_dir: str, n_chips: int = 1) -> dict | None:
    """``reduce`` of the newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    return reduce(load_events(max(paths, key=os.path.getmtime)), n_chips)


def program_seconds(reduced: dict | None, patterns: tuple[str, ...]
                    ) -> tuple[int, float] | None:
    """Executions and device seconds of the programs whose name matches
    any of ``patterns`` (regular expressions); ``None`` if none ran."""
    if not reduced:
        return None
    count, secs = 0, 0.0
    for name, p in reduced["programs"].items():
        if any(re.search(pat, name) for pat in patterns):
            count += p["count"]
            secs += p["seconds"]
    return (count, secs) if count else None
