"""Tracing pillar: nested, thread-safe spans with device-sync-aware
timing.

    with obs.span("protocol.run", impl="dense") as sp:
        out = sp.sync(engine_dispatch(...))   # registered for sync

At span exit the registered values are ``jax.block_until_ready``-ed
before the clock is read, so ``dur_us`` measures device work, not just
async dispatch latency.  The time spent in that block is recorded as
``wait_us`` (only on spans that registered values), so ``dur_us -
wait_us`` is the span's host part.  Pass ``sync=False`` (or register
nothing) for async paths where blocking would serialize a pipeline.

Spans nest per-thread via a thread-local stack; completed spans append
to one process-global record list exported as JSONL (``save_trace``) or
rendered as an indented tree (``format_tree``).  With
``obs.configure(profiler=True)`` each span also enters a
``jax.profiler.TraceAnnotation`` so it lines up with XLA ops in
Perfetto; ``profile_trace(logdir)`` wraps a block in
``jax.profiler.start_trace``/``stop_trace``.

When telemetry is disabled ``span()`` returns one shared no-op object —
no allocation, no clock read, no lock.
"""
from __future__ import annotations

import itertools
import json
import threading
from pathlib import Path

import jax

from repro.obs import core

__all__ = ["span", "Span", "trace_records", "clear_trace", "save_trace",
           "load_trace", "format_tree", "profile_trace"]

_records: list[dict] = []
_lock = threading.Lock()
_tls = threading.local()
_ids = itertools.count(1)


class _NoopSpan:
    """Shared disabled-mode span: every method is a constant no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def sync(self, value):
        return value

    def note(self, **fields) -> None:
        return None


_NOOP = _NoopSpan()


class Span:
    __slots__ = ("name", "meta", "_sync", "_vals", "id", "parent", "depth",
                 "t0", "_annot")

    def __init__(self, name: str, sync: bool | None, meta: dict):
        self.name = name
        self.meta = meta
        self._sync = core.sync_default() if sync is None else sync
        self._vals: list = []
        self._annot = None

    def sync(self, value):
        """Register ``value`` (any pytree of arrays) to be blocked on at
        span exit; returns it unchanged so call sites stay one-liners."""
        if self._sync:
            self._vals.append(value)
        return value

    def note(self, **fields) -> None:
        """Attach metadata to the span record."""
        self.meta.update(fields)

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.id = next(_ids)
        self.parent = stack[-1].id if stack else 0
        self.depth = len(stack)
        stack.append(self)
        if core.profiler_annotations():
            self._annot = jax.profiler.TraceAnnotation(self.name)
            self._annot.__enter__()
        self.t0 = core.now()
        return self

    def __exit__(self, *exc):
        wait = None
        if self._vals:
            tw = core.now()
            jax.block_until_ready(self._vals)
            wait = core.now() - tw
        t1 = core.now()
        if self._annot is not None:
            self._annot.__exit__(*exc)
        _tls.stack.pop()
        rec = {
            "name": self.name,
            "id": self.id,
            "parent": self.parent,
            "depth": self.depth,
            "thread": threading.current_thread().name,
            "ts_us": round((self.t0 - core.epoch()) * 1e6, 3),
            "dur_us": round((t1 - self.t0) * 1e6, 3),
        }
        if wait is not None:
            rec["wait_us"] = round(wait * 1e6, 3)
        if self.meta:
            rec["meta"] = {k: _jsonable(v) for k, v in self.meta.items()}
        with _lock:
            _records.append(rec)
        return False


def span(name: str, *, sync: bool | None = None, **meta):
    """A timed span context manager (the shared no-op when disabled)."""
    if not core.enabled():
        return _NOOP
    return Span(name, sync, meta)


def _jsonable(v):
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    if hasattr(v, "item"):
        try:
            return v.item()
        except Exception:
            pass
    return str(v)


def trace_records() -> list[dict]:
    """Snapshot of completed span records (copy; safe to mutate)."""
    with _lock:
        return [dict(r) for r in _records]


def clear_trace() -> None:
    with _lock:
        _records.clear()


def save_trace(path) -> Path:
    """Write completed spans as JSONL (one record per line)."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    recs = trace_records()
    with p.open("w") as f:
        for r in recs:
            f.write(json.dumps(r, sort_keys=True) + "\n")
    return p


def load_trace(path) -> list[dict]:
    recs = []
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if line:
            recs.append(json.loads(line))
    return recs


def format_tree(records: list[dict] | None = None) -> str:
    """Render spans as an indented tree, one line per span:

        protocol.run                         1234.5us  impl=dense
          signature.accumulate_grams          987.6us
    """
    recs = trace_records() if records is None else list(records)
    if not recs:
        return "(no spans recorded)"
    recs.sort(key=lambda r: (r.get("ts_us", 0.0), r.get("id", 0)))
    by_parent: dict[int, list[dict]] = {}
    ids = {r.get("id") for r in recs}
    for r in recs:
        parent = r.get("parent", 0)
        if parent not in ids:
            parent = 0
        by_parent.setdefault(parent, []).append(r)
    lines: list[str] = []

    def walk(parent: int, indent: int) -> None:
        for r in by_parent.get(parent, []):
            meta = r.get("meta") or {}
            extra = "  " + " ".join(f"{k}={v}" for k, v in meta.items()) \
                if meta else ""
            pad = "  " * indent
            label = f"{pad}{r['name']}"
            lines.append(f"{label:<44s} {r['dur_us']:>12.1f}us{extra}")
            walk(r.get("id", -1), indent + 1)

    walk(0, 0)
    return "\n".join(lines)


class profile_trace:
    """Context manager pass-through to ``jax.profiler.start_trace`` —
    wraps a block so spans and XLA ops land in one Perfetto trace."""

    def __init__(self, logdir: str):
        self.logdir = str(logdir)

    def __enter__(self):
        jax.profiler.start_trace(self.logdir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False
