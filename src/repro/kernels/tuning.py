"""Block-size selection for the Pallas kernels: static heuristics plus a
measured-sweep autotuner with a persistent on-disk cache.

Every kernel wrapper (``kernels/*/ops.py``) resolves its tile sizes here
when the caller does not pin them:

  1. **Cache hit** — an entry keyed ``kernel x shape-bucket x backend``
     (backend = platform + device kind, via ``kernels.dispatch``), filled
     by a previous ``autotune`` sweep.  Cached tiles measured on one
     device class are never replayed on another.
  2. **Heuristic default** — when tuning is off (no cache entry), a
     static per-backend rule: on TPU, MXU-friendly 128-512 tiles; on CPU
     (interpret mode) the grid-step count IS the cost, so tiles grow to
     the whole (lane-rounded) dimension and the grid collapses toward a
     single step.

The sweep (``autotune``) times caller-supplied candidates and records the
winner.  Set ``REPRO_TUNE_CACHE=/path/to/cache.json`` to persist results
across processes (``benchmarks/bench_kernels.py --tune`` populates it);
without the env var the sweep still caches in-memory for the process.

Shape buckets round every dimension up to a power of two, so one sweep at
``n=2048`` serves ``n in (1025..2048]`` — tile choice is insensitive to
sub-bucket variation and the sweep cost stays bounded.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Callable, Iterable

from repro.kernels import dispatch

__all__ = ["KERNELS", "shape_bucket", "cache_key", "cache_path",
           "heuristic_blocks", "get_blocks", "autotune", "lookup",
           "record", "clear_cache", "divisor_block",
           "featurize_gram_vmem_bytes", "vmem_budget_bytes",
           "SCOPED_VMEM_BYTES"]

_ENV = "REPRO_TUNE_CACHE"
_LANE = 128
_SUBLANE = 16          # row-tile quantum valid for both f32 and bf16

#: Default scoped-VMEM limit of a Mosaic kernel by device kind (lowercase
#: substrings of ``jax.devices()[0].device_kind``, matched as
#: ``launch/roofline.HW_TABLE`` matches).  Only kinds whose limit a
#: compile has confirmed are listed: v5e refuses a kernel that allocates
#: more than 16 MiB.  A lowered tile plan on any other kind raises.
SCOPED_VMEM_BYTES: dict[str, int] = {"v5 lite": 16 << 20, "v5e": 16 << 20}
_VMEM_RESERVE = 1 << 20        # left to Mosaic's internal scratch

#: Kernel families the tuner knows tile heuristics for.
KERNELS = ("gram", "gram_project", "featurize_gram", "eigproject",
           "linkage", "assign", "recurrent_scan")

# In-memory overlay of the on-disk cache (survives the process even when
# REPRO_TUNE_CACHE is unset — "tuning on" without persistence).
_mem: dict[str, dict] = {}
_loaded_from: str | None = None


def _round_lane(x: int) -> int:
    """Round up to the 128-lane quantum (minimum one lane group)."""
    return max(_LANE, ((int(x) + _LANE - 1) // _LANE) * _LANE)


def _pow2_ceil(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def divisor_block(n: int, cap: int = 4096) -> int:
    """Largest lane-multiple block <= ``cap`` that divides ``n`` exactly
    (for kernels like ``linkage`` whose rows are padded once up front and
    cannot re-pad per call).  ``n`` must itself be a lane multiple."""
    if n % _LANE:
        raise ValueError(f"row length {n} is not a lane multiple of {_LANE}")
    for b in range(min(cap, n), _LANE - 1, -_LANE):
        if n % b == 0:
            return b
    return _LANE


def shape_bucket(**dims: int) -> str:
    """Canonical bucket string: dims sorted by name, pow2-ceiled."""
    return ",".join(f"{k}={_pow2_ceil(v)}" for k, v in sorted(dims.items()))


def _backend_tag() -> str:
    return f"{dispatch.backend_kind()}:{dispatch.device_kind()}"


def cache_key(kernel: str, **dims: int) -> str:
    return f"{kernel}|{_backend_tag()}|{shape_bucket(**dims)}"


def cache_path() -> Path | None:
    p = os.environ.get(_ENV, "")
    return Path(p) if p else None


def _load_disk() -> None:
    """Merge the on-disk cache under the in-memory overlay (memory wins:
    it holds this process's fresher sweeps)."""
    global _loaded_from
    p = cache_path()
    tag = str(p) if p else None
    if tag == _loaded_from:
        return
    _loaded_from = tag
    if p is None or not p.exists():
        return
    try:
        disk = json.loads(p.read_text())
    except (OSError, json.JSONDecodeError):
        return
    for k, v in disk.items():
        _mem.setdefault(k, v)


def _persist() -> None:
    p = cache_path()
    if p is None:
        return
    p.parent.mkdir(parents=True, exist_ok=True)
    tmp = p.with_suffix(p.suffix + ".tmp")
    tmp.write_text(json.dumps(_mem, indent=2, sort_keys=True) + "\n")
    tmp.replace(p)


def clear_cache() -> None:
    """Drop the in-memory cache (tests; does not touch the disk file)."""
    global _loaded_from
    _mem.clear()
    _loaded_from = None


def lookup(kernel: str, **dims: int) -> dict | None:
    """Tuned blocks for this kernel/backend/bucket, or None."""
    _load_disk()
    hit = _mem.get(cache_key(kernel, **dims))
    return dict(hit["blocks"]) if hit else None


def record(kernel: str, blocks: dict, measured_s: float | None = None,
           sweep: dict | None = None, **dims: int) -> None:
    """Store a sweep winner; persists when REPRO_TUNE_CACHE is set."""
    entry: dict = {"blocks": dict(blocks)}
    if measured_s is not None:
        entry["measured_s"] = measured_s
    if sweep:
        entry["sweep"] = sweep
    _load_disk()
    _mem[cache_key(kernel, **dims)] = entry
    _persist()


# ---------------------------------------------------------------------------
# Static heuristics — the defaults when tuning is off
# ---------------------------------------------------------------------------

def heuristic_blocks(kernel: str, **dims: int) -> dict:
    """Per-backend static tile defaults.

    Lowered backends (TPU/GPU) get MXU/SM-friendly 128-512 tiles — big
    enough to amortize the pipeline, small enough that double-buffered
    operands fit VMEM.  CPU interpret mode has no VMEM and pays a fixed
    Python cost PER GRID STEP, so tiles grow to the lane-rounded full
    dimension (capped) and the grid collapses toward one step.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}: one of {KERNELS}")
    lowered = dispatch.supports_lowering()

    def tile(dim: int, accel_cap: int, interp_cap: int) -> int:
        cap = accel_cap if lowered else interp_cap
        return min(_round_lane(dim), cap)

    if kernel == "gram":
        return {"block_n": tile(dims["n"], 512, 4096),
                "block_d": tile(dims["d"], 256, 2048)}
    if kernel == "gram_project":
        return {"block_n": tile(dims["n"], 512, 4096),
                "block_k": tile(dims["k"], 256, 2048),
                "double_buffer": lowered}
    if kernel == "featurize_gram":
        if not lowered:
            return {"block_n": tile(dims["n"], 512, 4096),
                    "double_buffer": False}
        return {"block_n": _featurize_gram_block_n(**dims),
                "double_buffer": True}
    if kernel == "eigproject":
        return {"block_d": tile(dims["d"], 256, 2048),
                "block_k": tile(dims["k"], 256, 2048)}
    if kernel == "linkage":
        return {"block": divisor_block(dims["n"],
                                       cap=512 if lowered else 4096)}
    if kernel == "recurrent_scan":
        # chunk = time tile (the sequential grid axis — its square drives
        # the intra-chunk pairwise-decay footprint).  Lowered backends
        # amortize the O(chunk^2) tile on the MXU, so bigger wins; the
        # interpreter executes it eagerly, so the quadratic dominates and
        # small chunks win.  block_d = channel tile.
        chunk = max(8, min(64 if lowered else 16, _pow2_ceil(dims["s"])))
        return {"chunk": chunk,
                "block_d": tile(dims["d"], 256, 1024)}
    # assign: rows = arrival wave, lanes = flattened d*d directory axis
    return {"block_b": tile(dims["b"], 256, 1024),
            "block_d2": tile(dims["d2"], 512, 8192)}


def featurize_gram_vmem_bytes(block_n: int, m: int, d: int,
                              itemsize: int) -> int:
    """VMEM the DMA-streamed ``featurize_gram`` kernel allocates: two
    ``(block_n, m)`` row slots, the resident ``(m, d)`` projection, the
    f32 ``(d, d)`` accumulator and output, and the projected ``(block_n,
    d)`` tile (f32 accumulate plus its compute-dtype copy).  ``m``/``d``
    are lane-padded as the wrapper pads them."""
    m, d = _round_lane(m), _round_lane(d)
    return (2 * block_n * m * itemsize + m * d * itemsize
            + 2 * d * d * 4 + block_n * d * (4 + itemsize))


def vmem_budget_bytes(kind: str | None = None) -> int:
    """VMEM a lowered tile plan may fill on device ``kind`` (default: the
    host's device 0): its scoped-VMEM limit less Mosaic's reserve.
    Raises for a kind ``SCOPED_VMEM_BYTES`` does not list."""
    kind = dispatch.device_kind() if kind is None else kind
    limit = next((v for k, v in SCOPED_VMEM_BYTES.items()
                  if k in kind.lower()), None)
    if limit is None:
        raise ValueError(f"no scoped-VMEM limit for device kind {kind!r}: "
                         f"add it to SCOPED_VMEM_BYTES")
    return limit - _VMEM_RESERVE


def _featurize_gram_block_n(n: int, m: int, d: int, itemsize: int) -> int:
    """Largest row tile (a multiple of 16, <= 512, no taller than the
    rows) whose ``featurize_gram_vmem_bytes`` fits ``vmem_budget_bytes``.
    Raises when even the smallest tile does not fit beside the resident
    projection."""
    budget = vmem_budget_bytes()
    fixed = featurize_gram_vmem_bytes(0, m, d, itemsize)
    per_row = featurize_gram_vmem_bytes(1, m, d, itemsize) - fixed
    fit = (budget - fixed) // per_row
    block = min(512, fit, -(-int(n) // _SUBLANE) * _SUBLANE)
    block = block // _SUBLANE * _SUBLANE
    if block < _SUBLANE:
        raise ValueError(
            f"featurize_gram: no row tile fits {budget} B of VMEM beside a "
            f"resident ({m}, {d}) projection at itemsize {itemsize}")
    return block


def get_blocks(kernel: str, **dims: int) -> dict:
    """The resolved tile plan: heuristic defaults overlaid by any tuned
    cache entry for this kernel x backend x shape-bucket."""
    blocks = heuristic_blocks(kernel, **dims)
    hit = lookup(kernel, **dims)
    if hit:
        blocks.update(hit)
    dispatch.record_dispatch(kernel, blocks)
    return blocks


# ---------------------------------------------------------------------------
# The measured sweep
# ---------------------------------------------------------------------------

def autotune(kernel: str, run: Callable[[dict], None],
             candidates: Iterable[dict], n_iter: int = 3, warmup: int = 1,
             **dims: int) -> dict:
    """Time ``run(blocks)`` over candidate tile plans, cache the winner.

    ``run`` must execute the kernel end-to-end and block until ready.
    Candidates that raise ``ValueError`` (invalid divisibility for the
    shape) are skipped.  Returns the winning blocks; the measured sweep
    is recorded under the kernel/backend/bucket cache key and persisted
    when ``REPRO_TUNE_CACHE`` is set.
    """
    results: dict[str, float] = {}
    best: tuple[float, dict] | None = None
    for cand in candidates:
        cand = dict(cand)
        try:
            for _ in range(warmup):
                run(cand)
            t0 = time.perf_counter()
            for _ in range(n_iter):
                run(cand)
            dt = (time.perf_counter() - t0) / n_iter
        except ValueError:
            continue
        results[json.dumps(cand, sort_keys=True)] = dt
        if best is None or dt < best[0]:
            best = (dt, cand)
    if best is None:
        raise ValueError(f"no valid tuning candidate for {kernel} {dims}")
    record(kernel, best[1], measured_s=best[0], sweep=results, **dims)
    return best[1]
