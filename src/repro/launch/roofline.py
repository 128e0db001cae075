"""Roofline modeling: hardware table, compiled-artifact analysis, and
analytic per-kernel cost models for the Pallas tile sweep.

Two consumers share this module:

* ``launch/dryrun.py`` — per (arch x shape x mesh) terms from a compiled
  module::

    compute_term    = HLO_FLOPs_global  / (chips * peak_flops)
    memory_term     = HLO_bytes_global  / (chips * hbm_bw)
    collective_term = collective_bytes_global / (chips * link_bw)

  ``compiled.cost_analysis()`` provides per-device FLOPs / bytes accessed
  (the SPMD module is the per-device program), so global = per_device *
  chips and the two formulations coincide.  Collective bytes are NOT in
  cost_analysis: we parse the optimized HLO (``compiled.as_text()``) and
  sum the shape bytes of every all-gather / all-reduce / reduce-scatter /
  all-to-all / collective-permute (using max(result, operand) bytes per
  op — a ring-transfer proxy, documented in EXPERIMENTS.md).

* ``benchmarks/bench_roofline.py`` — per (kernel x shape x tile plan)
  analytic FLOP/byte counts (``kernel_costs``) against the HOST device's
  roof (``detect_hardware``), the measurement loop that justifies the
  ``kernels/tuning.py`` tile heuristics.

Hardware peaks live in ``HW_TABLE`` keyed by device kind (the
``kernels.dispatch.device_kind()`` string).  A kind that is not in the
table is an error, never a default: a roof borrowed from another chip
would be reported under that chip's name.  The CPU row only ranks tile
plans in interpret mode and is never a device number.  ``peak_flops``
may be overridden per call (the ``--peak-flops`` benchmark flag).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

from repro.kernels import dispatch


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip peaks: dense-matmul FLOP/s (bf16 where the unit has one),
    main-memory bandwidth, and per-link interconnect bandwidth."""

    name: str
    peak_flops: float
    hbm_bw: float
    link_bw: float


V5E = HardwareSpec("tpu-v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9)

#: Device-kind -> peaks.  Keys are matched as lowercase substrings of
#: ``jax.devices()[0].device_kind`` (e.g. "TPU v5 lite" matches "v5 lite").
HW_TABLE: dict[str, HardwareSpec] = {
    "v5 lite": V5E,
    "v5e": V5E,
    "v5p": HardwareSpec("tpu-v5p", peak_flops=459e12, hbm_bw=2765e9,
                        link_bw=100e9),
    "v4": HardwareSpec("tpu-v4", peak_flops=275e12, hbm_bw=1228e9,
                       link_bw=50e9),
    "v6": HardwareSpec("tpu-v6e", peak_flops=918e12, hbm_bw=1640e9,
                       link_bw=100e9),
    "a100": HardwareSpec("gpu-a100", peak_flops=312e12, hbm_bw=1555e9,
                         link_bw=300e9),
    "h100": HardwareSpec("gpu-h100", peak_flops=989e12, hbm_bw=3350e9,
                         link_bw=450e9),
    # Interpret-mode host: one AVX-ish core-complex worth of f32 matmul
    # and a DDR-class memory system.  Deliberately round numbers — the
    # CPU roof only ranks tile plans, it is not a performance claim.
    "cpu": HardwareSpec("cpu", peak_flops=2e11, hbm_bw=50e9, link_bw=10e9),
}


def detect_hardware(peak_flops: float | None = None) -> HardwareSpec:
    """The host device's ``HardwareSpec`` by device-kind substring match.
    Raises ``ValueError`` for a kind ``HW_TABLE`` does not list.
    ``peak_flops`` overrides the matmul peak (the ``--peak-flops``
    flag)."""
    kind = dispatch.device_kind()
    hw = next((spec for key, spec in HW_TABLE.items()
               if key in kind.lower()), None)
    if hw is None:
        raise ValueError(f"no peaks for device kind {kind!r}: add it to "
                         f"HW_TABLE with its source")
    if peak_flops is not None:
        hw = dataclasses.replace(hw, name=f"{hw.name}-custom",
                                 peak_flops=float(peak_flops))
    return hw


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*(\w+\[[\d,]*\][^ ]*|\([^)]*\))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _shape_bytes(text: str) -> int:
    """Sum bytes over every TYPE[dims] occurrence in ``text``."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_per_device: int
    counts: dict[str, int]
    bytes_by_kind: dict[str, int]


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    bytes_by_kind: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        if "-done(" in line:          # async pair: count the -start only
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        result_text, kind = m.groups()
        result_bytes = _shape_bytes(result_text)
        # operand shapes appear in the argument list after the op name
        args = line[m.end():]
        operand_bytes = _shape_bytes(args)
        counts[kind] += 1
        bytes_by_kind[kind] += max(result_bytes, operand_bytes)
    return CollectiveStats(
        bytes_per_device=sum(bytes_by_kind.values()),
        counts={k: v for k, v in counts.items() if v},
        bytes_by_kind={k: v for k, v in bytes_by_kind.items() if v})


def model_flops(n_params_active: int, tokens: int, kind: str) -> float:
    """6ND for training (fwd+bwd), 2ND for inference."""
    factor = 6.0 if kind == "train" else 2.0
    return factor * n_params_active * tokens


@dataclasses.dataclass
class Roofline:
    chips: int
    hlo_flops_per_device: float
    hlo_bytes_per_device: float
    collective_bytes_per_device: float
    collective_counts: dict[str, int]
    collective_bytes_by_kind: dict[str, int]
    model_flops_global: float
    hw: HardwareSpec

    @property
    def compute_term_s(self) -> float:
        return self.hlo_flops_per_device / self.hw.peak_flops

    @property
    def memory_term_s(self) -> float:
        return self.hlo_bytes_per_device / self.hw.hbm_bw

    @property
    def collective_term_s(self) -> float:
        return self.collective_bytes_per_device / self.hw.link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_term_s,
                 "memory": self.memory_term_s,
                 "collective": self.collective_term_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.hlo_flops_per_device * self.chips
        return self.model_flops_global / total if total else 0.0

    def to_dict(self) -> dict[str, Any]:
        return {
            "chips": self.chips,
            "hw": self.hw.name,
            "hlo_flops_per_device": self.hlo_flops_per_device,
            "hlo_bytes_per_device": self.hlo_bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "collective_counts": self.collective_counts,
            "collective_bytes_by_kind": self.collective_bytes_by_kind,
            "model_flops_global": self.model_flops_global,
            "compute_term_s": self.compute_term_s,
            "memory_term_s": self.memory_term_s,
            "collective_term_s": self.collective_term_s,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def analyze(compiled, chips: int, model_flops_global: float,
            hw: HardwareSpec) -> Roofline:
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):     # some backends return [dict]
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    stats = parse_collectives(compiled.as_text())
    return Roofline(
        chips=chips,
        hlo_flops_per_device=flops,
        hlo_bytes_per_device=byts,
        collective_bytes_per_device=float(stats.bytes_per_device),
        collective_counts=stats.counts,
        collective_bytes_by_kind=stats.bytes_by_kind,
        model_flops_global=model_flops_global,
        hw=hw,
    )


def memory_summary(compiled) -> dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:              # pragma: no cover
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = float(v)
    if out:
        out["total_hbm_bytes"] = (out.get("argument_size_in_bytes", 0)
                                  + out.get("output_size_in_bytes", 0)
                                  + out.get("temp_size_in_bytes", 0)
                                  - out.get("alias_size_in_bytes", 0))
    return out


# ---------------------------------------------------------------------------
# Analytic kernel cost models — the tile-sweep measurement loop
# ---------------------------------------------------------------------------

def kernel_costs(kernel: str, blocks: dict | None = None,
                 itemsize: int = 4, **dims: int) -> dict[str, float]:
    """Analytic ``{"flops", "bytes"}`` for one kernel dispatch under a
    tile plan.

    FLOPs are tile-independent (the useful work); bytes are NOT — a tile
    plan that re-streams an operand per output block pays for it here,
    which is exactly why the sweep can rank plans before timing them.
    ``itemsize`` is the streamed-operand element size (4 f32, 2 bf16,
    1 int8 directory).  Dims follow the ``kernels.tuning`` vocabulary.
    """
    b = dict(blocks or {})
    if kernel == "gram":
        n, d = dims["n"], dims["d"]
        bd = b.get("block_d", 128)
        # each of the (d/bd)^2 output tiles streams two (n, bd) panels
        tiles = max(1, -(-d // bd)) ** 2
        return {"flops": 2.0 * n * d * d,
                "bytes": tiles * 2.0 * n * bd * itemsize + d * d * 4.0}
    if kernel == "gram_project":
        n, d, k = dims["n"], dims["d"], dims["k"]
        bk = b.get("block_k", 128)
        kblocks = max(1, -(-k // bk))
        # X re-streams once per k-block; V rides per (k, n) grid step
        return {"flops": 4.0 * n * d * k,
                "bytes": (kblocks * n * d + n // max(b.get("block_n", 128),
                                                     1) * d * k) * itemsize
                + k * 4.0}
    if kernel == "featurize_gram":
        n, m, d = dims["n"], dims["m"], dims["d"]
        return {"flops": 2.0 * n * m * d + 2.0 * n * d * d,
                "bytes": (n * m + m * d) * itemsize + d * d * 4.0}
    if kernel == "eigproject":
        nb, d, k = dims.get("b", 1), dims["d"], dims["k"]
        ublocks = max(1, -(-nb // b.get("block_u", nb)))
        # each Gram streams once; the (d, k) table re-streams per user block
        return {"flops": 2.0 * nb * d * d * k,
                "bytes": (nb * d * d + ublocks * d * k) * itemsize
                + nb * k * 4.0}
    if kernel == "linkage":
        n = dims["n"]
        # two source rows + mask in, one row out, plus the fused reduction
        return {"flops": 5.0 * n, "bytes": 4.0 * n * 4.0}
    if kernel == "assign":
        bb, d2, t = dims["b"], dims["d2"], dims.get("t", 128)
        bbk = b.get("block_b", 128)
        rowblocks = max(1, -(-bb // bbk))
        # S streams once; the directory re-streams per wave row-block
        return {"flops": 2.0 * bb * d2 * t,
                "bytes": bb * d2 * 4.0 + rowblocks * t * d2 * itemsize
                + bb * (t + 2) * 4.0}
    raise ValueError(f"no cost model for kernel {kernel!r}")


def kernel_roofline(kernel: str, blocks: dict | None = None,
                    hw: HardwareSpec | None = None, itemsize: int = 4,
                    **dims: int) -> dict[str, Any]:
    """Roofline terms for one kernel dispatch: analytic costs against the
    host (or given) hardware roof, plus the bound classification and the
    time floor the tile plan cannot beat."""
    hw = hw or detect_hardware()
    costs = kernel_costs(kernel, blocks, itemsize=itemsize, **dims)
    compute_s = costs["flops"] / hw.peak_flops
    memory_s = costs["bytes"] / hw.hbm_bw
    return {
        "kernel": kernel, "hw": hw.name, "blocks": dict(blocks or {}),
        "flops": costs["flops"], "bytes": costs["bytes"],
        "compute_term_s": compute_s, "memory_term_s": memory_s,
        "roof_s": max(compute_s, memory_s),
        "bound": "compute" if compute_s >= memory_s else "memory",
        "arithmetic_intensity": (costs["flops"] / costs["bytes"]
                                 if costs["bytes"] else 0.0),
    }
