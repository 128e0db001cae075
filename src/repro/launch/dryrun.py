import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
# Placeholder host devices exist ONLY in this dry-run entry point; smoke
# tests and benchmarks see the single real CPU device.

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
for the production meshes and record memory/cost/roofline artifacts.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all combos
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite_8b \
      --shape train_4k --mesh pod --verbose
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multipod --skip-existing

Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>.json
(read by benchmarks/roofline reporting and EXPERIMENTS.md).
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax

from repro import optim
from repro.configs.base import ARCH_IDS, INPUT_SHAPES, get_arch
from repro.launch import mesh as ML
from repro.launch.compile_cache import use_compile_cache
from repro.launch import roofline as RL
from repro.launch import sharding as SH
from repro.launch import steps as ST

LM_ARCHS = [a for a in ARCH_IDS if not a.startswith("paper_")]
OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _build_lowered(cfg, shape, mesh, opts, block_impl: str = "auto"):
    params_shape = ST.abstract_params(cfg)
    batch_shape = ST.input_specs(cfg, shape)
    bspecs = SH.batch_specs(batch_shape, mesh)
    batch_in = SH.attach(batch_shape, bspecs, mesh)

    if block_impl == "manual" and shape.kind == "train":
        # Manual Megatron TP+SP via shard_map (dense decoders only).
        # Params stay scan-STACKED even for the unrolled scan-correction
        # variants (the manual path slices them in a python loop).
        from repro.launch import manual_tp as MT

        stacked_cfg = dataclasses.replace(cfg, scan_layers=True)
        params_shape = ST.abstract_params(stacked_cfg)
        optimizer = optim.adamw(1e-4)
        step, mspecs = MT.make_manual_train_step(cfg, mesh, optimizer)
        params_in = SH.attach(params_shape, mspecs, mesh)
        opt_shape = ST.abstract_opt_state(cfg, optimizer, params_shape)
        opt_in = SH.attach(opt_shape, _opt_specs(opt_shape, mspecs), mesh)
        with mesh:
            return jax.jit(step).lower(params_in, opt_in, batch_in)

    pspecs = SH.param_specs(params_shape, mesh, opts)
    params_in = SH.attach(params_shape, pspecs, mesh)

    if shape.kind == "train":
        optimizer = optim.adamw(1e-4)
        opt_shape = ST.abstract_opt_state(cfg, optimizer, params_shape)
        opt_in = SH.attach(opt_shape, _opt_specs(opt_shape, pspecs), mesh)
        step = ST.make_train_step(cfg, mesh, optimizer, opts,
                                  param_specs=pspecs)
        with mesh:
            return jax.jit(step).lower(params_in, opt_in, batch_in)
    if shape.kind == "prefill":
        step = ST.make_prefill_step(cfg, mesh, opts)
        with mesh:
            return jax.jit(step).lower(params_in, batch_in)
    state_shape = ST.abstract_decode_state(cfg, shape)
    sspecs = SH.state_specs(state_shape, mesh)
    state_in = SH.attach(state_shape, sspecs, mesh)
    step = ST.make_serve_step(cfg, mesh, opts)
    with mesh:
        return jax.jit(step).lower(params_in, state_in, batch_in)


def _metrics(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    stats = RL.parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll_bytes": float(stats.bytes_per_device),
            "coll_counts": stats.counts,
            "coll_bytes_by_kind": stats.bytes_by_kind}


def run_one(arch_id: str, shape_name: str, mesh_kind: str,
            opts: SH.ShardingOptions | None = None,
            verbose: bool = False, attn_impl: str | None = None,
            block_impl: str = "auto") -> dict:
    shape = INPUT_SHAPES[shape_name]
    base_cfg = get_arch(arch_id)
    cfg = ST.variant_for_shape(base_cfg, shape)
    variant = "swa" if cfg is not base_cfg else "base"
    if attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    mesh = ML.make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = mesh.devices.size
    opts = opts or SH.ShardingOptions()

    # --- The artifact: full-depth scanned program. ----------------------
    t0 = time.perf_counter()
    lowered = _build_lowered(cfg, shape, mesh, opts, block_impl)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    mem = RL.memory_summary(compiled)
    raw = _metrics(compiled)

    # --- Scan correction: XLA cost_analysis counts a while body ONCE, so
    # derive the per-layer-group cost from two UNROLLED shallow variants
    # and extrapolate to full depth (EXPERIMENTS.md §Dry-run notes).
    pat_len = len(cfg.block_pattern)
    if cfg.encoder_layers:
        cfg1 = dataclasses.replace(cfg, scan_layers=False, n_layers=1,
                                   encoder_layers=1)
        cfg2 = dataclasses.replace(cfg, scan_layers=False, n_layers=2,
                                   encoder_layers=2)
        extra_groups = cfg.n_layers - 1.0
    else:
        cfg1 = dataclasses.replace(cfg, scan_layers=False, n_layers=pat_len)
        cfg2 = dataclasses.replace(cfg, scan_layers=False,
                                   n_layers=2 * pat_len)
        extra_groups = (cfg.n_groups - 1.0
                        + len(cfg.rest_kinds) / pat_len)
    m1 = _metrics(_build_lowered(cfg1, shape, mesh, opts,
                                 block_impl).compile())
    m2 = _metrics(_build_lowered(cfg2, shape, mesh, opts,
                                 block_impl).compile())

    def corr(key):
        per_group = m2[key] - m1[key]
        return m1[key] + extra_groups * per_group

    corrected = {k: corr(k) for k in ("flops", "bytes", "coll_bytes")}
    coll_counts = {
        k: int(round(m1["coll_counts"].get(k, 0)
                     + extra_groups * (m2["coll_counts"].get(k, 0)
                                       - m1["coll_counts"].get(k, 0))))
        for k in set(m1["coll_counts"]) | set(m2["coll_counts"])}

    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind in ("train", "prefill")
                                   else 1)
    mf = RL.model_flops(cfg.n_active_params(), tokens, shape.kind)
    roof = RL.Roofline(
        chips=chips,
        hlo_flops_per_device=corrected["flops"],
        hlo_bytes_per_device=corrected["bytes"],
        collective_bytes_per_device=corrected["coll_bytes"],
        collective_counts=coll_counts,
        collective_bytes_by_kind=m2["coll_bytes_by_kind"],
        model_flops_global=mf,
        hw=RL.V5E,                 # the production mesh is a v5e pod
    )

    result = {
        "arch": arch_id, "arch_name": cfg.name, "shape": shape_name,
        "mesh": mesh_kind, "chips": chips, "kind": shape.kind,
        "variant": variant,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "roofline": roof.to_dict(),
        "roofline_raw_scanned": {k: raw[k]
                                 for k in ("flops", "bytes", "coll_bytes")},
        "scan_correction": {"extra_groups": extra_groups,
                            "g1": {k: m1[k] for k in
                                   ("flops", "bytes", "coll_bytes")},
                            "g2": {k: m2[k] for k in
                                   ("flops", "bytes", "coll_bytes")}},
        "sharding": {"fsdp": opts.fsdp,
                     "activation_mode": opts.activation_mode},
        "status": "ok",
    }
    if verbose:
        print(json.dumps(result, indent=2))
        print(compiled.memory_analysis())
    return result


def _opt_specs(opt_shape, pspecs):
    """Optimizer state inherits each param's spec; scalars replicated."""
    from jax.sharding import PartitionSpec as P

    inner = opt_shape.inner
    if isinstance(inner, dict) and set(inner) == {"m", "v"}:
        inner_specs = {"m": pspecs, "v": pspecs}
    elif inner == ():
        inner_specs = ()
    else:  # momentum: velocity tree mirrors params
        inner_specs = pspecs
    return type(opt_shape)(step=P(), inner=inner_specs)


def main() -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default all)")
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default=None, choices=["pod", "multipod"])
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--verbose", action="store_true")
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--activation-mode", default="seq",
                    choices=["dp", "seq", "tensor", "megatron"])
    ap.add_argument("--attn-impl", default=None,
                    choices=["jnp", "chunked", "pallas"])
    ap.add_argument("--block-impl", default="auto",
                    choices=["auto", "manual"])
    ap.add_argument("--tag", default="", help="suffix for artifact files")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else LM_ARCHS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [args.mesh] if args.mesh else ["pod", "multipod"]
    opts = SH.ShardingOptions(fsdp=bool(args.fsdp),
                              activation_mode=args.activation_mode)

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"__{args.tag}" if args.tag else ""
                out = OUT_DIR / f"{arch}__{shape}__{mesh_kind}{tag}.json"
                if args.skip_existing and out.exists():
                    print(f"[skip] {out.name}")
                    continue
                label = f"{arch} x {shape} x {mesh_kind}"
                try:
                    t0 = time.perf_counter()
                    result = run_one(arch, shape, mesh_kind, opts,
                                     args.verbose, args.attn_impl,
                                     args.block_impl)
                    dt = time.perf_counter() - t0
                    print(f"[ok]   {label}  ({dt:.1f}s, "
                          f"bottleneck={result['roofline']['bottleneck']})",
                          flush=True)
                except Exception as e:  # noqa: BLE001
                    result = {"arch": arch, "shape": shape,
                              "mesh": mesh_kind, "status": "fail",
                              "error": f"{type(e).__name__}: {e}",
                              "traceback": traceback.format_exc()[-4000:]}
                    failures.append(label)
                    print(f"[FAIL] {label}: {type(e).__name__}: {e}",
                          flush=True)
                out.write_text(json.dumps(result, indent=2))
    if failures:
        print(f"\n{len(failures)} FAILURES:\n  " + "\n  ".join(failures))
        return 1
    print("\nall dry-runs passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
