"""Readings that the limits of ``correct`` are set from, many seeds in one
process (set-up and compilation are paid once per shape).

    python3 bench/calibrate.py --workload cifar10.oneshot --seeds 11,12,13 \
        --seconds 3 [--control]

For each seed it builds the cell as a run does, drives a short window of
the timed path and prints the numbers that ``check`` compares
(the program's readings).  With ``--control`` it also prints the same
numbers for the cell's control: the plain reference computed one
precision lower (or the program's own lower-precision path), put in the
program's place.  The benchmark's runs never run the control.
Output: one JSON line per seed and side on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None, *, bench_dir: Path = harness.BENCH,
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--control-seeds", default="",
                    help="with --control: only these seeds read the "
                         "control (default: every seed)")
    ap.add_argument("--fault", default="",
                    help="plant this fault of bench/faults.py in the timed "
                         "path and print the program's readings under it")
    ap.add_argument("--sweep", default="",
                    help="key=v1,v2,...: one window per value of a mix "
                         "key, printing the window's numbers (no checks)")
    args = ap.parse_args(argv)
    _, _, cfg, mix, limits = harness.resolve(bench_dir, args.workload)
    if require_tpu:
        harness.use_checkout_cache(bench_dir.parent)
    import jax

    if require_tpu and jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    driver = harness.load_module(bench_dir / "drivers" / f"{mix['driver']}.py")
    if args.fault:
        from bench import faults

        getattr(faults, args.fault)(setattr)
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = harness.Ctx(workload=args.workload, seed=seed,
                          seconds=args.seconds, trace=False, cfg=cfg,
                          mix=mix, limits=limits, bench_dir=bench_dir)
        state = driver.setup(ctx)
        if args.sweep:
            key, vals = args.sweep.split("=")
            for val in vals.split(","):
                mix[key] = float(val)
                win = driver.window(state, args.seconds)
                print(json.dumps({"seed": seed, key: float(val),
                                  "ops": win.ops, "e2e": win.e2e,
                                  "host": win.host}), flush=True)
            continue
        win = driver.window(state, args.seconds)
        print(json.dumps({"seed": seed, "side": args.fault or "program",
                          "ops": win.ops,
                          "readings": driver.readings(state)}), flush=True)
        if args.control and (not control_seeds or seed in control_seeds):
            print(json.dumps({"seed": seed, "side": "control",
                              "readings": driver.control(state)}),
                  flush=True)
        del state
    return 0


if __name__ == "__main__":
    sys.exit(main())
