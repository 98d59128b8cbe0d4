#!/usr/bin/env python3
"""The control of ``correct``: a run that breaks the configuration's
guarantee must come out as not correct.

    python benchmarks/chip/control.py --workload <name> --seeds 11 12 13

The configurations state exact components. Each traffic driver's
``control()`` breaks that guarantee in the program's place, at the
cell's own size: ``static`` labels from min-label propagation stopped
after a few sweeps instead of at convergence; ``stream`` the window's
last delete batch left unapplied; ``serve`` answers from the
early-stopped labels.

For each seed it prints the numbers the benchmark compares, beside
their limits, as one JSON line. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import bench


def control(workload: str, seed: int, seconds: float, root=bench.ROOT,
            devices=None, home=bench.HERE) -> dict:
    import drivers
    spec = bench.load_cell(root, workload, home)
    mix = spec["mix"]
    if devices is None:
        bench.require_chips(int(spec["cell"]["chips"]))
    bench.enable_compile_cache(root)
    t0 = time.perf_counter()
    checks = drivers.load(mix["driver"], home)(
        spec["config"], mix, seed, seconds, home=home).control()
    return {"workload": workload, "seed": seed, "control": mix["driver"],
            "seconds": time.perf_counter() - t0,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    try:
        for seed in args.seeds:
            print(json.dumps(control(args.workload, seed, args.seconds)),
                  flush=True)
    except bench.NoChip as err:
        print(f"control: {err}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
