#!/usr/bin/env python3
"""Find the knee of a serving cell once: the highest offered rate at
which the backlog does not grow over the window.

    python benchmarks/chip/knee.py --workload soc.serve --seed 5 \
        --seconds 10 --rates 200 400 600 800

One process sets the cell up once, then offers each rate for
``--seconds`` and prints one JSON line per rate: the latency tail, how
late the generator ran, the queue at the start and end of the window,
and how long the queue took to drain after the last arrival. The rate
of the cell's mix is then set from it by hand; the benchmark's runs
never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import bench
import drivers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = bench.load_cell(bench.ROOT, args.workload)
    try:
        bench.require_chips(int(spec["cell"]["chips"]))
    except bench.NoChip as err:
        print(f"knee: {err}", file=sys.stderr)
        return 1
    bench.enable_compile_cache(bench.ROOT)
    d = drivers.load(spec["mix"]["driver"])(
        spec["config"], dict(spec["mix"]), args.seed, args.seconds)
    d.setup()
    for rate in args.rates:
        d.mix["rate_per_s"] = rate
        d.plan()
        out = d.window()
        lat = d.latency_s[np.isfinite(d.latency_s)]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(d.reqs),
            "answered": int(lat.size),
            "p50_ms": float(np.median(lat) * 1e3),
            "p95_ms": out["query_p95_ms"],
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "drain_s": out["window_s"] - args.seconds,
            "ticks": len(d.timings["tick"]),
            "tick_ms_median": float(np.median(d.timings["tick"])),
            "backlog": d.notes["backlog"],
            "generator_late_ms": d.notes["generator_late_ms"]}),
            flush=True)
        d.timings.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
