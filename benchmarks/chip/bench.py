#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in the repository's
``BENCHMARK.json``: a configuration (``configs/<file>.json``, whose
graph ``generators/<generator>.py`` makes) under a traffic mix
(``traffic/<mix>.json``, run by the driver it names,
``traffic/<driver>.py``). Set-up generates the graph on the device from
the seed, hands it to the program and warms every shape the window uses;
the window then runs whole units for ``--seconds``; after it, every
answer the window produced is compared with the scipy reference.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1``
runs the window under the JAX profiler, with the program's
``repro.obs`` spans mirrored into the trace, and reports the cell's
per-layer metrics, each read by ``metrics/<name>.py``, plus the device
busy time and a breakdown.

The last line on stdout is the result, one JSON object. Earlier lines
carry the run's notes: routes, generator lateness, compiles inside the
window, peak device bytes. The last lines on stderr name each number
compared beside its limit. Without a TPU, or with fewer chips than the
cell asks for, the command exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
TRACE_DIR = HERE / ".trace"
# fires once per program compiled or loaded from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(root: Path, workload: str, home: Path = HERE) -> dict:
    """The cell, its configuration, its mix and its metric entries."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((home / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    end_to_end = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"] if mine(m)
                 and m["moves"] in reported]
    return {"cell": cell, "config": cfg, "mix": mix,
            "end_to_end": end_to_end, "per_layer": per_layer}


def require_chips(chips: int):
    """The devices of this run; raises ``NoChip`` off a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform} "
                     f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: Path) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` at
    the root of the checkout: a fixed path, so a second run finds every
    program the first one compiled."""
    import jax
    path = os.environ.get(CACHE_ENV) or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class GcPauses:
    """Python garbage collections while armed: count and longest pause."""

    def __init__(self):
        self.armed, self.count, self.longest_ms, self._t0 = False, 0, 0.0, 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if not self.armed:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count += 1
            self.longest_ms = max(self.longest_ms,
                                  (time.perf_counter() - self._t0) * 1e3)


class CompileCounter:
    """Counts programs compiled or loaded from the cache while armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._timed)

    def _event(self, name, **_):
        if self.armed and name == COMPILE_EVENT:
            self.count += 1

    def _timed(self, name, _secs, **_):
        self._event(name)


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(args, root: Path = ROOT, devices=None, home: Path = HERE) -> dict:
    """One run of one cell; returns the result and the notes. ``home``
    holds the cell's mix, its driver, generator and metric readers."""
    import jax
    import drivers
    import plugins
    spec = load_cell(root, args.workload, home)
    cell, cfg, mix = spec["cell"], spec["config"], spec["mix"]
    if devices is None:
        devices = require_chips(int(cell["chips"]))
    cache = enable_compile_cache(root)
    counter, pauses = CompileCounter(), GcPauses()
    traced = bool(args.trace)
    if traced:
        import roofline
        from repro.obs import trace as obs
        peak = roofline.peak(devices[0].device_kind)   # unknown: raise now
        obs.enable(capacity=1 << 20, jax_annotations=True)
    driver = drivers.load(mix["driver"], home)(
        cfg, mix, args.seed, args.seconds, annotate=traced, home=home)
    driver.setup()
    setup_s = time.perf_counter() - T_START
    if traced:
        obs.tracer().reset()            # keep the window's spans only
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans come from
        options.enable_hlo_proto = False    # annotations, not frames
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    counter.armed = pauses.armed = True
    with driver.span("bench.window"):
        window = driver.window()
    counter.armed = pauses.armed = False
    reduced = None
    if traced:
        jax.profiler.stop_trace()
        from trace_reduce import reduce_trace
        reduced = reduce_trace(str(TRACE_DIR))
    memory_peak = peak_bytes(devices)
    driver.release()
    gc.collect()
    t_check = time.perf_counter()
    attempted, failed, checks = driver.check()
    check_s = time.perf_counter() - t_check
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    readings = {"setup_s": setup_s, **window}
    if not traced:
        metrics = {m["name"]: {"value": readings[m["name"]],
                               "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    else:
        ctx = types.SimpleNamespace(
            timings=driver.timings, trace=reduced,
            spans=obs.tracer().log.events(), driver=mix["driver"],
            units=getattr(driver, "units", 0),
            num_nodes=driver.graph.num_nodes,
            num_edges=driver.graph.num_edges,
            peak=peak)
        metrics = {}
        for m in spec["per_layer"]:
            value = plugins.load("metrics", m["name"], home).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    notes = {"workload": args.workload, "seed": args.seed,
             "seconds": args.seconds, "trace": int(traced),
             "compile_cache": cache, "readings": readings,
             "compiles_in_window": counter.count, "check_s": check_s,
             "gc_in_window": {"count": pauses.count,
                              "longest_ms": pauses.longest_ms},
             "timings_ms": {k: {"n": len(v), "median": statistics.median(v),
                                "max": max(v)}
                            for k, v in driver.timings.items() if v},
             **driver.notes}
    return {"result": result, "notes": notes}


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args, root)
    except NoChip as err:
        print(f"bench: {err}; no result", file=sys.stderr)
        return 1
    print(json.dumps({"notes": out["notes"]}), flush=True)
    for name, c in out["result"]["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
