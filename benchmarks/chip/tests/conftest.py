"""Shared set-up of the benchmark's tests: import paths and a toy root.

The tests run on the CPU at toy sizes. They steer around the harness's
look for a TPU by handing ``bench.run`` the CPU devices themselves, and
give it a ``BENCHMARK.json`` of toy configurations in a temporary
directory, with a copy of the benchmark's drivers, generators and
metric readers and traffic files scaled to the toys.
"""
from __future__ import annotations

import json
import shutil
import sys
import types
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(REPO / "src")]

TOY_RMAT = {"name": "toy-rmat", "generator": "rmat",
            "params": {"scale": 11, "num_edges": 4096, "a": 0.45,
                       "b": 0.22, "c": 0.22},
            "num_vertices": 2048, "num_edges": 4096}
TOY_GRID = {"name": "toy-grid", "generator": "grid",
            "params": {"side": 48, "keep_share": 0.65, "diag_share": 0.02}}
# the drivers of the stream and serve cells that PERF.md holds for a
# cited traffic source, with toy mixes and their metrics
HELD_MIXES = {
    "stream": {"driver": "stream", "insert_rows": 64, "inserts_per_cycle": 10,
               "delete_rows": 64, "deletes_per_cycle": 1},
    "serve": {"driver": "serve", "rate_per_s": 100, "rows_per_request": 32,
              "kinds": {"same_component": 0.5, "component_size": 0.5},
              "zipf_constant": 0.99}}


def _e2e(name, unit, cell, better):
    return {"name": name, "unit": unit, "better": better, "bound": 0.25,
            "source": "host_clock", "workloads": [cell]}


def _layer(name, unit, moves, cell, source="host_clock"):
    return {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "held", "moves": moves, "workloads": [cell]}


HELD_METRICS = {
    "end_to_end": [
        _e2e("mutations_per_s", "mutations/s", "toy.stream", "higher"),
        _e2e("query_p95_ms", "ms", "toy.serve", "lower")],
    "per_layer": [
        _layer("idle_share.stream", "%", "mutations_per_s", "toy.stream",
               "device_trace"),
        _layer("insert_batch_ms", "ms", "mutations_per_s", "toy.stream"),
        _layer("delete_batch_ms", "ms", "mutations_per_s", "toy.stream"),
        _layer("idle_share.serve", "%", "query_p95_ms", "toy.serve",
               "device_trace"),
        _layer("tick_host_ms", "ms", "query_p95_ms", "toy.serve",
               "program_span")]}
CELLS = {"toy.static": ("toy-rmat", "static"),
         "grid.static": ("toy-grid", "static"),
         "toy.stream": ("toy-rmat", "stream"),
         "toy.serve": ("toy-rmat", "serve")}
# the toy cell that stands in for each committed cell
STANDS_FOR = {"soc.static": "toy.static", "usa.static": "grid.static"}


def toy_benchmark(root: Path, configs=(TOY_RMAT, TOY_GRID),
                  cells=CELLS) -> Path:
    """Write the committed BENCHMARK.json with toy configurations, toy
    cells and the held cells' metrics under ``root``, and a benchmark
    home beside it: copies of the drivers, generators and metric
    readers, and the mixes scaled to toy size. Returns the home."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "cfg").mkdir(exist_ok=True)
    for cfg in configs:
        (root / "cfg" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    bench["configs"] = [{"name": c["name"], "file": f"cfg/{c['name']}.json"}
                        for c in configs]
    bench["workloads"] = [{"name": n, "config": c, "traffic": t,
                           "chips": 1} for n, (c, t) in cells.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted(STANDS_FOR[w] for w in m["workloads"])
    for kind, metrics in HELD_METRICS.items():
        bench[kind] += json.loads(json.dumps(metrics))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    home = root / "home"
    for kind in ("traffic", "generators", "metrics"):
        (home / kind).mkdir(parents=True, exist_ok=True)
        for f in (CHIP / kind).glob("*.*"):
            shutil.copy(f, home / kind / f.name)
    for name, mix in HELD_MIXES.items():
        (home / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    return home


@pytest.fixture
def toy(tmp_path):
    """Run a toy cell: ``toy(workload, trace=0, seed=...)``."""
    import jax
    import bench
    home = toy_benchmark(tmp_path)

    def run(workload, trace=0, seed=2**31 + 17, seconds=0.5):
        args = types.SimpleNamespace(workload=workload, seed=seed,
                                     seconds=seconds, trace=trace)
        return bench.run(args, tmp_path, devices=jax.devices(), home=home)

    run.root, run.home = tmp_path, home
    return run


@pytest.fixture
def cpu_trace(monkeypatch):
    """Let a traced run read a CPU trace: the XLA client thread stands
    in for the device plane, and the CPU gets a made-up peak."""
    import roofline
    import trace_reduce
    monkeypatch.setattr(trace_reduce, "is_device_ops_line",
                        lambda p, line: p.startswith("/host:")
                        and line.startswith("tf_XLAPjRt"))
    monkeypatch.setattr(roofline, "peak",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
