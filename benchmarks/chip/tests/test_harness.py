"""The harness: found by name, refuses the CPU, peaks, controls, and the
shape of BENCHMARK.json."""
import json
import re

import pytest

import bench
import control
import roofline
from conftest import CHIP, REPO, TOY_RMAT, toy_benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_unknown_device_kind_raises():
    with pytest.raises(roofline.UnknownDevice, match="cpu"):
        roofline.peak("cpu")
    v5e = roofline.peak("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]


def test_least_solve_bytes_of_soc():
    assert roofline.least_solve_bytes(2**22, 29_360_128) == 251_658_240


def test_no_tpu_exits_nonzero_with_no_result(capsys):
    rc = bench.main(["--workload", "soc.static", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs a TPU" in out.err


def _add(root, *, config=None, cell=None, metric=None):
    """Append entries to the toy BENCHMARK.json, as a later PR would."""
    b = json.loads((root / "BENCHMARK.json").read_text())
    if config:
        (root / "cfg" / f"{config['name']}.json").write_text(
            json.dumps(config))
        b["configs"].append({"name": config["name"],
                             "file": f"cfg/{config['name']}.json"})
    if cell:
        b["workloads"].append(dict(cell, chips=1))
        for m in b["end_to_end"]:
            if m["name"] == "solve_edges_per_s" and \
                    cell["traffic"] == "static":
                m["workloads"].append(cell["name"])
    if metric:
        b[metric[0]].append(metric[1])
    (root / "BENCHMARK.json").write_text(json.dumps(b))


def _run(root, home, workload, trace=0):
    import types
    import jax
    args = types.SimpleNamespace(workload=workload, seed=3, seconds=0.3,
                                 trace=trace)
    return bench.run(args, root, devices=jax.devices(), home=home)["result"]


def test_a_new_config_file_and_entry_make_a_runnable_cell(tmp_path):
    """Adding a deployment touches no existing file: one configuration
    file and one BENCHMARK.json entry."""
    home = toy_benchmark(tmp_path)
    new = dict(TOY_RMAT, name="toy-kron",
               params=dict(TOY_RMAT["params"], a=0.57, b=0.19, c=0.19))
    _add(tmp_path, config=new, cell={"name": "kron.static",
                                     "config": "toy-kron",
                                     "traffic": "static"})
    res = _run(tmp_path, home, "kron.static")
    assert res["correct"]
    assert set(res["metrics"]) == {"solve_edges_per_s", "setup_s"}


RING = '''"""A ring of ``n`` vertices cut into ``pieces`` arcs."""
import jax.numpy as jnp


def graph(key, params):
    n, pieces = int(params["n"]), int(params["pieces"])
    u = jnp.arange(n, dtype=jnp.int32)
    keep = (u + 1) % (n // pieces) != 0
    return jnp.stack([u, (u + 1) % n], 1)[keep], n
'''


def test_a_new_generator_file_makes_a_runnable_cell(tmp_path):
    """A new kind of graph is one file under ``generators/`` plus its
    configuration: no existing file changes."""
    home = toy_benchmark(tmp_path)
    (home / "generators" / "ring.py").write_text(RING)
    ring = {"name": "toy-ring", "generator": "ring",
            "params": {"n": 1024, "pieces": 8}}
    _add(tmp_path, config=ring, cell={"name": "ring.static",
                                      "config": "toy-ring",
                                      "traffic": "static"})
    res = _run(tmp_path, home, "ring.static")
    assert res["correct"] and res["attempted"] >= 1


REPEAT = '''"""Solves of one method the mix names, back to back."""
import numpy as np

import reference
from drivers import Driver, now


class Traffic(Driver):

    def setup(self):
        self.load_graph()
        self._solve()

    def _solve(self):
        from repro.api import Solver
        return np.asarray(Solver.open(self.graph)
                          .solve(self.mix["method"]).labels)

    def window(self):
        start, self.out = now(), []
        while now() - start < self.seconds or not self.out:
            self.out.append(self._solve())
        self.record("solve", (now() - start) / len(self.out))
        return {"labels_per_s": len(self.out) * self.graph.num_nodes
                / (now() - start)}

    def check(self):
        ref = reference.cc_labels(self.graph.edges, self.graph.num_nodes)
        bad = sum(not np.array_equal(x, ref) for x in self.out)
        return len(self.out), bad, {"bad_solves": (bad, 0)}
'''
READER = '''"""Mean solve time of the window."""


def read(ctx):
    t = ctx.timings.get("solve")
    return t[0] if t else None
'''


def test_a_new_driver_mix_and_metric_make_a_runnable_cell(tmp_path,
                                                        cpu_trace):
    """A new kind of traffic is a driver file, a mix file and a reader
    file, plus BENCHMARK.json entries: no existing file changes."""
    home = toy_benchmark(tmp_path)
    (home / "traffic" / "repeat.py").write_text(REPEAT)
    (home / "traffic" / "adaptive.json").write_text(json.dumps(
        {"driver": "repeat", "method": "adaptive"}))
    (home / "metrics" / "solve_ms.py").write_text(READER)
    _add(tmp_path, cell={"name": "toy.adaptive", "config": "toy-rmat",
                         "traffic": "adaptive"},
         metric=("end_to_end", {"name": "labels_per_s", "unit": "labels/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["toy.adaptive"]}))
    _add(tmp_path, metric=("per_layer", {
        "name": "solve_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "api", "moves": "labels_per_s",
        "workloads": ["toy.adaptive"]}))
    res = _run(tmp_path, home, "toy.adaptive")
    assert res["correct"], res
    assert set(res["metrics"]) == {"labels_per_s", "setup_s"}
    res = _run(tmp_path, home, "toy.adaptive", trace=1)
    assert res["correct"] and set(res["metrics"]) == {"solve_ms"}


@pytest.mark.parametrize("workload,check", [("toy.static", "wrong_labels"),
                                            ("grid.static", "wrong_labels"),
                                            ("toy.stream", "wrong_labels"),
                                            ("toy.serve", "wrong_answers")])
def test_the_control_comes_out_not_correct(tmp_path, workload, check):
    import jax
    home = toy_benchmark(tmp_path)
    out = control.control(workload, 5, 0.5, root=tmp_path,
                          devices=jax.devices(), home=home)
    c = out["checks"][check]
    assert c["value"] > c["limit"]


def test_benchmark_json_follows_its_contract():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert b["command"] == ["python3", "benchmarks/chip/bench.py"]
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    metrics = b["end_to_end"] + b["per_layer"]
    names = list(cells) + list(configs) + [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert (CHIP / "generators" / f"{cfg['generator']}.py").exists()
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        mix = json.loads((CHIP / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (CHIP / "traffic" / f"{mix['driver']}.py").exists()
        assert len(w["why"]) <= 200
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in \
        e2e["setup_s"]
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:
        reported = [m for m in b["end_to_end"]
                    if w in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w in m["workloads"] for m in b["per_layer"])
