"""Each traffic mix end to end at a toy size on the CPU, and the faults
that have to turn ``correct`` false."""
import json

import numpy as np
import pytest

from conftest import CELLS


def expected_metrics(root, workload, traced):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    kind = "per_layer" if traced else "end_to_end"
    return {m["name"] for m in bench[kind]
            if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_untraced_run_reports_the_cells_end_to_end_metrics(toy, workload):
    out = toy(workload)
    res = out["result"]
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == expected_metrics(toy.root, workload, 0)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 1
    assert list(res)[-1] == "checks"
    assert out["notes"]["compiles_in_window"] == 0


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_traced_run_reports_the_cells_per_layer_metrics(toy, cpu_trace,
                                                        workload):
    res = toy(workload, trace=1)["result"]
    assert res["correct"], res
    assert set(res["metrics"]) == expected_metrics(toy.root, workload, 1)
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert 0 < len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10
    for name, m in res["metrics"].items():
        assert m["value"] >= 0, name
        if m["unit"] == "%":
            assert m["value"] <= 100, name


def test_same_seed_same_inputs(toy):
    import graphs
    cfg = json.loads((toy.root / "cfg" / "toy-rmat.json").read_text())
    a = graphs.host_graph(cfg, 2**33 + 5).edges
    assert np.array_equal(a, graphs.host_graph(cfg, 2**33 + 5).edges)
    assert not np.array_equal(a, graphs.host_graph(cfg, 6).edges)
    grid = json.loads((toy.root / "cfg" / "toy-grid.json").read_text())
    side, keep, extra = graphs.generator(grid).sizes(grid["params"])
    assert graphs.host_graph(grid, 1).num_edges == keep + extra


def test_rmat_ids_are_permuted(toy):
    """Graph500 relabels R-MAT's ids: the hub is not vertex 0, and no
    id range holds the high degrees."""
    import graphs
    cfg = json.loads((toy.root / "cfg" / "toy-rmat.json").read_text())
    hubs = []
    for seed in (2**40 + 1, 2, 3, 4, 5):
        e = graphs.host_graph(cfg, seed).edges
        deg = np.bincount(e.ravel(), minlength=cfg["num_vertices"])
        hubs.append(int(deg.argmax()))
        low, high = np.split(deg, 2)
        assert 0.8 < low.sum() / high.sum() < 1.25
    assert len(set(hubs)) == len(hubs)


# -- faults of the timed path: each must make ``correct`` false ----------

def _solve_patch(monkeypatch, alter):
    from repro.api import solver
    real = solver.Solver.solve

    def solve(self, *a, **k):
        res = real(self, *a, **k)
        return res._replace(labels=alter(self, res.labels))

    monkeypatch.setattr(solver.Solver, "solve", solve)


def test_static_fault_state_unchanged(toy, monkeypatch):
    import jax.numpy as jnp
    _solve_patch(monkeypatch, lambda s, lab: jnp.arange(
        lab.shape[0], dtype=lab.dtype))
    assert not toy("toy.static")["result"]["correct"]


def test_static_fault_answer_altered(toy, monkeypatch):
    _solve_patch(monkeypatch, lambda s, lab: lab.at[7].set(lab[7] + 1))
    out = toy("toy.static")["result"]
    assert not out["correct"]
    assert out["checks"]["wrong_labels"]["value"] > 0


def test_static_fault_half_the_edges_left_out(toy, monkeypatch):
    from repro.api import solver
    real = solver.Solver.open.__func__

    def open_half(cls, graph, *a, **k):
        half = type(graph)(edges=graph.edges[: graph.num_edges // 2],
                           num_nodes=graph.num_nodes, name=graph.name)
        return real(cls, half, *a, **k)

    monkeypatch.setattr(solver.Solver, "open", classmethod(open_half))
    assert not toy("toy.static")["result"]["correct"]


def _session_patch(monkeypatch, kind, alter):
    from repro.api import solver
    real = getattr(solver.Solver, kind)

    def call(self, edges):
        return alter(real, self, np.asarray(edges))

    monkeypatch.setattr(solver.Solver, kind, call)


def test_stream_fault_delete_leaves_state_unchanged(toy, monkeypatch):
    _session_patch(monkeypatch, "delete", lambda real, s, e: None)
    assert not toy("toy.stream")["result"]["correct"]


def test_stream_fault_half_of_each_insert_left_out(toy, monkeypatch):
    _session_patch(monkeypatch, "insert",
                   lambda real, s, e: real(s, e[: len(e) // 2]))
    assert not toy("toy.stream")["result"]["correct"]


def test_serve_fault_answer_altered(toy, monkeypatch):
    from repro.connectivity import service
    real = service.ConnectivityService._run_query_group

    def altered(self, tenant, kind, reqs):
        real(self, tenant, kind, reqs)
        if kind == "component_size":
            reqs[0].result = np.asarray(reqs[0].result) + 1

    monkeypatch.setattr(service.ConnectivityService, "_run_query_group",
                        altered)
    res = toy("toy.serve")["result"]
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_serve_fault_half_of_each_batch_left_out(toy, monkeypatch):
    from repro.connectivity import service
    real = service.ConnectivityService._run_query_group

    def half(self, tenant, kind, reqs):
        real(self, tenant, kind, reqs)
        for r in reqs:
            r.result = np.asarray(r.result)[: len(r.result) // 2]

    monkeypatch.setattr(service.ConnectivityService, "_run_query_group",
                        half)
    assert not toy("toy.serve")["result"]["correct"]


def test_serve_fault_request_never_answered(toy, monkeypatch):
    from repro.connectivity import service
    real = service.ConnectivityService.step

    def drop_some(self):
        return [r for r in real(self) if r.uid % 10]

    monkeypatch.setattr(service.ConnectivityService, "step", drop_some)
    res = toy("toy.serve")["result"]
    assert not res["correct"]
    assert res["failed"] > 0
