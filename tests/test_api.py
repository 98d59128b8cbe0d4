"""The repro.api facade (ISSUE 5 / DESIGN.md §10).

Covers the four tentpole claims:

  * **surface stability** — ``repro.api.__all__`` and the ``BACKENDS``
    registry (names + capability matrix) are snapshot-pinned, so any
    accidental drift of the public surface fails loudly;
  * **inspectable plans** — ``plan.explain()`` reports the backend that
    ``policy.select_method`` actually chooses (asserted over corpus
    graphs), forced-backend overrides round-trip, and planning touches
    the device not at all;
  * **the Solver session** — static solve, insert, delete, and every
    ``queries.py`` lookup agree with the dynamic oracle; the
    steady-state mutation path is transfer-free under
    ``jax.transfer_guard("disallow")`` when driven via the facade;
  * **single counting implementation** — ``cc.num_components``,
    ``IncrementalCC.num_components``, ``Solver.num_components`` and the
    registry all delegate to ``connectivity.queries.count_components``.
"""
import numpy as np
import pytest

from _graphgen import corpus
from repro import __version__
from repro.api import (BACKENDS, Capabilities, ExecutionPlan, Solver,
                       available_backends, capability_matrix, get_backend,
                       register_backend, solve)
from repro.connectivity import policy
from repro.core.unionfind import (DynamicConnectivityOracle,
                                  connected_components_oracle)

import repro.api as api_mod  # noqa: E402  (module-object identity checks)


# ---------------------------------------------------------------------------
# Public-API stability (CI satellite): snapshot, fail on surface drift
# ---------------------------------------------------------------------------

EXPECTED_ALL = [
    "BACKENDS", "Backend", "CCResult", "Capabilities", "DeviceGraph",
    "ExecutionPlan", "Solver", "WorkCounters", "available_backends",
    "capability_matrix", "get_backend", "register_backend", "solve",
]

EXPECTED_BACKENDS = [
    "adaptive", "atomic_hook", "batched", "distributed", "dynamic",
    "hostloop", "incremental", "labelprop", "multijump", "pallas",
    "pallas_fused", "sampled", "sampled_fused", "soman",
]

# (static, batched, streaming, deletions, sharded, device_loop,
#  bit_exact_counters, spanning_forest, maintained_forest) per backend —
# the DESIGN.md §10 capability matrix (maintained_forest = keeps the
# forest as a device resident across mutations, DESIGN.md §14)
EXPECTED_CAPABILITIES = {
    "soman":         (1, 0, 0, 0, 0, 1, 1, 1, 0),
    "multijump":     (1, 0, 0, 0, 0, 1, 1, 1, 0),
    "atomic_hook":   (1, 0, 0, 0, 0, 1, 1, 1, 0),
    "adaptive":      (1, 0, 0, 0, 0, 1, 1, 1, 0),
    "labelprop":     (1, 0, 0, 0, 0, 1, 1, 0, 0),
    "pallas":        (1, 0, 0, 0, 0, 1, 0, 0, 0),
    "pallas_fused":  (1, 0, 0, 0, 0, 1, 1, 0, 0),
    "sampled":       (1, 0, 0, 0, 0, 1, 1, 1, 0),
    "sampled_fused": (1, 0, 0, 0, 0, 1, 1, 0, 0),
    "hostloop":      (1, 0, 0, 0, 0, 0, 0, 0, 0),
    "batched":       (1, 1, 0, 0, 0, 1, 1, 0, 0),
    "incremental":   (1, 0, 1, 0, 0, 1, 1, 0, 0),
    "dynamic":       (1, 0, 1, 1, 0, 1, 1, 0, 1),
    "distributed":   (1, 0, 0, 0, 1, 1, 0, 0, 0),
}

_CAP_FIELDS = ("static", "batched", "streaming", "deletions", "sharded",
               "device_loop", "bit_exact_counters", "spanning_forest",
               "maintained_forest")


def test_public_api_surface_is_stable():
    assert sorted(api_mod.__all__) == EXPECTED_ALL
    assert available_backends() == EXPECTED_BACKENDS
    assert __version__                      # from repro import Solver works
    import repro
    assert repro.Solver is Solver
    assert sorted(repro.__all__) == sorted(["__version__"] + EXPECTED_ALL)


def test_backend_capability_matrix_is_stable():
    matrix = capability_matrix()
    assert sorted(matrix) == EXPECTED_BACKENDS
    got = {name: tuple(int(caps[f]) for f in _CAP_FIELDS)
           for name, caps in matrix.items()}
    assert got == EXPECTED_CAPABILITIES


def test_register_backend_is_a_one_decorator_change():
    """Third-party backends plug in with one decorator and are
    immediately routable by name (and duplicate names are rejected)."""
    name = "_test_constant"
    try:
        @register_backend(name, Capabilities(static=True))
        def _run(plan):
            import jax.numpy as jnp
            from repro.api import CCResult, WorkCounters
            return CCResult(jnp.zeros((plan.num_nodes,), jnp.int32),
                            WorkCounters.zeros())

        assert get_backend(name).capabilities.static
        res = Solver.open([[0, 1]], 3).solve(backend=name)
        assert np.asarray(res.labels).tolist() == [0, 0, 0]
        with pytest.raises(ValueError, match="already registered"):
            register_backend(name, Capabilities())(lambda plan: None)
    finally:
        BACKENDS.pop(name, None)

    with pytest.raises(KeyError, match="unknown backend"):
        get_backend("_no_such_backend")


# ---------------------------------------------------------------------------
# ExecutionPlan: the adaptive decision, inspectable (acceptance criterion)
# ---------------------------------------------------------------------------

def test_plan_explain_reports_the_policy_choice():
    """For corpus graphs spanning the heuristic's regimes, the plan's
    backend equals what ``policy.select_method`` chooses for the same
    (|V|, |E|) — with a shared empty cache so the autotune layer cannot
    diverge the comparison."""
    cache = policy.AutotuneCache()          # in-memory, empty
    checked = 0
    for name, n, edges in corpus():
        if n == 0 or len(edges) == 0:
            continue
        solver = Solver.open(edges, n, policy_cache=cache)
        plan = solver.plan()
        want = policy.select_method(n, len(edges), cache=cache)
        assert plan.backend == want, (name, plan.backend, want)
        assert plan.reason == "heuristic"
        text = plan.explain()
        assert plan.backend in text
        assert plan.bucket_key in text
        assert f"|V|={n}" in text and f"|E|={len(edges)}" in text
        checked += 1
    assert checked >= 3                     # the ISSUE's floor


def test_plan_reports_autotune_provenance():
    """A warm autotune cache overrides the heuristic AND the plan says
    so."""
    name, n, edges = next(c for c in corpus() if c[1] > 0 and len(c[2]))
    cache = policy.AutotuneCache()
    cache.record(n, len(edges), "labelprop", 1.0)   # fake measured winner
    plan = Solver.open(edges, n, policy_cache=cache).plan()
    assert plan.backend == "labelprop"
    assert plan.reason == "autotune"
    assert "autotune" in plan.explain()


def test_plan_forced_backend_round_trips():
    """A forced backend override survives plan -> run -> result, for
    every static single-graph backend."""
    name, n, edges = next(c for c in corpus()
                          if c[1] > 0 and len(c[2]) >= 8)
    want = connected_components_oracle(edges, n)
    solver = Solver.open(edges, n)
    for backend in ("soman", "adaptive", "labelprop", "pallas_fused"):
        plan = solver.plan(backend=backend)
        assert plan.backend == backend and plan.reason == "forced"
        assert "forced" in plan.explain()
        res = plan.run()
        np.testing.assert_array_equal(np.asarray(res.labels), want,
                                      err_msg=backend)
        assert solver.plan(method=backend).backend == backend
    with pytest.raises(ValueError, match="unknown method"):
        solver.plan(method="frobnicate")
    # forced backends validate at PLAN time, not deep inside run()
    with pytest.raises(KeyError, match="unknown backend"):
        solver.plan(backend="_no_such")
    with pytest.raises(ValueError, match="solve_batch"):
        solver.plan(backend="batched")
    with pytest.raises(ValueError, match="needs a mesh"):
        solver.plan(backend="distributed")
    with pytest.raises(ValueError, match="not\\s+both"):
        solver.plan("soman", backend="adaptive")
    # typo'd tuning kwargs raise (legacy TypeError strictness kept)
    with pytest.raises(TypeError, match="unknown option"):
        solver.plan("adaptive", lift_step=9)
    # fresh sessions read zeroed counters, never KeyError
    assert Solver.open(num_nodes=3).work["hook_ops"] == 0


def test_plan_forced_method_wins_over_mesh_default():
    """A mesh session defaults to the distributed backend, but an
    explicitly named method must still route to its own backend (with
    real work counters), and unknown methods must still raise."""
    import jax
    name, n, edges = next(c for c in corpus()
                          if c[1] > 0 and len(c[2]) >= 8)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    solver = Solver.open(edges, n, mesh=mesh)
    assert solver.plan().backend == "distributed"
    plan = solver.plan("soman")
    assert plan.backend == "soman" and plan.reason == "forced"
    res = plan.run()
    assert int(res.work.hook_ops) > 0          # real counters, not zeros
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  connected_components_oracle(edges, n))
    with pytest.raises(ValueError, match="unknown method"):
        solver.plan("frobnicate")


def test_plan_segmentation_override_and_prediction():
    name, n, edges = next(c for c in corpus()
                          if c[1] > 0 and len(c[2]) >= 16)
    solver = Solver.open(edges, n)
    plan = solver.plan(method="adaptive", num_segments=4)
    assert plan.segmentation.num_segments == 4
    assert "override" in plan.explain()
    assert plan.predicted["hook_ops_per_round"] == len(edges)
    assert plan.predicted["jump_ops_per_sweep"] == n
    res = plan.run()
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  connected_components_oracle(edges, n))


def test_plan_on_mutated_session_uses_live_edge_features():
    """A streaming session's plan must feed the policy the host-tracked
    edge count, NOT the log's pow2 capacity padding — selection,
    autotune bucket, and explain() metadata all key off it."""
    from repro.core.batch import bucket_shape
    rng = np.random.default_rng(7)
    n = 64
    s = Solver.open(num_nodes=n, policy_cache=policy.AutotuneCache())
    edges = rng.integers(0, n, (1000, 2)).astype(np.int32)
    s.insert(edges)
    plan = s.plan()
    assert plan.num_edges == s.num_edges == 1000   # not capacity (1024+)
    assert plan.bucket == bucket_shape(n, 1000)
    want = policy.select_method(n, 1000, cache=s.policy_cache)
    assert plan.backend == want
    np.testing.assert_array_equal(
        np.asarray(plan.run().labels),
        connected_components_oracle(edges, n))


def test_plan_is_pure_host_metadata():
    """Planning never touches the device: legal in full under
    ``transfer_guard("disallow")`` once the graph is device-resident."""
    import jax
    from repro.graphs.device import DeviceGraph
    name, n, edges = next(c for c in corpus() if c[1] > 0 and len(c[2]))
    dg = DeviceGraph.from_edges(edges, n)
    solver = Solver.open(dg)
    with jax.transfer_guard("disallow"):
        plan = solver.plan()
        plan.explain()
        solver.plan(backend="pallas_fused").explain()


# ---------------------------------------------------------------------------
# The Solver session: solve + insert + delete + queries vs the oracle
# ---------------------------------------------------------------------------

def test_solver_session_full_lifecycle_matches_oracle():
    rng = np.random.default_rng(0)
    n = 32
    e1 = rng.integers(0, n, (40, 2)).astype(np.int32)
    e2 = rng.integers(0, n, (6, 2)).astype(np.int32)
    s = Solver.open(e1, n)
    oracle = DynamicConnectivityOracle(n)
    oracle.insert(e1)

    np.testing.assert_array_equal(np.asarray(s.solve().labels),
                                  oracle.labels())
    s.insert(e2)
    oracle.insert(e2)
    np.testing.assert_array_equal(np.asarray(s.labels), oracle.labels())

    kills = e1[:5]
    s.delete(kills)
    oracle.delete(kills)
    labels = oracle.labels()
    np.testing.assert_array_equal(np.asarray(s.labels), labels)

    # every queries.py lookup, via the session
    pairs = rng.integers(0, n, (17, 2))
    np.testing.assert_array_equal(
        s.same_component(pairs),
        labels[pairs[:, 0]] == labels[pairs[:, 1]])
    verts = rng.integers(0, n, 9)
    sizes = {v: int((labels == labels[v]).sum()) for v in verts}
    np.testing.assert_array_equal(
        s.component_size(verts), [sizes[v] for v in verts])
    assert s.num_components() == np.unique(labels).size
    assert s.connected(int(pairs[0, 0]), int(pairs[0, 1])) == bool(
        labels[pairs[0, 0]] == labels[pairs[0, 1]])
    hist = s.component_histogram()
    assert int(hist.sum()) == np.unique(labels).size
    np.testing.assert_array_equal(
        np.asarray(s.component_sizes()),
        [int((labels == c).sum()) for c in labels])

    # bounds validation at the facade boundary
    with pytest.raises(ValueError, match="out of range"):
        s.same_component([[0, n]])
    with pytest.raises(ValueError, match="out of range"):
        s.insert([[0, n]])
    with pytest.raises(ValueError, match="num_nodes"):
        from repro.graphs.device import DeviceGraph
        s.insert(DeviceGraph.from_edges([[0, 1]], n + 1))


def test_solver_open_requires_a_graph_or_num_nodes():
    with pytest.raises(ValueError, match="graph or"):
        Solver.open()
    # bare session over |V| only: labels solve lazily to identity —
    # and the property read leaves introspection state untouched
    s = Solver.open(num_nodes=5)
    assert np.asarray(s.labels).tolist() == [0, 1, 2, 3, 4]
    assert s.num_components() == 5
    assert s.stats["solves"] == 0
    assert s.last_method is None and s.last_plan is None


def test_solver_routes_mutations_through_policy():
    """Bulk first batch -> static rebuild; small second batch ->
    incremental absorb; small delete -> scoped tombstone route. Same
    contract the registry/service stack inherits from the facade."""
    g = np.stack([np.arange(30), np.arange(30) + 1], 1).astype(np.int32)
    s = Solver.open(num_nodes=31)
    s.insert(g)                              # bulk: no absorbed set yet
    assert s.last_method in policy.AUTOTUNE_METHODS
    assert s.stats["rebuilds"] == 1
    s.insert(g[:3])
    assert s.last_method == policy.INCREMENTAL_ABSORB
    assert s.stats["absorbs"] == 1
    s.delete(g[:2])
    assert s.last_method in policy.DELETE_METHODS
    assert s.stats["scoped_deletes"] == 1
    assert s.version == int(s.version_device)
    # route counters stay internally consistent: every mutation is
    # classified exactly once
    assert s.stats["absorbs"] + s.stats["scoped_deletes"] + \
        s.stats["rebuilds"] == s.stats["inserts"] + s.stats["deletes"]

    # opening WITH edges counts the seed snapshot as the first (bulk)
    # insert, so the same invariant holds for graph-opened sessions
    s2 = Solver.open(g, 31)
    s2.insert(g[:3])
    assert s2.stats["inserts"] == 2          # seed + explicit batch
    assert s2.stats["absorbs"] + s2.stats["rebuilds"] == 2


def test_solver_steady_state_mutations_are_transfer_free():
    """Acceptance (ISSUE 5): the steady-state insert AND delete paths
    stay transfer-free under ``jax.transfer_guard("disallow")`` when
    driven directly via the facade (the service test pins the same
    property through the full registry/service stack)."""
    import jax
    from repro.graphs.device import DeviceGraph

    rng = np.random.default_rng(3)
    n = 64
    edges = rng.integers(0, n, (96, 2)).astype(np.int32)
    s = Solver.open(num_nodes=n)
    # warm every jit entry the steady state will hit
    s.insert(edges[:64])
    s.insert(DeviceGraph.from_edges(edges[64:72], n))
    s.delete(DeviceGraph.from_edges(edges[:8], n))

    with jax.transfer_guard("disallow"):
        s.insert(DeviceGraph.from_edges(edges[72:80], n))
        s.delete(DeviceGraph.from_edges(edges[8:16], n))

    oracle = DynamicConnectivityOracle(n)
    oracle.insert(edges[:80])
    oracle.delete(edges[:16])
    np.testing.assert_array_equal(np.asarray(s.labels), oracle.labels())


def test_solver_forest_route_steady_state_transfer_free():
    """ISSUE 9: the forest-maintaining absorb and the tree-aware delete
    are single-device-program ticks too — the steady state stays
    transfer-free under ``jax.transfer_guard("disallow")`` once warmed
    (the lazy ``ensure_forest`` rebuild is the only syncing exception
    and runs outside the guard here)."""
    import jax
    from repro.graphs.device import DeviceGraph

    rng = np.random.default_rng(5)
    n = 64
    edges = rng.integers(0, n, (96, 2)).astype(np.int32)
    s = Solver.open(num_nodes=n, delete_route="tombstone-delete-forest")
    s.insert(edges[:64])                 # bulk seed (may adopt)
    s.state.ensure_forest()              # repair + warm outside the guard
    s.insert(DeviceGraph.from_edges(edges[64:72], n))
    s.delete(DeviceGraph.from_edges(edges[:8], n))
    assert s.state.forest_valid

    with jax.transfer_guard("disallow"):
        s.insert(DeviceGraph.from_edges(edges[72:80], n))
        s.delete(DeviceGraph.from_edges(edges[8:16], n))

    oracle = DynamicConnectivityOracle(n)
    oracle.insert(edges[:80])
    oracle.delete(edges[:16])
    np.testing.assert_array_equal(np.asarray(s.labels), oracle.labels())


def test_solver_solve_batch_mixed_inputs():
    graphs = [(np.array([[0, 1], [2, 3]], np.int32), 5),
              (np.array([[0, 1]], np.int32), 2),
              (np.array([[1, 2], [0, 3], [3, 4]], np.int32), 6)]
    out = Solver.solve_batch(graphs)
    for (edges, n), res in zip(graphs, out):
        np.testing.assert_array_equal(
            np.asarray(res.labels),
            connected_components_oracle(edges, n))


# ---------------------------------------------------------------------------
# Single counting implementation (satellite): everything delegates to
# connectivity.queries.count_components
# ---------------------------------------------------------------------------

def test_num_components_single_implementation():
    from repro.connectivity import queries
    from repro.core.cc import num_components
    from repro.core.incremental import IncrementalCC

    for name, n, edges in corpus():
        if n == 0:
            continue
        labels = connected_components_oracle(edges, n)
        want = int(np.unique(labels).size)
        assert num_components(labels) == want, name
        assert int(queries.count_components(labels)) == want, name
        inc = IncrementalCC(n)
        inc.insert(edges)
        assert inc.num_components() == want, name
        s = Solver.open(edges, n)
        assert s.num_components() == want, name


def test_count_components_is_the_only_device_counter(monkeypatch):
    """Pin the delegation: cc.num_components, IncrementalCC, and the
    Solver all route through queries.count_components (monkeypatching
    it changes every answer)."""
    from repro.connectivity import queries
    from repro.core.cc import num_components
    from repro.core.incremental import IncrementalCC
    import jax.numpy as jnp

    monkeypatch.setattr(queries, "count_components",
                        lambda labels: jnp.asarray(12345, jnp.int32))
    labels = np.zeros(4, np.int32)
    assert num_components(labels) == 12345
    inc = IncrementalCC(4)
    assert inc.num_components() == 12345
    assert Solver.open(np.zeros((0, 2), np.int32), 4) \
        .num_components() == 12345


# ---------------------------------------------------------------------------
# Registry/service parity: the tenant layer is a thin shell over Solver
# ---------------------------------------------------------------------------

def test_tenant_graph_is_backed_by_a_solver_session():
    from repro.connectivity.registry import GraphRegistry

    reg = GraphRegistry()
    t = reg.create("t", 16)
    assert isinstance(t.solver, Solver)
    edges = np.array([[0, 1], [1, 2], [4, 5]], np.int32)
    reg.insert("t", edges)
    assert t.last_method == t.solver.last_method
    np.testing.assert_array_equal(np.asarray(t.labels),
                                  np.asarray(t.solver.labels))
    assert reg.count_components("t") == Solver.open(edges, 16) \
        .num_components()
