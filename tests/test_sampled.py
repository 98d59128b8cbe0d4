"""The sampling-accelerated backends (ISSUE 8): k-out sampling phase +
residue scan, the degree-skew policy feature, the phase-split
telemetry, and the headline work reduction.

Conformance (labels vs both oracles over the corpus) lives in
``test_conformance.py``'s matrix — ``sampled`` / ``sampled_fused`` are
ordinary rows there, and the spanning-forest property test covers the
forest product. This module pins what is SPECIFIC to sampling:

* ``DeviceGraph.degree_skew`` — measured once at host ingest, ``None``
  for device-resident arrays, preserved across the pytree protocol;
* the policy routing rule — ``method="auto"`` picks ``sampled`` on
  skewed-degree graphs at scale and never on road-like or corpus-sized
  inputs;
* the acceptance criterion — ``sampled`` total hook_ops <= half the
  ``jnp`` adaptive backend's on a power-law stand-in, labels identical;
* the ``repro.obs`` work split (``sampled.hook_ops.sample`` /
  ``.residue`` device counters, kept while tracing is on) and the
  per-plan ``sampled_stats`` artifact;
* labels-only solves — a forest is recorded (and
  ``sampled.forest_solves`` counted) only for ``spanning_forest()``,
  and leaving it out changes no label, counter or statistic.
"""
import numpy as np
import pytest

from _graphgen import corpus, power_law
from repro.api import Solver, solve
from repro.connectivity import policy
from repro.core.rounds import WorkCounters
from repro.core.unionfind import connected_components_oracle


def _powerlaw_edges(n, e, seed=7):
    return np.asarray(power_law(n, e, seed), np.int32)


def _grid_edges(side):
    """Road-network stand-in: a 2D grid (skew ~= 2, tiny diameter of
    degree variation)."""
    idx = np.arange(side * side).reshape(side, side)
    return np.concatenate([
        np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], -1),
        np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], -1),
    ]).astype(np.int32)


# ---------------------------------------------------------------------------
# degree_skew: static metadata, measured at host ingest only
# ---------------------------------------------------------------------------

def test_degree_skew_measured_at_host_ingest():
    import jax
    import jax.numpy as jnp

    from repro.graphs.device import DeviceGraph

    star = np.stack([np.zeros(63, np.int64), np.arange(1, 64)], -1)
    g = DeviceGraph.from_edges(star, 64)
    # deg(hub) = 63, mean degree = 2*63/64 -> skew = exactly 32
    assert g.degree_skew == pytest.approx(32.0)

    # device-resident arrays skip the measurement (no transfer)
    g_dev = DeviceGraph.from_edges(jnp.asarray(star, jnp.int32), 64)
    assert g_dev.degree_skew is None

    # the skew rides the pytree aux data across jit boundaries
    leaves, treedef = jax.tree_util.tree_flatten(g)
    assert jax.tree_util.tree_unflatten(treedef, leaves).degree_skew \
        == pytest.approx(32.0)

    # ...and survives the device-side shaping helpers
    assert g.pad_pow2().degree_skew == pytest.approx(32.0)

    # degenerate inputs do not divide by zero
    assert DeviceGraph.from_edges(np.zeros((0, 2), np.int32),
                                  5).degree_skew == 1.0


def test_degree_skew_separates_skewed_from_road_like():
    from repro.graphs.device import measure_degree_skew
    skew_pl = measure_degree_skew(_powerlaw_edges(1024, 8192), 1024)
    skew_grid = measure_degree_skew(_grid_edges(32), 1024)
    assert skew_pl >= policy.SAMPLED_SKEW, skew_pl
    assert skew_grid < policy.SAMPLED_SKEW, skew_grid


# ---------------------------------------------------------------------------
# Policy routing: skewed at scale -> sampled; road/corpus-sized -> not
# ---------------------------------------------------------------------------

def test_policy_routes_sampled_on_skewed_graphs_at_scale():
    f = policy.extract_features
    assert policy.heuristic_method(
        f(10_000, 80_000, degree_skew=50.0)) == "sampled"
    # road-like skew: never sampled
    assert policy.heuristic_method(
        f(10_000, 80_000, degree_skew=2.0)) == "adaptive"
    # below the edge floor: the exact engines win (two extra jit
    # launches don't pay for themselves)
    assert policy.heuristic_method(
        f(512, 2_000, degree_skew=50.0)) != "sampled"
    # unmeasured skew (device-resident ingest): no sampling route
    assert policy.heuristic_method(f(10_000, 80_000)) == "adaptive"
    # select_method threads the kwarg through the explained path
    assert policy.select_method(10_000, 80_000, degree_skew=50.0,
                                cache=policy.AutotuneCache()) == "sampled"
    # the sampled engine is an autotune candidate
    assert "sampled" in policy.AUTOTUNE_METHODS


def test_auto_plan_picks_sampled_for_powerlaw_host_ingest():
    edges = _powerlaw_edges(1024, 8192)
    s = Solver.open(edges, 1024, policy_cache=policy.AutotuneCache())
    plan = s.plan()
    assert plan.backend == "sampled"
    assert plan.reason == "heuristic"
    assert plan.predicted["degree_skew"] >= policy.SAMPLED_SKEW
    assert "sampled" in plan.explain()

    # a road-like graph of the same size stays on the exact engines
    road = Solver.open(_grid_edges(32), 1024,
                       policy_cache=policy.AutotuneCache())
    assert road.plan().backend != "sampled"


# ---------------------------------------------------------------------------
# Acceptance criterion: >= 2x hook_ops reduction, labels identical
# ---------------------------------------------------------------------------

def test_sampled_halves_hook_ops_on_skewed_stand_in():
    n, e = 1024, 8192
    edges = _powerlaw_edges(n, e)
    want = connected_components_oracle(edges, n)

    base = solve(edges, n, backend="adaptive")
    samp = solve(edges, n, backend="sampled")
    np.testing.assert_array_equal(np.asarray(base.labels), want)
    np.testing.assert_array_equal(np.asarray(samp.labels), want)
    assert 2 * int(samp.work.hook_ops) <= int(base.work.hook_ops), (
        int(samp.work.hook_ops), int(base.work.hook_ops))


def test_sampled_stats_artifact_shows_phase_split():
    n, e = 1024, 8192
    s = Solver.open(_powerlaw_edges(n, e), n)
    res = s.solve(backend="sampled")
    stats = s.last_plan.artifacts["sampled_stats"]
    assert set(stats) == {"sample_hook_ops", "residue_hook_ops",
                          "n_sampled", "n_residue", "giant_label",
                          "giant_size"}
    # phase billing folds exactly into the total
    assert stats["sample_hook_ops"] + stats["residue_hook_ops"] \
        == int(res.work.hook_ops)
    # the sampling phase did collapse a giant component: the residue is
    # a small fraction of the edge list
    assert stats["giant_size"] > n // 2
    assert stats["n_residue"] < e // 4
    # k-out sampling touches at most V*k slots per round
    from repro.core.sampled import SAMPLE_K
    assert stats["n_sampled"] <= n * SAMPLE_K


def test_sampled_obs_counters_record_work_split():
    """With tracing on, a sampled solve keeps its work split as device
    counters, read back as ints that sum to the solve's hook work."""
    from repro.obs import trace as obs
    obs.tracer().reset()
    obs.enable()
    try:
        res = solve(_powerlaw_edges(256, 1024, seed=9), 256,
                    backend="sampled")
        counters = dict(obs.tracer().counters)
    finally:
        obs.disable()
        obs.tracer().reset()
    assert counters["sampled.solves"] == 1
    assert counters["sampled.hook_ops.sample"] > 0
    # the residue side may legitimately bill 0 (the sampling phase can
    # fully collapse a small graph) but the counter must be surfaced
    assert counters["sampled.hook_ops.sample"] \
        + counters["sampled.hook_ops.residue"] == int(res.work.hook_ops)
    assert all(type(v) is int for v in counters.values())


def test_sampled_fused_matches_sampled_labels_and_counters():
    """The fused-residue variant is label-identical; its counters match
    the jnp-residue variant's (both bill true work only)."""
    n, e = 512, 4096
    edges = _powerlaw_edges(n, e, seed=11)
    a = solve(edges, n, backend="sampled")
    b = solve(edges, n, backend="sampled_fused")
    np.testing.assert_array_equal(np.asarray(a.labels),
                                  np.asarray(b.labels))
    for field, x, y in zip(WorkCounters._fields, a.work, b.work):
        assert int(x) == int(y), (field, int(x), int(y))


# ---------------------------------------------------------------------------
# Labels-only solves: a forest is recorded only when the caller asks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,edges", [
    pytest.param(n, e, id=name) for name, n, e in corpus()])
def test_labels_only_solve_matches_forest_solve(n, edges):
    """``solve_sampled`` without a forest gives the labels, counters and
    phase split of the forest-recording solve bit for bit, the labels
    of the ``sampled`` backend, and no parent table."""
    from repro.core.sampled import solve_sampled
    plain = solve_sampled(edges, n)
    forest = solve_sampled(edges, n, forest=True)
    assert plain.parents is None
    assert np.asarray(forest.parents).shape == (n, 2)
    np.testing.assert_array_equal(np.asarray(plain.labels),
                                  np.asarray(forest.labels))
    for field, x, y in zip(WorkCounters._fields, plain.work, forest.work):
        assert int(x) == int(y), (field, int(x), int(y))
    assert plain.stats.keys() == forest.stats.keys()
    for key in plain.stats:
        assert int(plain.stats[key]) == int(forest.stats[key]), key
    np.testing.assert_array_equal(
        np.asarray(plain.labels),
        np.asarray(solve(edges, n, backend="sampled").labels))


def test_forest_solves_counter_counts_only_forest_requests():
    """``solve("auto")`` routed to ``sampled`` records no forest; the
    ``spanning_forest()`` that follows records one."""
    from repro.obs import trace as obs
    obs.tracer().reset()
    try:
        s = Solver.open(_powerlaw_edges(1024, 8192), 1024,
                        policy_cache=policy.AutotuneCache())
        s.solve("auto")
        assert s.last_method == "sampled"
        assert obs.tracer().counters.get("sampled.forest_solves", 0) == 0
        forest = s.spanning_forest()
        assert obs.tracer().counters["sampled.forest_solves"] == 1
    finally:
        obs.tracer().reset()
    assert np.asarray(forest.parents).shape == (1024, 2)


def test_fused_residue_records_no_forest():
    from repro.core.sampled import solve_sampled
    with pytest.raises(ValueError, match="no forest"):
        solve_sampled(_powerlaw_edges(64, 256), 64, fused=True,
                      forest=True)
