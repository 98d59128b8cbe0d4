"""The paper's core: Connected Components correctness + work-efficiency
properties, all variants, against the union-find oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr
from _graphgen import edge_lists
from _propcheck import given, settings, st

from repro.core.cc import (METHODS, WorkCounters, connected_components,
                           connected_components_hostloop,
                           connected_components_pallas, num_components)
from repro.core.segmentation import (adaptive_num_segments,
                                     plan_segmentation)
from repro.core.unionfind import connected_components_oracle
from repro.graphs import generators as G


def oracle_check(edges, n, **kw):
    want = connected_components_oracle(edges, n)
    for m in METHODS:
        got = connected_components(edges, n, method=m, **kw)
        np.testing.assert_array_equal(
            np.asarray(got.labels), want, err_msg=f"method={m}")
    return want


# --------------------------------------------------------------------------
# Deterministic structure tests
# --------------------------------------------------------------------------

def test_empty_graph():
    for m in METHODS:
        r = connected_components(np.zeros((0, 2)), 5, method=m)
        np.testing.assert_array_equal(np.asarray(r.labels),
                                      np.arange(5))


def test_zero_nodes():
    r = connected_components(np.zeros((0, 2)), 0)
    assert r.labels.shape == (0,)


def test_chain_star_cliques(rng):
    for g in (G.chain(17), G.star(9), G.disjoint_cliques(4, 5),
              G.grid_road(8, seed=1)):
        oracle_check(g.edges, g.num_nodes)


def test_self_loops_and_duplicates():
    edges = np.array([[0, 0], [1, 2], [1, 2], [2, 1], [3, 3]])
    want = oracle_check(edges, 5)
    assert num_components(want) == 4   # {0},{1,2},{3},{4}


def test_labels_are_canonical_minima(rng):
    g = G.rmat(8, 4, seed=3)
    r = connected_components(g.edges, g.num_nodes)
    labels = np.asarray(r.labels)
    for comp in np.unique(labels):
        members = np.where(labels == comp)[0]
        assert comp == members.min()


# --------------------------------------------------------------------------
# Property tests (hypothesis) — cases drawn from the shared _graphgen
# strategies so every suite fuzzes one distribution
# --------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(edge_lists)
def test_all_methods_match_oracle(case):
    n, edges = case
    edges = np.asarray(edges, np.int32).reshape(-1, 2)
    oracle_check(edges, n)


@settings(max_examples=15, deadline=None)
@given(edge_lists, st.integers(0, 2**31 - 1))
def test_permutation_equivariance(case, seed):
    """Relabeling vertices by a permutation perm maps component labels
    consistently: labels'(perm[v]) == min(perm[component of v])."""
    n, edges = case
    edges = np.asarray(edges, np.int32).reshape(-1, 2)
    perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
    base = np.asarray(connected_components(edges, n).labels)
    permuted = np.asarray(
        connected_components(perm[edges] if len(edges) else edges,
                             n).labels)
    for v in range(n):
        comp = np.where(base == base[v])[0]
        assert permuted[perm[v]] == perm[comp].min()


@settings(max_examples=15, deadline=None)
@given(edge_lists)
def test_idempotent_relabel(case):
    """Running CC on (v, label(v)) edges reproduces the same labels."""
    n, edges = case
    edges = np.asarray(edges, np.int32).reshape(-1, 2)
    labels = np.asarray(connected_components(edges, n).labels)
    star_edges = np.stack([np.arange(n, dtype=np.int32), labels], 1)
    again = np.asarray(connected_components(star_edges, n).labels)
    np.testing.assert_array_equal(labels, again)


@settings(max_examples=10, deadline=None)
@given(edge_lists, st.integers(1, 9))
def test_segment_count_does_not_change_answer(case, s):
    n, edges = case
    edges = np.asarray(edges, np.int32).reshape(-1, 2)
    want = connected_components_oracle(edges, n)
    got = connected_components(edges, n, method="adaptive",
                               num_segments=s)
    np.testing.assert_array_equal(np.asarray(got.labels), want)


# --------------------------------------------------------------------------
# Work-efficiency claims (the paper's currency)
# --------------------------------------------------------------------------

def test_adaptive_heuristic_value():
    assert adaptive_num_segments(58_000_000, 24_000_000) == 5   # usa-osm
    assert adaptive_num_segments(182_000_000, 2_000_000) == 182
    assert adaptive_num_segments(10, 1000) == 1


def test_segmentation_plan_covers_edges():
    plan = plan_segmentation(1000, 300)
    assert plan.num_segments == adaptive_num_segments(1000, 300)
    assert plan.num_segments * plan.segment_size >= 1000


def test_multijump_reduces_syncs_vs_soman():
    """Fig. 5 mechanism: Multi-Jump removes the per-sweep host
    convergence checks of the Soman baseline."""
    g = G.grid_road(24, seed=2)
    soman = connected_components(g.edges, g.num_nodes, method="soman")
    mj = connected_components(g.edges, g.num_nodes, method="multijump")
    assert int(mj.work.sync_rounds) < int(soman.work.sync_rounds)
    np.testing.assert_array_equal(np.asarray(soman.labels),
                                  np.asarray(mj.labels))


def test_atomic_hook_single_pass_on_easy_graph():
    """Atomic-Hook (root chase) connects a star in one hook round."""
    g = G.star(64)
    r = connected_components(g.edges, g.num_nodes, method="atomic_hook")
    assert int(r.work.hook_rounds) <= 2
    assert num_components(r.labels) == 1


def test_adaptive_fewer_jump_sweeps_than_multijump_on_road():
    """Intermediate compressions shorten chases on high-diameter
    graphs (the paper's road-map speedup mechanism)."""
    g = G.grid_road(40, extra_prob=0.0, seed=5)
    mj = connected_components(g.edges, g.num_nodes, method="multijump")
    ad = connected_components(g.edges, g.num_nodes, method="adaptive")
    assert int(ad.work.jump_sweeps) <= int(mj.work.jump_sweeps) * 2
    np.testing.assert_array_equal(np.asarray(mj.labels),
                                  np.asarray(ad.labels))


def test_hostloop_matches_and_counts_syncs():
    g = G.disjoint_cliques(3, 6, seed=0)
    labels, stats = connected_components_hostloop(
        g.edges, g.num_nodes, method="soman")
    np.testing.assert_array_equal(
        labels, connected_components_oracle(g.edges, g.num_nodes))
    assert stats["sync_rounds"] >= stats["hook_rounds"]


# --------------------------------------------------------------------------
# Pallas kernel backend
# --------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(edge_lists)
def test_pallas_backend_matches_oracle(case):
    n, edges = case
    edges = np.asarray(edges, np.int32).reshape(-1, 2)
    want = connected_components_oracle(edges, n)
    got = connected_components_pallas(edges, n, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_pallas_on_structured_graphs():
    for g in (G.grid_road(12, seed=7), G.rmat(7, 4, seed=7),
              G.disjoint_cliques(5, 4)):
        want = connected_components_oracle(g.edges, g.num_nodes)
        got = connected_components_pallas(g.edges, g.num_nodes,
                                          interpret=True)
        np.testing.assert_array_equal(np.asarray(got), want)


# --------------------------------------------------------------------------
# Fused Pallas backend (method="pallas_fused"): one launch per segment scan
# --------------------------------------------------------------------------

def _fused_oracle_matrix():
    """The propcheck oracle matrix: RMAT, grid-road, star, disconnected."""
    return (G.rmat(7, 4, seed=11), G.grid_road(10, seed=11), G.star(33),
            G.disjoint_cliques(4, 6, seed=11))


def test_pallas_fused_bit_identical_to_jnp_backend():
    """Acceptance: labels bit-identical to the jnp backend on the oracle
    matrix — and the work counters match too (same hooks, same sweeps)."""
    for g in _fused_oracle_matrix():
        want = connected_components_oracle(g.edges, g.num_nodes)
        jnp_res = connected_components(g.edges, g.num_nodes,
                                       method="adaptive")
        fused = connected_components(g.edges, g.num_nodes,
                                     method="pallas_fused")
        np.testing.assert_array_equal(np.asarray(fused.labels), want,
                                      err_msg=g.name)
        np.testing.assert_array_equal(np.asarray(fused.labels),
                                      np.asarray(jnp_res.labels),
                                      err_msg=g.name)
        for field, a, b in zip(WorkCounters._fields, fused.work,
                               jnp_res.work):
            assert int(a) == int(b), (g.name, field, int(a), int(b))


def _subjaxprs(v):
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)


def _pallas_call_sites(jaxpr) -> int:
    """Static pallas_call call sites in a jaxpr (recursing through
    jit/scan/while sub-jaxprs)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                n += _pallas_call_sites(sub)
    return n


def _launch_lower_bound(jaxpr) -> int:
    """Lower bound on runtime kernel launches: scan bodies multiply by
    their static trip count; while bodies count once (>= 1 trip for the
    compress loop, whose first sweep always runs)."""
    n = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            n += 1
        elif name == "scan":
            n += eqn.params["length"] * _launch_lower_bound(
                eqn.params["jaxpr"].jaxpr)
        else:
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    n += _launch_lower_bound(sub)
    return n


def test_fused_single_launch_vs_per_round_backend():
    """Acceptance: the fused path issues 1 pallas_call per segment scan
    where the per-round backend issued >= num_segments + jump_sweeps
    launches (one hook launch per segment + one multi_jump launch per
    compress sweep)."""
    import jax.numpy as jnp
    from repro.core import cc as cc_mod
    from repro.core import rounds
    from repro.graphs.device import as_device_graph
    from repro.kernels.cc_fused.ops import fused_segment_scan

    g = G.rmat(7, 4, seed=5)
    dg = as_device_graph(g)
    plan = dg.plan
    assert plan.num_segments > 1          # a real multi-segment scan
    segments = rounds.pad_and_segment(dg.edges, plan)
    counts = rounds.segment_true_counts(plan.num_edges, plan)
    pi0 = jnp.arange(g.num_nodes, dtype=jnp.int32)

    # fused: the WHOLE segment scan is ONE pallas_call
    fused_jaxpr = jax.make_jaxpr(
        lambda p, s, c: fused_segment_scan(p, s, c, interpret=True))(
            pi0, segments, counts).jaxpr
    assert _pallas_call_sites(fused_jaxpr) == 1

    # per-round backend: its hook launch is nested under the segment
    # scan (x num_segments at runtime) and its compress launch under the
    # sweep loop (x per-segment sweeps at runtime)
    old_jaxpr = jax.make_jaxpr(
        lambda e: cc_mod._cc_adaptive_pallas(
            e, num_nodes=g.num_nodes, num_segments=plan.num_segments,
            interpret=True))(dg.edges).jaxpr
    assert _launch_lower_bound(old_jaxpr) >= plan.num_segments + 1

    # scan-only sweep count from the fused kernel's counters (verified
    # bit-compatible with the jnp composition in the sibling test):
    # every segment compresses at least once, so the per-round backend's
    # num_segments hook launches + one launch per sweep dominate the
    # fused path's single launch many times over
    _, sweeps = fused_segment_scan(pi0, segments, counts, interpret=True)
    scan_sweeps = int(sweeps.sum())
    assert scan_sweeps >= plan.num_segments
    old_scan_launches = plan.num_segments + scan_sweeps
    assert old_scan_launches >= 2 * plan.num_segments > 2
    assert _pallas_call_sites(fused_jaxpr) < old_scan_launches


def test_fused_kernel_matches_ref_sweep_counts():
    """The fused kernel's per-segment sweep counters equal the jnp
    composition's exactly (work billing is bit-compatible)."""
    import jax.numpy as jnp
    from repro.core import rounds
    from repro.graphs.device import as_device_graph
    from repro.kernels.cc_fused.ops import fused_segment_scan
    from repro.kernels.cc_fused.ref import ref_segment_scan

    g = G.grid_road(9, seed=4)
    dg = as_device_graph(g)
    segments = rounds.pad_and_segment(dg.edges, dg.plan)
    counts = rounds.segment_true_counts(dg.plan.num_edges, dg.plan)
    pi0 = jnp.arange(g.num_nodes, dtype=jnp.int32)
    got_pi, sweeps = fused_segment_scan(pi0, segments, counts,
                                        interpret=True)
    ref_pi, ref_work = ref_segment_scan(pi0, segments, counts)
    np.testing.assert_array_equal(np.asarray(got_pi), np.asarray(ref_pi))
    assert int(sweeps.sum()) == int(ref_work.jump_sweeps)
