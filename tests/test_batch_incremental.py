"""Batched + incremental CC engines on the shared adaptive core:
bit-identity with the single-graph path, oracle agreement under
streaming insertions, and true-edge work billing."""
import numpy as np
import pytest

from _graphgen import dynamic_scripts, edge_list_batches, edges_array
from _propcheck import given, settings, st
from repro.core import rounds
from repro.core.batch import (bucket_shape, bucketize,
                              connected_components_batched)
from repro.core.cc import (connected_components,
                           connected_components_hostloop, num_components)
from repro.core.incremental import DynamicCC, IncrementalCC
from repro.core.segmentation import plan_segmentation
from repro.core.unionfind import (DynamicConnectivityOracle,
                                  connected_components_oracle)
from repro.graphs import generators as G


def mixed_graphs():
    return [
        G.chain(17),
        G.star(9),
        G.disjoint_cliques(4, 5),
        G.grid_road(8, seed=1),
        G.rmat(6, 4, seed=3),
        G.chain(2),
        # zero-edge graph: 5 isolated vertices
        G.Graph(edges=np.zeros((0, 2), np.int64), num_nodes=5),
    ]


# --------------------------------------------------------------------------
# Batched engine
# --------------------------------------------------------------------------

def test_batched_bit_identical_to_per_graph():
    graphs = mixed_graphs()
    batched = connected_components_batched(graphs)
    assert len(batched) == len(graphs)
    for g, res in zip(graphs, batched):
        single = connected_components(g.edges, g.num_nodes)
        want = connected_components_oracle(g.edges, g.num_nodes)
        np.testing.assert_array_equal(np.asarray(res.labels), want,
                                      err_msg=g.name)
        np.testing.assert_array_equal(np.asarray(res.labels),
                                      np.asarray(single.labels),
                                      err_msg=g.name)
        assert res.labels.shape == (g.num_nodes,)


def test_batched_accepts_edge_tuples():
    pairs = [(np.array([[0, 1], [1, 2]]), 4),
             (np.array([[0, 3]]), 5)]
    out = connected_components_batched(pairs)
    np.testing.assert_array_equal(np.asarray(out[0].labels), [0, 0, 0, 3])
    np.testing.assert_array_equal(np.asarray(out[1].labels),
                                  [0, 1, 2, 0, 4])


def test_bucketize_groups_by_padded_shape():
    graphs = [(np.zeros((3, 2)), 7), (np.zeros((4, 2)), 8),
              (np.zeros((100, 2)), 7)]
    batches = bucketize(graphs)
    shapes = {(b.num_nodes, b.edges.shape[1]) for b in batches}
    assert (8, 8) in shapes          # the two small graphs share a bucket
    assert (8, 128) in shapes
    sizes = sorted(b.edges.shape[0] for b in batches)
    assert sizes == [1, 2]
    assert bucket_shape(7, 3) == (8, 8)
    assert bucket_shape(9, 129) == (16, 256)


def test_batched_work_bills_true_edges_only():
    """hook_ops must be a multiple of E_true (one hook evaluation per
    true edge per round; ``lift_steps`` issues no chase) even though
    the bucket pads the edge list (padding is free)."""
    g = G.chain(17)           # 16 edges -> padded to 32 in its bucket
    res = connected_components_batched([g], lift_steps=2)[0]
    bill = g.num_edges
    assert int(res.work.hook_ops) % bill == 0
    assert int(res.work.hook_ops) >= bill
    # jump_ops bill the true |V| per sweep, not the padded bucket height
    assert int(res.work.jump_ops) == \
        g.num_nodes * int(res.work.jump_sweeps)


@settings(max_examples=8, deadline=None)
@given(edge_list_batches)
def test_batched_matches_oracle_property(cases):
    pairs = [(edges_array(e), n) for n, e in cases]
    out = connected_components_batched(pairs)
    for (edges, n), res in zip(pairs, out):
        want = connected_components_oracle(edges, n)
        np.testing.assert_array_equal(np.asarray(res.labels), want)


# --------------------------------------------------------------------------
# Incremental engine
# --------------------------------------------------------------------------

def test_incremental_matches_oracle_over_batches():
    n = 60
    rng = np.random.default_rng(7)
    inc = IncrementalCC(n)
    accumulated = np.zeros((0, 2), np.int32)
    for size in (5, 1, 17, 0, 9, 30):
        batch = rng.integers(0, n, (size, 2)).astype(np.int32)
        inc.insert(batch)
        accumulated = np.concatenate([accumulated, batch], axis=0)
        want = connected_components_oracle(accumulated, n)
        np.testing.assert_array_equal(np.asarray(inc.labels), want)
    assert inc.num_edges_inserted == accumulated.shape[0]
    assert inc.num_components() == num_components(want)


def test_incremental_noop_batch_costs_zero_hook_rounds():
    inc = IncrementalCC(10)
    inc.insert([[0, 1], [1, 2], [3, 4]])
    before = dict(inc.work)
    inc.insert([[0, 2], [2, 1], [4, 3]])   # all already connected
    assert inc.work["hook_rounds"] == before["hook_rounds"]
    assert inc.work["hook_ops"] == before["hook_ops"]
    np.testing.assert_array_equal(
        np.asarray(inc.labels),
        connected_components_oracle(
            np.array([[0, 1], [1, 2], [3, 4]]), 10))


def test_incremental_rejects_out_of_range():
    inc = IncrementalCC(4)
    with pytest.raises(ValueError):
        inc.insert([[0, 4]])
    with pytest.raises(ValueError):
        inc.insert([[-1, 2]])
    with pytest.raises(ValueError):
        inc.connected(0, 4)            # JAX would clamp, not error
    with pytest.raises(ValueError):
        inc.connected(-1, 0)


def test_incremental_work_cheaper_than_recompute():
    """The incremental absorb hooks only the new edges; a from-scratch
    adaptive run re-hooks the full accumulated edge list every batch."""
    n = 256
    rng = np.random.default_rng(3)
    batches = [rng.integers(0, n, (32, 2)).astype(np.int32)
               for _ in range(8)]
    inc = IncrementalCC(n)
    full_hook_ops = 0
    acc = np.zeros((0, 2), np.int32)
    for b in batches:
        inc.insert(b)
        acc = np.concatenate([acc, b], axis=0)
        full = connected_components(acc, n, method="adaptive")
        full_hook_ops += int(full.work.hook_ops)
    assert inc.work["hook_ops"] < full_hook_ops


def test_incremental_empty_graph():
    inc = IncrementalCC(0)
    inc.insert(np.zeros((0, 2), np.int32))
    assert inc.labels.shape == (0,)


# --------------------------------------------------------------------------
# Fully-dynamic engine (DESIGN.md §9): deletions
# --------------------------------------------------------------------------

def run_script(dyn: DynamicCC, script, n: int,
               check_every_step: bool = True):
    """Drive a dynamic engine and the host oracle through one
    interleaved insert/delete script, asserting label agreement."""
    oracle = DynamicConnectivityOracle(n)
    for op, batch in script:
        edges = edges_array(batch)
        if op == 0:
            dyn.insert(edges)
            oracle.insert(edges)
        else:
            dyn.delete(edges)
            oracle.delete(edges)
        if check_every_step:
            np.testing.assert_array_equal(np.asarray(dyn.labels),
                                          oracle.labels(),
                                          err_msg=str(script))
    return oracle


@settings(max_examples=10, deadline=None)
@given(dynamic_scripts())
def test_dynamic_matches_oracle_over_scripts(case):
    """Acceptance: after EVERY step of any interleaved insert/delete
    script, DynamicCC's labels equal a from-scratch union-find (and
    scipy) recompute over the surviving edge multiset."""
    n, script = case
    run_script(DynamicCC(n), script, n)


def test_dynamic_bridge_delete_splits_nonbridge_does_not():
    """The split detector: deleting a cycle edge keeps the partition
    (version unchanged, zero stale risk), deleting the bridge splits
    it (version ticks)."""
    from _graphgen import two_cliques_one_bridge
    n, edges, bridge = two_cliques_one_bridge(5, 4)
    dyn = DynamicCC(n)
    dyn.insert(edges)
    v0 = dyn.version
    dyn.delete([edges[0]])              # clique-internal: not a bridge
    assert dyn.version == v0
    assert dyn.num_components() == 1
    dyn.delete([bridge])                # the bridge: an actual split
    assert dyn.version == v0 + 1
    assert dyn.num_components() == 2
    assert not dyn.connected(0, n - 1)
    np.testing.assert_array_equal(
        np.asarray(dyn.labels),
        connected_components_oracle(
            edges_array([e for e in edges
                         if e not in (edges[0], bridge)]), n))


def test_dynamic_absent_delete_is_free_and_silent():
    """Deleting absent edges (or double-deleting) retires nothing:
    zero hook rounds, zero sweeps, no version tick."""
    dyn = DynamicCC(10)
    dyn.insert([[0, 1], [1, 2], [3, 4]])
    dyn.delete([[3, 4]])
    v0, before = dyn.version, dict(dyn.work)
    dyn.delete([[5, 6], [3, 4], [7, 7]])     # absent + double + loop
    after = dyn.work
    assert dyn.version == v0
    assert after["hook_rounds"] == before["hook_rounds"]
    assert after["jump_sweeps"] == before["jump_sweeps"]
    assert after["hook_ops"] == before["hook_ops"]
    assert dyn.num_edges_deleted == 1


def test_dynamic_delete_retires_every_copy_orientation_blind():
    dyn = DynamicCC(6)
    dyn.insert([[0, 1], [1, 0], [0, 1], [2, 3]])
    dyn.delete([[1, 0]])                # kills all three copies
    assert dyn.num_edges_deleted == 3
    assert dyn.num_edges_alive == 1
    assert not dyn.connected(0, 1)


def test_dynamic_scoped_recompute_cheaper_than_full():
    """The paper's currency: a bridge deletion inside ONE of many
    components re-hooks only that component's survivors — hook_ops
    must undercut a from-scratch recompute of the whole graph."""
    g = G.disjoint_cliques(6, 8, seed=0)      # 6 components, 28 edges each
    edges = np.asarray(g.edges, np.int32)
    dyn = DynamicCC(g.num_nodes)
    dyn.insert(edges)
    base = dyn.work["hook_ops"]
    dyn.delete([edges[0]])                    # one clique-internal edge
    scoped_ops = dyn.work["hook_ops"] - base
    oracle = DynamicConnectivityOracle(g.num_nodes)
    oracle.insert(edges)
    oracle.delete([edges[0]])
    full = connected_components(edges_array(oracle.alive()),
                                g.num_nodes, method="adaptive")
    np.testing.assert_array_equal(np.asarray(dyn.labels), oracle.labels())
    assert 0 < scoped_ops < int(full.work.hook_ops), \
        (scoped_ops, int(full.work.hook_ops))


def test_dynamic_forest_compaction_remaps_parent_eidx():
    """ISSUE 9 satellite: ``DynamicCC.compact()`` packs the tombstone
    log AND remaps the maintained forest's ``parent_eidx`` through the
    compaction permutation in one step — afterwards every recorded
    pointer still names the ALIVE log row holding its parent edge, and
    the forest route keeps working over the renumbered log."""
    from repro.graphs.device import DeviceGraph

    rng = np.random.default_rng(11)
    n = 32
    edges = rng.integers(0, n, (48, 2)).astype(np.int32)
    dyn = DynamicCC(n)
    oracle = DynamicConnectivityOracle(n)
    dyn.insert(edges)
    oracle.insert(edges)
    assert dyn.forest_valid                  # inserts never stale it
    kills = edges[::3].copy()
    dyn.delete_graph_forest(DeviceGraph.from_edges(kills, n))
    oracle.delete(kills)
    np.testing.assert_array_equal(np.asarray(dyn.labels), oracle.labels())

    labels_before = np.asarray(dyn.labels).copy()
    rows_before = dyn.log.rows
    dyn.compact()
    assert dyn.log.rows < rows_before        # tombstones dropped
    assert dyn.forest_valid
    np.testing.assert_array_equal(np.asarray(dyn.labels), labels_before)
    parents = np.asarray(dyn.forest[0])
    eidx = np.asarray(dyn.forest[1])
    log_e = np.asarray(dyn.log.edges)
    log_a = np.asarray(dyn.log.alive)
    recorded = np.flatnonzero(parents[:, 0] >= 0)
    assert recorded.size > 0
    for r in recorded:
        k = int(eidx[r])
        assert 0 <= k < dyn.log.rows, (int(r), k)
        assert bool(log_a[k]), (int(r), k)
        assert (sorted(map(int, log_e[k]))
                == sorted(map(int, parents[r]))), (int(r), k)
    # the forest keeps working post-compaction: kill a live tree edge
    tree0 = [sorted(map(int, parents[recorded[0]]))]
    dyn.delete_graph_forest(DeviceGraph.from_edges(tree0, n))
    oracle.delete(tree0)
    np.testing.assert_array_equal(np.asarray(dyn.labels), oracle.labels())


def test_dynamic_plain_delete_stales_forest_lazy_rebuild():
    """A plain (non-forest) delete leaves the maintained forest stale;
    the next forest-route call lazily rebuilds it exactly once (counted
    in ``delete_route_counts()['rebuild']``) and lands on the same
    labels as the oracle."""
    from repro.graphs.device import DeviceGraph

    n = 16
    ring = [[i, (i + 1) % n] for i in range(n)]
    dyn = DynamicCC(n)
    oracle = DynamicConnectivityOracle(n)
    dyn.insert(ring)
    oracle.insert(ring)
    dyn.delete([[0, 1]])                     # plain route: forest stales
    oracle.delete([[0, 1]])
    assert not dyn.forest_valid
    dyn.delete_graph_forest(DeviceGraph.from_edges([[4, 5]], n))
    oracle.delete([[4, 5]])
    assert dyn.forest_valid and dyn.forest_rebuilds == 1
    assert dyn.delete_route_counts()["rebuild"] == 1
    np.testing.assert_array_equal(np.asarray(dyn.labels), oracle.labels())


def test_dynamic_fused_scan_bit_identical():
    """scan_method='pallas_fused' runs the scoped recompute through the
    fused kernel: labels AND work counters bit-identical to jnp."""
    rng = np.random.default_rng(5)
    n = 48
    edges = rng.integers(0, n, (70, 2))
    kills = edges[rng.integers(0, 70, 15)]
    a = DynamicCC(n)
    b = DynamicCC(n, scan_method="pallas_fused")
    for dyn in (a, b):
        dyn.insert(edges)
        dyn.delete(kills)
    np.testing.assert_array_equal(np.asarray(a.labels),
                                  np.asarray(b.labels))
    assert a.work == b.work


def test_dynamic_validation_and_degenerate():
    dyn = DynamicCC(4)
    with pytest.raises(ValueError):
        dyn.delete([[0, 4]])
    with pytest.raises(ValueError):
        dyn.delete([[-1, 0]])
    with pytest.raises(ValueError):
        DynamicCC(4, scan_method="nope")
    dyn.delete(np.zeros((0, 2), np.int32))   # empty batch: no-op
    dyn.delete([[0, 1]])                     # delete before any insert
    assert dyn.num_edges_deleted == 0
    empty = DynamicCC(0)
    empty.insert(np.zeros((0, 2)))
    empty.delete(np.zeros((0, 2)))
    assert empty.labels.shape == (0,)


# --------------------------------------------------------------------------
# Shared rounds core: billing + API contracts
# --------------------------------------------------------------------------

def test_segment_true_counts_sum_to_true_edges():
    plan = plan_segmentation(100, 30)        # pads 100 edges over s segs
    counts = np.asarray(rounds.segment_true_counts(100, plan))
    assert counts.shape == (plan.num_segments,)
    assert counts.sum() == 100
    assert counts.max() <= plan.segment_size


def test_adaptive_hook_ops_bill_true_edges():
    """Single-graph adaptive billing: with padding present, hook_ops is
    (1 + cleanup_rounds) * E_true — never a function of the padded
    segment size, nor of ``lift_steps`` (every hook reads flat labels
    and issues no chase)."""
    g = G.chain(17)                          # 16 edges
    lift, segs = 2, 3                        # seg=6 -> 18 padded slots
    plan = plan_segmentation(g.num_edges, g.num_nodes, segs)
    assert plan.padded_edges > g.num_edges   # the scenario under test
    res = connected_components(g.edges, g.num_nodes, method="adaptive",
                               num_segments=segs, lift_steps=lift)
    cleanup = int(res.work.hook_rounds) - plan.num_segments
    assert cleanup >= 0
    # the old (buggy) padded billing would have charged
    # plan.segment_size per scan segment instead of the true count
    assert int(res.work.hook_ops) == (1 + cleanup) * g.num_edges


def test_hostloop_unknown_method_raises():
    g = G.chain(5)
    with pytest.raises(ValueError, match="unknown method"):
        connected_components_hostloop(g.edges, g.num_nodes,
                                      method="adaptive")
