"""Connectivity query service (DESIGN.md §7): on-device query kernels
vs NumPy oracles across every generator family, policy selection,
autotune-cache persistence, registry version/invalidation safety, and
the slot-based service engine."""
import collections
import json

import numpy as np
import pytest

from _graphgen import (dynamic_scripts, edges_array,
                       graph_with_query_pairs, insert_batch_cases,
                       two_cliques_one_bridge)
from _propcheck import given, settings, st
from repro.connectivity import policy, queries
from repro.connectivity.registry import GraphRegistry
from repro.connectivity.service import ConnectivityService
from repro.core.batch import next_pow2, pad_rows_pow2
from repro.core.cc import connected_components, num_components
from repro.core.incremental import IncrementalCC
from repro.core.unionfind import connected_components_oracle
from repro.graphs import generators as G


def generator_family_graphs():
    """One graph per generators family (the kernel-oracle matrix)."""
    return [
        G.chain(23),
        G.star(11),
        G.disjoint_cliques(4, 5),
        G.grid_road(7, seed=1),
        G.rmat(6, 4, seed=3),
        G.random_uniform(40, 70, seed=2),
        G.molecule_batch(3, 7, 9, seed=4),
        G.table1_scaled("usa-osm", scale=1 / 4096, seed=5),
        # degenerate: no edges / single vertex
        G.Graph(edges=np.zeros((0, 2), np.int64), num_nodes=6),
        G.Graph(edges=np.zeros((0, 2), np.int64), num_nodes=1),
    ]


def oracle_labels(g):
    return connected_components_oracle(g.edges, g.num_nodes)


# --------------------------------------------------------------------------
# Query kernels vs NumPy oracles
# --------------------------------------------------------------------------

def test_query_kernels_match_numpy_oracle_across_families():
    rng = np.random.default_rng(0)
    for g in generator_family_graphs():
        labels = oracle_labels(g)
        n = g.num_nodes
        # count_components == np.unique
        want_count = int(np.unique(labels).size) if n else 0
        assert int(queries.count_components(labels)) == want_count, g.name
        if n == 0:
            continue
        # same_component on a random pair batch
        pairs = rng.integers(0, n, (17, 2))
        got = np.asarray(queries.same_component(labels, pairs))
        want = labels[pairs[:, 0]] == labels[pairs[:, 1]]
        np.testing.assert_array_equal(got, want, err_msg=g.name)
        # component_size against a Counter census
        census = collections.Counter(labels.tolist())
        verts = rng.integers(0, n, (13,))
        got_sz = np.asarray(queries.component_size(labels, verts))
        want_sz = np.array([census[labels[v]] for v in verts])
        np.testing.assert_array_equal(got_sz, want_sz, err_msg=g.name)
        # component_sizes for every vertex
        got_all = np.asarray(queries.component_sizes(labels))
        want_all = np.array([census[l] for l in labels.tolist()])
        np.testing.assert_array_equal(got_all, want_all, err_msg=g.name)
        # histogram: one count per component in bin floor(log2 size)
        hist = np.asarray(queries.component_histogram(labels))
        want_h = np.zeros_like(hist)
        for size in census.values():
            want_h[int(np.floor(np.log2(size)))] += 1
        np.testing.assert_array_equal(hist, want_h, err_msg=g.name)
        assert hist.sum() == want_count, g.name


@settings(max_examples=10, deadline=None)
@given(graph_with_query_pairs())
def test_query_kernels_property(case):
    """Any random (graph, query batch): kernels == NumPy on the oracle
    labels, and padding to the shared pow2 buckets never changes the
    sliced answers."""
    n, edges, qpairs = case
    edges = edges_array(edges)
    qpairs = edges_array(qpairs)
    labels = connected_components_oracle(edges, n)
    got = np.asarray(queries.same_component(labels, qpairs))
    want = labels[qpairs[:, 0]] == labels[qpairs[:, 1]]
    np.testing.assert_array_equal(got, want)
    padded = pad_rows_pow2(qpairs)
    assert padded.shape[0] == next_pow2(max(qpairs.shape[0], 8))
    np.testing.assert_array_equal(
        np.asarray(queries.same_component(labels, padded))[: len(qpairs)],
        want)
    assert int(queries.count_components(labels)) == np.unique(labels).size
    sizes = np.asarray(queries.component_size(labels, qpairs[:, 0]))
    census = collections.Counter(labels.tolist())
    np.testing.assert_array_equal(
        sizes, [census[labels[v]] for v in qpairs[:, 0]])


def test_floor_log2_exact_at_int32_boundaries():
    """The histogram binning must be exact where a float32 cast is not:
    2^k - 1 above 2^24 rounds UP to 2^k under float32."""
    ks = [1, 2, 15, 16, 17, 23, 24, 25, 26, 30]
    n = np.array([x for k in ks for x in ((1 << k) - 1, 1 << k,
                                          (1 << k) + 1)], np.int32)
    got = np.asarray(queries._floor_log2(n))
    want = np.floor(np.log2(n.astype(np.float64))).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_num_components_wrappers_on_device():
    g = G.disjoint_cliques(3, 4)
    labels = connected_components(g.edges, g.num_nodes).labels
    assert num_components(labels) == 3
    inc = IncrementalCC(g.num_nodes)
    inc.insert(g.edges)
    assert inc.num_components() == 3
    assert num_components(np.array([], np.int32)) == 0


# --------------------------------------------------------------------------
# Policy: heuristic, auto method, autotune cache
# --------------------------------------------------------------------------

def test_policy_heuristic_regimes():
    # sparse (s <= 1 segment): atomic_hook
    assert policy.select_method(100, 20) == "atomic_hook"
    # mid-density: the paper's adaptive segmentation
    assert policy.select_method(100, 400) == "adaptive"
    # near-clique: labelprop
    assert policy.select_method(12, 66) == "labelprop"
    # small delta over existing state: incremental absorb
    assert policy.select_method(100, 400, delta_edges=20) == \
        policy.INCREMENTAL_ABSORB
    # bulk load (delta dominates): a static method
    assert policy.select_method(100, 10, delta_edges=500) in \
        policy.STATIC_METHODS


def test_policy_delete_routes_on_tree_edge_ratio():
    """ISSUE 9: the delete-side heuristic splits on the tree-edge-ratio
    feature — dense graphs (most deletes provably non-tree) take the
    maintained-forest route, road-like |E| ~ |V| graphs stay on the
    plain scoped recompute, and bulk drops still fall through to a
    static rebuild over the survivors."""
    fresh = policy.AutotuneCache()          # no measured overrides
    # dense regime: ratio = 99/1000 << FOREST_TREE_RATIO
    assert policy.select_method(100, 1000, delta_deletes=10,
                                cache=fresh) == \
        policy.DYNAMIC_DELETE_FOREST
    # road-like regime: ratio ~ 1 -> nearly every delete IS a tree edge
    assert policy.select_method(100, 99, delta_deletes=5,
                                cache=fresh) == policy.DYNAMIC_DELETE
    # bulk drop falls through to a static rebuild either way
    assert policy.select_method(100, 1000, delta_deletes=900,
                                cache=fresh) in policy.STATIC_METHODS
    f = policy.extract_features(100, 1000, delta_deletes=10)
    assert f.tree_edge_ratio == pytest.approx(99 / 1000)
    assert policy.extract_features(100, 99).tree_edge_ratio == \
        pytest.approx(1.0)


def test_method_auto_matches_oracle_across_families():
    for g in generator_family_graphs():
        res = connected_components(g.edges, g.num_nodes, method="auto")
        np.testing.assert_array_equal(
            np.asarray(res.labels), oracle_labels(g), err_msg=g.name)


def test_autotune_cache_roundtrip_and_override(tmp_path):
    path = str(tmp_path / "autotune.json")
    cache = policy.AutotuneCache(path)
    g = G.rmat(5, 4, seed=0)
    won = cache.measure(g.edges, g.num_nodes)
    assert won in policy.AUTOTUNE_METHODS
    # measured winner overrides the heuristic for the whole bucket
    assert policy.select_method(g.num_nodes, g.num_edges,
                                cache=cache) == won
    # persisted JSON reloads into a fresh cache
    reloaded = policy.AutotuneCache(path)
    assert reloaded.lookup(g.num_nodes, g.num_edges) == won
    payload = json.loads(open(path).read())
    assert payload["version"] == policy.CACHE_FORMAT_VERSION
    (entry,) = payload["entries"].values()
    assert entry["method"] == won and entry["ms"] > 0
    # a different bucket misses
    assert reloaded.lookup(4 * g.num_nodes, 64 * g.num_edges) is None


def test_autotune_candidates_need_no_pallas_kernel():
    """No CC Pallas kernel lowers for a TPU, so no autotune candidate
    may reach one: every traced program of every candidate backend is
    free of pallas_call."""
    import repro.api.backends  # noqa: F401 — registers trace entries
    from repro.analysis.jaxpr_utils import trace, walk_eqns
    from repro.api.registry import trace_entries
    for method in policy.AUTOTUNE_METHODS:
        entries = [e for e in trace_entries() if e.backend == method]
        assert entries, method
        for entry in entries:
            traced = trace(entry, (256, 1024))
            assert traced.failure is None, (entry.name, traced.failure)
            prims = {eqn.primitive.name for eqn in walk_eqns(traced.jaxpr)}
            assert "pallas_call" not in prims, entry.name


def test_autotune_cached_pallas_winner_is_dropped(tmp_path):
    """A cache file naming a winner ``measure`` can no longer record
    (written before the Pallas backend left the candidate set) must not
    route anything to it."""
    path = tmp_path / "autotune.json"
    key = policy.AutotuneCache.key(1000, 4000)
    path.write_text(json.dumps({
        "version": policy.CACHE_FORMAT_VERSION,
        "entries": {key: {"method": "pallas_fused", "ms": 1.0}}}))
    cache = policy.AutotuneCache(str(path))
    assert cache.lookup(1000, 4000) is None
    assert policy.select_method(1000, 4000, cache=cache) == "adaptive"


@pytest.mark.parametrize("interpret", [True, False])
def test_measure_candidates_ignore_interpret_mode(monkeypatch, interpret):
    """``measure`` times the same candidate set whether or not Pallas
    kernels would run interpreted on this host."""
    import jax.numpy as jnp

    import repro.api
    import repro.kernels
    timed = []

    class _Result:
        labels = jnp.zeros((4,), jnp.int32)

    def fake_solve(edges, num_nodes, method):
        timed.append(method)
        return _Result()

    monkeypatch.setattr(repro.kernels, "default_interpret",
                        lambda: interpret)
    monkeypatch.setattr(repro.api, "solve", fake_solve)
    policy.AutotuneCache().measure(np.zeros((3, 2), np.int32), 4, reps=1)
    assert tuple(dict.fromkeys(timed)) == policy.AUTOTUNE_METHODS


def test_autotune_save_is_atomic_and_collision_free(tmp_path):
    """Two caches (standing in for two concurrent ConnectivityService
    processes) interleave saves to one path: every save goes through a
    process-unique temp file + atomic rename, so the JSON on disk is
    complete and parseable after every interleaving, and no stray temp
    files survive."""
    path = str(tmp_path / "shared" / "autotune.json")
    a = policy.AutotuneCache(path)
    b = policy.AutotuneCache(path)
    for i in range(4):
        a.record(64 << i, 256 << i, "adaptive", 1.0 + i)
        payload = json.loads(open(path).read())
        assert payload["version"] == policy.CACHE_FORMAT_VERSION
        b.record(96 << i, 512 << i, "atomic_hook", 2.0 + i)
        payload = json.loads(open(path).read())
        assert payload["version"] == policy.CACHE_FORMAT_VERSION
    leftovers = [p for p in (tmp_path / "shared").iterdir()
                 if p.name != "autotune.json"]
    assert leftovers == []
    # last writer wins wholesale, and its table is intact
    assert policy.AutotuneCache(path).entries == b.entries


# --------------------------------------------------------------------------
# Registry: versioning + invalidation safety
# --------------------------------------------------------------------------

def test_registry_lifecycle_and_validation():
    reg = GraphRegistry()
    reg.create("a", 10)
    with pytest.raises(ValueError, match="already registered"):
        reg.create("a", 10)
    with pytest.raises(KeyError, match="unknown tenant"):
        reg.get("b")
    with pytest.raises(ValueError, match="out of range"):
        reg.insert("a", [[0, 10]])
    with pytest.raises(ValueError, match="out of range"):
        reg.same_component("a", [[0, 10]])
    reg.drop("a")
    assert reg.names() == []


def test_registry_version_ticks_only_on_merge():
    reg = GraphRegistry()
    reg.create("g", 8)
    v0 = reg.version("g")
    reg.insert("g", [[0, 1], [2, 3]])
    v1 = reg.version("g")
    assert v1 > v0
    # already-connected batch: no merge, version unchanged, cache warm
    assert bool(reg.same_component("g", [[0, 1]])[0])
    reg.insert("g", [[1, 0], [3, 2]])
    assert reg.version("g") == v1
    t = reg.get("g")
    hits_before = t.stats.cache_hits
    assert bool(reg.same_component("g", [[0, 1]])[0])
    assert t.stats.cache_hits == hits_before + 1
    # a merging batch ticks the version and invalidates
    reg.insert("g", [[1, 2]])
    assert reg.version("g") > v1
    assert bool(reg.same_component("g", [[0, 3]])[0])


@settings(max_examples=6, deadline=None)
@given(insert_batch_cases())
def test_registry_never_serves_stale_answers_property(case):
    """The invalidation property from the ISSUE: across any insert-batch
    sequence, a cached ``same_component`` answer is never stale — every
    response equals the union-find oracle on the edges inserted so far,
    with the SAME query batch re-asked every round to maximize cache
    pressure."""
    n, batches = case
    reg = GraphRegistry()
    reg.create("t", n)
    rng = np.random.default_rng(n)
    fixed_pairs = rng.integers(0, n, (9, 2))      # re-asked every round
    acc = np.zeros((0, 2), np.int32)
    for batch in batches:
        edges = np.asarray(batch, np.int32).reshape(-1, 2)
        reg.insert("t", edges)
        acc = np.concatenate([acc, edges], axis=0)
        labels = connected_components_oracle(acc, n)
        got = np.asarray(reg.same_component("t", fixed_pairs))
        want = labels[fixed_pairs[:, 0]] == labels[fixed_pairs[:, 1]]
        np.testing.assert_array_equal(got, want)
        assert reg.count_components("t") == np.unique(labels).size
        # and the full label state stays at the oracle fixed point
        np.testing.assert_array_equal(np.asarray(reg.get("t").labels),
                                      labels)


@settings(max_examples=8, deadline=None)
@given(dynamic_scripts())
def test_registry_stale_free_across_splits_property(case):
    """Acceptance (ISSUE 4): across any interleaved insert/delete
    script, cached answers are never stale — every ``same_component`` /
    ``component_size`` / ``count_components`` response equals the
    union-find oracle over the surviving edges, the SAME query batch is
    re-asked after every mutation to maximize cache pressure, and the
    version (= invalidation) ticks EXACTLY when the canonical
    partition changed (merge or split) — never for a batch that left
    connectivity alone."""
    from repro.core.unionfind import DynamicConnectivityOracle
    n, script = case
    reg = GraphRegistry()
    reg.create("t", n)
    oracle = DynamicConnectivityOracle(n)
    rng = np.random.default_rng(n)
    fixed_pairs = rng.integers(0, n, (7, 2))
    prev_labels = connected_components_oracle(
        np.zeros((0, 2), np.int32), n)
    for op, batch in script:
        edges = edges_array(batch)
        v_before = reg.version("t")
        if op == 0:
            reg.insert("t", edges)
            oracle.insert(edges)
        else:
            reg.delete("t", edges)
            oracle.delete(edges)
        labels = oracle.labels()
        changed = not np.array_equal(labels, prev_labels)
        # invalidation precision: the version moved iff the partition
        # did (insert merges and delete splits both count; anything
        # else keeps every cached answer warm)
        assert reg.version("t") - v_before == int(changed), str(script)
        got = np.asarray(reg.same_component("t", fixed_pairs))
        want = labels[fixed_pairs[:, 0]] == labels[fixed_pairs[:, 1]]
        np.testing.assert_array_equal(got, want, err_msg=str(script))
        assert reg.count_components("t") == np.unique(labels).size
        sizes = np.asarray(
            reg.component_size("t", fixed_pairs[:, 0]))
        want_sizes = np.asarray(
            [np.sum(labels == labels[v]) for v in fixed_pairs[:, 0]])
        np.testing.assert_array_equal(sizes, want_sizes)
        np.testing.assert_array_equal(np.asarray(reg.get("t").labels),
                                      labels, err_msg=str(script))
        prev_labels = labels


def test_registry_version_ticks_only_on_actual_split():
    """The delete-side mirror of the merge-tick test: a non-bridge
    delete keeps every cached answer warm; a bridge delete invalidates
    exactly once."""
    n, edges, bridge = two_cliques_one_bridge(4, 4)
    reg = GraphRegistry()
    reg.create("g", n)
    reg.insert("g", edges)
    v0 = reg.version("g")
    assert bool(reg.same_component("g", [[0, n - 1]])[0])
    reg.delete("g", [edges[0]])          # cycle edge: partition intact
    assert reg.version("g") == v0
    t = reg.get("g")
    hits = t.stats.cache_hits
    assert bool(reg.same_component("g", [[0, n - 1]])[0])
    assert t.stats.cache_hits == hits + 1      # cache stayed warm
    reg.delete("g", [bridge])            # split: one tick, cache cold
    assert reg.version("g") == v0 + 1
    assert not bool(reg.same_component("g", [[0, n - 1]])[0])
    assert t.stats.scoped_deletes == 2


def test_registry_policy_routes_bulk_then_absorb():
    g = G.rmat(6, 6, seed=2)
    reg = GraphRegistry()
    t = reg.create("g", g.num_nodes)
    edges = np.asarray(g.edges)
    reg.insert("g", edges[: edges.shape[0] - 16])     # bulk load
    assert t.last_method in policy.STATIC_METHODS
    assert t.stats.rebuilds == 1
    reg.insert("g", edges[edges.shape[0] - 16:])      # small delta
    assert t.last_method == policy.INCREMENTAL_ABSORB
    assert t.stats.absorbs == 1
    np.testing.assert_array_equal(np.asarray(t.labels), oracle_labels(g))


# --------------------------------------------------------------------------
# Service engine
# --------------------------------------------------------------------------

def test_service_mixed_stream_matches_oracle_and_microbatches():
    tenants = {"social": G.rmat(5, 5, seed=1),
               "road": G.grid_road(6, seed=2)}
    reg = GraphRegistry()
    svc = ConnectivityService(reg, slots=64)
    for name, g in tenants.items():
        reg.create(name, g.num_nodes)
    rng = np.random.default_rng(0)
    n_rounds = 3
    splits = {name: np.array_split(rng.permutation(g.num_edges), n_rounds)
              for name, g in tenants.items()}
    acc = {name: np.zeros((0, 2), np.int64) for name in tenants}
    for rnd in range(n_rounds):
        expected = {}
        for name, g in tenants.items():
            edges = np.asarray(g.edges)[splits[name][rnd]]
            svc.submit_insert(name, edges)
            acc[name] = np.concatenate([acc[name], edges], axis=0)
            for _ in range(3):      # 3 requests -> ONE kernel call
                pairs = rng.integers(0, g.num_nodes, (11, 2))
                uid = svc.submit_query(name, "same_component", pairs)
                expected[uid] = (name, pairs)
            svc.submit_query(name, "count_components")
        calls_before = svc.stats["query_calls"]
        finished = {r.uid: r for r in svc.run()}
        # per tick: 2 tenants x (1 same_component microbatch + 1 count)
        assert svc.stats["query_calls"] == calls_before + 4
        for uid, (name, pairs) in expected.items():
            labels = connected_components_oracle(acc[name],
                                                 tenants[name].num_nodes)
            want = labels[pairs[:, 0]] == labels[pairs[:, 1]]
            np.testing.assert_array_equal(
                np.asarray(finished[uid].result), want)
    assert svc.stats["inserts_absorbed"] == 2 * n_rounds
    assert svc.stats["insert_calls"] == 2 * n_rounds
    assert svc.stats["recomputes_avoided"] == svc.stats["queries_served"]
    assert svc.stats["errors"] == 0


def test_service_coalesces_inserts_per_tenant():
    reg = GraphRegistry()
    reg.create("g", 12)
    svc = ConnectivityService(reg, slots=8)
    for e in ([[0, 1]], [[1, 2]], [[3, 4]]):
        svc.submit_insert("g", e)
    svc.run()
    # three insert requests -> one coalesced registry insert
    assert svc.stats["inserts_absorbed"] == 3
    assert svc.stats["insert_calls"] == 1
    assert reg.get("g").stats.inserts == 1
    assert bool(reg.same_component("g", [[0, 2]])[0])


def test_service_errors_do_not_poison_the_tick():
    reg = GraphRegistry()
    reg.create("g", 8)
    svc = ConnectivityService(reg, slots=8)
    bad = svc.submit_query("nope", "count_components")
    ok = svc.submit_query("g", "count_components")
    finished = {r.uid: r for r in svc.run()}
    assert finished[bad].error and finished[bad].done
    assert finished[ok].result == 8 and finished[ok].error is None
    with pytest.raises(ValueError, match="unknown kind"):
        svc.submit("g", "frobnicate")
    with pytest.raises(ValueError, match="unknown query kind"):
        svc.submit_query("g", "insert")
    with pytest.raises(ValueError, match="requires a payload"):
        svc.submit_query("g", "same_component")
    with pytest.raises(ValueError, match="requires a payload"):
        svc.submit("g", "insert")


def test_service_steady_state_has_no_host_transfers():
    """Acceptance (ISSUE 3 + 4): the steady-state service mutation
    paths — device-side coalescing, policy feature extraction from
    DeviceGraph metadata, the on-device merge tick (insert), AND the
    tombstone + scoped-recompute + split tick (delete) — perform ZERO
    implicit host transfers, including a mixed insert+delete tick.
    ``jax.transfer_guard("disallow")`` turns any
    ``bool(device_scalar)``, ``np.concatenate`` fallback, or
    host-scalar jit argument into a hard error."""
    import jax
    from repro.connectivity.service import ConnectivityService
    from repro.core.unionfind import DynamicConnectivityOracle
    from repro.graphs.device import DeviceGraph

    g = G.grid_road(8, extra_prob=0.0, seed=0)
    n, edges = g.num_nodes, np.asarray(g.edges, np.int32)
    reg = GraphRegistry()
    svc = ConnectivityService(reg, slots=16)
    reg.create("t", n)
    # bulk load, then warm every jit entry the steady state will hit
    # (same coalesced shapes as the guarded ticks below)
    svc.submit_insert("t", edges[:-40])
    svc.run()
    svc.submit_insert("t", edges[-40:-30])
    svc.submit_insert("t", edges[-30:-20])
    svc.run()
    svc.submit_delete("t", edges[:5])
    svc.submit_delete("t", edges[5:10])
    svc.run()
    assert reg.get("t").last_method in policy.DELETE_METHODS

    # steady state: same shapes again. Admission (submit) is ingress
    # and may sync for validation; the TICK — coalescing, policy
    # features, absorb, tombstone, scoped recompute, version ticks —
    # must not transfer at all.
    svc.submit_insert("t", DeviceGraph.from_edges(edges[-20:-10], n))
    svc.submit_insert("t", DeviceGraph.from_edges(edges[-10:], n))
    with jax.transfer_guard("disallow"):
        finished = svc.run()
    assert [r.error for r in finished] == [None, None]

    # steady-state DELETE tick (same coalesced shape as the warm one)
    svc.submit_delete("t", DeviceGraph.from_edges(edges[10:15], n))
    svc.submit_delete("t", DeviceGraph.from_edges(edges[15:20], n))
    with jax.transfer_guard("disallow"):
        finished = svc.run()
    assert [r.error for r in finished] == [None, None]

    # MIXED insert+delete tick: re-insert edges deleted above and
    # delete others, one tick, still transfer-free
    svc.submit_insert("t", DeviceGraph.from_edges(edges[:5], n))
    svc.submit_insert("t", DeviceGraph.from_edges(edges[5:10], n))
    svc.submit_delete("t", DeviceGraph.from_edges(edges[20:25], n))
    svc.submit_delete("t", DeviceGraph.from_edges(edges[25:30], n))
    with jax.transfer_guard("disallow"):
        finished = svc.run()
    assert [r.error for r in finished] == [None] * 4
    assert all(r.done for r in finished)
    # results ride as device scalars (the tick never synced them)
    assert all(isinstance(r.result, jax.Array) for r in finished)

    # the guarded mutations really landed: answers match the dynamic
    # oracle replaying the exact mutation sequence
    oracle = DynamicConnectivityOracle(n)
    oracle.insert(edges[:-20])
    oracle.delete(edges[:10])
    oracle.insert(edges[-20:])
    oracle.delete(edges[10:20])
    oracle.insert(edges[:10])
    oracle.delete(edges[20:30])
    labels = oracle.labels()
    pairs = np.stack([np.arange(n, dtype=np.int32),
                      np.zeros(n, np.int32)], axis=1)
    got = np.asarray(reg.same_component("t", pairs))
    np.testing.assert_array_equal(got, labels == labels[0])
    np.testing.assert_array_equal(np.asarray(reg.get("t").labels), labels)


def test_service_interleaved_insert_delete_matches_oracle():
    """Mixed insert/delete/query traffic through the slot engine: every
    answer equals the dynamic oracle over the surviving edges; deletes
    coalesce per tenant per tick (one device call for k requests)."""
    from repro.core.unionfind import DynamicConnectivityOracle
    g = G.rmat(5, 5, seed=9)
    n = g.num_nodes
    edges = np.asarray(g.edges, np.int32)
    reg = GraphRegistry()
    svc = ConnectivityService(reg, slots=64)
    reg.create("t", n)
    oracle = DynamicConnectivityOracle(n)
    rng = np.random.default_rng(1)
    third = edges.shape[0] // 3
    chunks = (edges[:third], edges[third:2 * third], edges[2 * third:])
    for rnd, chunk in enumerate(chunks):
        svc.submit_insert("t", chunk)
        oracle.insert(chunk)
        if rnd:
            # delete a few live edges (sampled) + one absent edge,
            # split across requests to exercise coalescing
            live = oracle.alive()
            kills = live[rng.integers(0, live.shape[0], 4)]
            svc.submit_delete("t", kills[:2])
            svc.submit_delete("t", kills[2:])
            svc.submit_delete("t", [[0, n - 1]])
            oracle.delete(np.concatenate([kills, [[0, n - 1]]]))
        pairs = rng.integers(0, n, (9, 2))
        uid = svc.submit_query("t", "same_component", pairs)
        delete_calls = svc.stats["delete_calls"]
        finished = {r.uid: r for r in svc.run()}
        if rnd:       # 3 delete requests -> ONE coalesced device call
            assert svc.stats["delete_calls"] == delete_calls + 1
        labels = oracle.labels()
        np.testing.assert_array_equal(
            np.asarray(finished[uid].result),
            labels[pairs[:, 0]] == labels[pairs[:, 1]])
        np.testing.assert_array_equal(np.asarray(reg.get("t").labels),
                                      labels)
    assert svc.stats["errors"] == 0
    assert svc.stats["deletes_absorbed"] == 6


def test_registry_insert_accepts_device_graph_and_stays_fresh():
    """DeviceGraph inserts through the registry keep the version /
    invalidation protocol intact (device-side version ticks)."""
    from repro.graphs.device import DeviceGraph
    reg = GraphRegistry()
    reg.create("g", 8)
    reg.insert("g", DeviceGraph.from_edges([[0, 1], [2, 3]], 8))
    v1 = reg.version("g")
    assert v1 == 1
    assert bool(reg.same_component("g", [[0, 1]])[0])
    # non-merging DeviceGraph insert: version unchanged, cache warm
    reg.insert("g", DeviceGraph.from_edges([[1, 0]], 8))
    assert reg.version("g") == v1
    t = reg.get("g")
    hits = t.stats.cache_hits
    assert bool(reg.same_component("g", [[0, 1]])[0])
    assert t.stats.cache_hits == hits + 1
    # merging insert ticks and invalidates
    reg.insert("g", DeviceGraph.from_edges([[1, 2]], 8))
    assert reg.version("g") > v1
    assert bool(reg.same_component("g", [[0, 3]])[0])


def test_service_respects_slot_budget():
    reg = GraphRegistry()
    reg.create("g", 8)
    svc = ConnectivityService(reg, slots=2)
    for _ in range(5):
        svc.submit_query("g", "count_components")
    assert len(svc.step()) == 2
    assert len(svc.queue) == 3
    assert len(svc.run()) == 3

def test_service_tick_keeps_midtick_submissions():
    """Regression: ``step()`` must snapshot the admitted slice ONCE
    and delete exactly that many entries. The old ``self.queue =
    self.queue[self.slots:]`` reslice re-read the list, so a submit
    landing DURING the tick (a collect callback enqueueing follow-up
    work) below the slot budget was silently dropped — admitted by
    nobody, never retired."""
    reg = GraphRegistry()
    reg.create("g", 8)
    svc = ConnectivityService(reg, slots=4)

    class MidTickQueue(list):
        """Appends one follow-up request the first time the tick
        reads the admitted slice (before the deletion happens)."""
        def __init__(self, svc):
            super().__init__()
            self.svc = svc
            self.armed = False

        def __getitem__(self, item):
            out = super().__getitem__(item)
            if self.armed and isinstance(item, slice):
                self.armed = False
                # lands mid-tick, below the slot budget
                super().append(_mk(self.svc, "late"))
            return out

    def _mk(svc, tag):
        from repro.connectivity.service import Request
        svc._uid += 1
        return Request(svc._uid, "g", "count_components")

    q = MidTickQueue(svc)
    svc.queue = q
    svc.submit_query("g", "count_components")   # 1 queued < slots=4
    q.armed = True
    first = svc.step()
    # only the pre-tick request retired; the mid-tick one SURVIVES
    assert len(first) == 1
    assert len(svc.queue) == 1, "mid-tick submission was dropped"
    second = svc.step()
    assert len(second) == 1 and second[0].done
    assert svc.queue == []
