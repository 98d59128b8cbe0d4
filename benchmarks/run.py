"""Benchmark harness — one function per paper table/figure.

  table1      Graph properties of the scaled Table I stand-ins.
  fig5        Variant comparison (soman -> +multijump -> +atomic ->
              adaptive): wall-clock, host syncs, work counters — the
              paper's Fig. 5 in this container's currency (CPU-backend
              wall-clock is a secondary signal; work counts are primary).
  fig6        Segmentation sweep: speedup + work vs number of segments;
              the paper's Fig. 6 (optimum expected near s = 2|E|/|V|).
  kernels     Pallas kernel microbenches (interpret mode: correctness +
              overhead accounting, not TPU wall-clock — §Roofline covers
              TPU perf).
  batched     Batched-throughput table: a fleet of small graphs through
              the shape-bucketed vmapped engine vs a per-graph loop
              (DESIGN.md §4).
  incremental Incremental-vs-full-recompute table: streaming edge
              insertions absorbed by ``IncrementalCC`` vs a from-scratch
              adaptive run per batch (DESIGN.md §6).
  service     Connectivity-service table: a mixed insert/query stream
              through the multi-tenant registry (policy-routed inserts,
              microbatched on-device queries) vs the recompute-per-query
              counterfactual (DESIGN.md §7). Warm-starts the policy's
              autotune cache (JSON under results/).
  dynamic     Fully-dynamic table (DESIGN.md §9): interleaved
              insert/delete churn through ``DynamicCC`` (tombstone +
              scoped recompute over affected components only) vs a
              full recompute per mutation batch, across delete:insert
              ratios. hook_ops saved is the signal; asserts scoped
              beats full at ratio <= 1:10.
  api         Facade-overhead table (DESIGN.md §10): repro.api.solve
              (plan + policy + registry dispatch) vs the direct engine
              entry on the same DeviceGraph; asserts dispatch adds no
              measurable per-call overhead and plans stay host-only.
  fused       Fused-vs-per-round Pallas backend (DESIGN.md §8): the
              whole segment scan in ONE pallas_call (cc_fused kernel,
              method="pallas_fused") vs one launch per segment hook +
              one per compress sweep, interpret mode on CPU. Launch
              counts are the hardware-independent signal.
  sampled     Sampling-accelerated table (DESIGN.md §13): k-out
              sampling + residue-only scan (``sampled`` /
              ``sampled_fused``) vs the full-scan ``adaptive`` and
              ``pallas_fused`` backends on skewed (soc/kron) and
              road stand-ins; asserts the residue scan pays less than
              the full scan on skewed inputs and that the degree-skew
              policy routes ``auto`` onto/off sampling per class.

Output: CSV blocks on stdout + files under benchmarks/results/; the
batched/incremental/service/fused tables additionally emit one standard
``BENCH {json}`` line per row (machine-scrapable), a
``results/<name>.jsonl``, AND a ``BENCH_<name>.json`` summary at the
REPO ROOT so the perf trajectory is diffable across PRs.
Usage: ``python -m benchmarks.run [--only fig5] [--scale 0.004]``.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _block(r):
    import jax
    for leaf in jax.tree.leaves(r):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def _bench(fn, reps: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        _block(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _block(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _emit(name: str, header: str, rows: list) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.csv")
    with open(path, "w") as f:
        f.write(header + "\n")
        for row in rows:
            f.write(",".join(str(x) for x in row) + "\n")
    print(f"\n## {name} -> {path}")
    print(header)
    for row in rows:
        print(",".join(str(x) for x in row))


def _emit_bench(name: str, rows: list[dict]) -> None:
    """Standard BENCH JSON: one ``BENCH {...}`` line per row on stdout
    (scraped by CI/report tooling), a JSONL file under results/, and a
    ``BENCH_<name>.json`` summary at the repo root — the root files are
    committed-adjacent artifacts that make the perf trajectory diffable
    across PRs (CI uploads them)."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.jsonl")
    with open(path, "w") as f:
        for row in rows:
            rec = {"bench": name, **row}
            line = json.dumps(rec)
            f.write(line + "\n")
            print("BENCH " + line)
    summary = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    with open(summary, "w") as f:
        json.dump({"bench": name, "rows": rows}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    print(f"## {name} -> {path} + {summary}")


def graphs_for_scale(scale: float):
    from repro.graphs.generators import table1_scaled
    return [table1_scaled(name, scale=scale, seed=1)
            for name in ("usa-osm", "euro-osm-karls", "soc-live-journal",
                         "kron-logn21")]


def table1(scale: float) -> None:
    rows = []
    for g in graphs_for_scale(scale):
        s = g.stats()
        rows.append([s["name"], s["nodes"], s["edges"], s["avg_degree"],
                     s["max_degree"], s["size_mb"]])
    _emit("table1", "name,nodes,edges,avg_degree,max_degree,size_mb",
          rows)


def fig5(scale: float) -> None:
    """Fig. 5 analogue. ``soman``/``multijump`` also run under HOST-side
    control flow (the GPU baseline's CPU-GPU round trips, measured,
    via the facade's ``hostloop`` backend); fused variants are one jit.
    Work counters are the hardware-independent signal."""
    from repro.api import Solver
    from repro.core.unionfind import connected_components_oracle

    def hostloop(solver, method):
        plan = solver.plan(backend="hostloop", hostloop_method=method)
        res = plan.run()
        return res.labels, plan.artifacts["hostloop_stats"]

    rows = []
    for g in graphs_for_scale(scale):
        edges, n = g.edges, g.num_nodes
        want = connected_components_oracle(edges, n)
        solver = Solver.open(g)
        for method in ("soman", "multijump", "atomic_hook", "adaptive"):
            res = solver.solve(backend=method)
            assert np.array_equal(np.asarray(res.labels), want), method
            t_fused = _bench(
                lambda m=method: solver.solve(backend=m).labels)
            if method in ("soman", "multijump"):
                t_host = _bench(
                    lambda m=method: hostloop(solver, m)[0], reps=1)
                _, stats = hostloop(solver, method)
                syncs = stats["sync_rounds"]
            else:
                t_host, syncs = t_fused, 1
            w = res.work
            rows.append([
                g.name, method, round(t_host * 1e3, 2),
                round(t_fused * 1e3, 2), syncs,
                int(w.hook_ops), int(w.jump_ops), int(w.jump_sweeps),
                int(w.hook_rounds)])
    _emit("fig5", "graph,method,ms_hostloop,ms_fused,host_syncs,"
          "hook_ops,jump_ops,jump_sweeps,hook_rounds", rows)


def fig6(scale: float) -> None:
    """Segmentation sweep (Fig. 6): speedup over the single-segment
    Atomic-Hook baseline vs number of segments."""
    from repro.api import solve
    from repro.core.segmentation import adaptive_num_segments

    rows = []
    for g in graphs_for_scale(scale):
        edges, n = g.edges, g.num_nodes
        s_star = adaptive_num_segments(g.num_edges, n)
        candidates = sorted({1, max(2, s_star // 4), max(2, s_star // 2),
                             s_star, s_star * 2, s_star * 4})
        t1 = _bench(lambda: solve(
            edges, n, method="adaptive", num_segments=1).labels)
        for s in candidates:
            t = _bench(lambda s=s: solve(
                edges, n, method="adaptive", num_segments=s).labels)
            res = solve(edges, n, method="adaptive", num_segments=s)
            rows.append([g.name, s, int(s == s_star), round(t * 1e3, 2),
                         round(t1 / t, 3), int(res.work.jump_sweeps),
                         int(res.work.hook_ops)])
    _emit("fig6", "graph,segments,is_heuristic,ms,speedup_vs_1seg,"
          "jump_sweeps,hook_ops", rows)


def kernels() -> None:
    import jax.numpy as jnp
    from repro.kernels.embedding_bag import ops as eb, ref as ebr
    from repro.kernels.flash_attention import ops as fa, ref as far
    from repro.kernels.hook import ops as hk, ref as hkr
    from repro.kernels.multi_jump import ops as mj, ref as mjr
    from repro.kernels.segment_reduce import ops as sr, ref as srr

    rng = np.random.default_rng(0)
    rows = []

    q = jnp.asarray(rng.standard_normal((4, 256, 64)), jnp.float32)
    rows.append(["flash_attention", "4x256x64",
                 round(_bench(lambda: fa.flash_attention_pallas(
                     q, q, q, sm_scale=0.125, causal=True,
                     interpret=True), reps=1) * 1e3, 2),
                 round(_bench(lambda: far.ref_attention(
                     q, q, q, sm_scale=0.125, causal=True)) * 1e3, 2)])

    pi = jnp.asarray(np.maximum(np.arange(4096) - 1, 0), jnp.int32)
    rows.append(["multi_jump", "chain-4096",
                 round(_bench(lambda: mj.multi_jump_pallas(
                     pi, interpret=True), reps=1) * 1e3, 2),
                 round(_bench(lambda: mjr.ref_full_compress(pi))
                       * 1e3, 2)])

    edges = jnp.asarray(rng.integers(0, 1024, (4096, 2)), jnp.int32)
    pi0 = jnp.arange(1024, dtype=jnp.int32)
    rows.append(["hook", "V1024-E4096",
                 round(_bench(lambda: hk.hook_pallas(
                     pi0, edges, interpret=True), reps=1) * 1e3, 2),
                 round(_bench(lambda: hkr.ref_hook_round(pi0, edges))
                       * 1e3, 2)])

    vals = jnp.asarray(rng.standard_normal((4096, 32)), jnp.float32)
    ids = jnp.sort(jnp.asarray(rng.integers(0, 256, 4096), jnp.int32))
    rows.append(["segment_reduce", "4096x32-to-256",
                 round(_bench(lambda: sr.segment_reduce_pallas(
                     vals, ids, 256, interpret=True), reps=1) * 1e3, 2),
                 round(_bench(lambda: srr.ref_segment_reduce(
                     vals, ids, 256)) * 1e3, 2)])

    table = jnp.asarray(rng.standard_normal((10000, 16)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 10000, (512, 4)), jnp.int32)
    rows.append(["embedding_bag", "512bagsx4",
                 round(_bench(lambda: eb.embedding_bag_pallas(
                     table, idx, interpret=True), reps=1) * 1e3, 2),
                 round(_bench(lambda: ebr.ref_embedding_bag(
                     table, idx)) * 1e3, 2)])

    _emit("kernels", "kernel,shape,ms_interpret,ms_ref", rows)


def batched() -> None:
    """Batched-throughput table (DESIGN.md §4): a mixed fleet of small
    graphs through the shape-bucketed vmapped adaptive engine vs the
    per-graph jit loop. Labels are asserted bit-identical.

    ``jit_calls`` (device dispatches per fleet) is the primary,
    hardware-independent signal — the per-graph loop pays one dispatch
    per graph, the batched engine one per shape bucket. CPU-backend
    wall-clock does not reward dispatch amortization the way a real
    accelerator does (same caveat as fig5)."""
    from repro.api import Solver, solve
    from repro.core.batch import bucketize
    from repro.graphs.generators import (chain, disjoint_cliques,
                                         grid_road, rmat)

    fleets = {
        "molecules-64": [rmat(5, 3, seed=s) for s in range(64)],
        "mixed-48": ([chain(40 + s) for s in range(16)] +
                     [disjoint_cliques(3, 4 + s % 3, seed=s)
                      for s in range(16)] +
                     [grid_road(8, seed=s) for s in range(16)]),
        "medium-16": [rmat(8, 8, seed=s) for s in range(16)],
    }
    rows = []
    for name, graphs in fleets.items():
        batched_out = Solver.solve_batch(graphs)
        for g, r in zip(graphs, batched_out):
            want = solve(g.edges, g.num_nodes, method="adaptive").labels
            assert np.array_equal(np.asarray(r.labels),
                                  np.asarray(want)), name
        t_loop = _bench(lambda: [solve(
            g.edges, g.num_nodes, method="adaptive").labels
            for g in graphs])
        t_batched = _bench(
            lambda: [r.labels for r in Solver.solve_batch(graphs)])
        n_buckets = len(bucketize([(g.edges, g.num_nodes)
                                   for g in graphs]))
        rows.append({
            "fleet": name, "n_graphs": len(graphs),
            "n_buckets": n_buckets,
            "jit_calls_pergraph": len(graphs),
            "jit_calls_batched": n_buckets,
            "ms_pergraph_loop": round(t_loop * 1e3, 2),
            "ms_batched": round(t_batched * 1e3, 2),
            "speedup": round(t_loop / t_batched, 2),
            "graphs_per_s_batched": round(len(graphs) / t_batched, 1),
        })
    _emit_bench("batched", rows)


def incremental(scale: float) -> None:
    """Incremental-vs-full-recompute table (DESIGN.md §6): absorb a
    stream of edge-insertion batches into a ``Solver`` streaming
    session (policy-routed through the incremental engine) vs running
    the adaptive engine from scratch on the accumulated edge set after
    every batch. hook_ops is the hardware-independent signal."""
    from repro.api import Solver, solve
    from repro.core.unionfind import connected_components_oracle

    rows = []
    for g in graphs_for_scale(scale):
        edges, n = np.asarray(g.edges), g.num_nodes
        rng = np.random.default_rng(0)
        order = rng.permutation(edges.shape[0])
        n_batches = 8
        splits = np.array_split(order, n_batches)

        def run_incremental():
            inc = Solver.open(num_nodes=n)
            for s in splits:
                inc.insert(edges[s])
            return inc

        def run_full():
            ops = 0
            acc = np.zeros((0, 2), np.int32)
            labels = None
            for s in splits:
                acc = np.concatenate([acc, edges[s]], axis=0)
                r = solve(acc, n, method="adaptive")
                ops += int(r.work.hook_ops)
                labels = r.labels
            return ops, labels

        inc = run_incremental()
        full_ops, full_labels = run_full()
        want = connected_components_oracle(edges, n)
        assert np.array_equal(np.asarray(inc.labels), want), g.name
        assert np.array_equal(np.asarray(full_labels), want), g.name
        t_inc = _bench(lambda: run_incremental().labels, reps=2)
        t_full = _bench(lambda: run_full()[1], reps=2)
        rows.append({
            "graph": g.name, "nodes": n, "edges": int(edges.shape[0]),
            "batches": n_batches,
            "ms_incremental": round(t_inc * 1e3, 2),
            "ms_full_recompute": round(t_full * 1e3, 2),
            "speedup": round(t_full / t_inc, 2),
            "hook_ops_incremental": inc.work["hook_ops"],
            "hook_ops_full": full_ops,
            "hook_ops_saved_x": round(full_ops /
                                      max(inc.work["hook_ops"], 1), 2),
        })
    _emit_bench("incremental", rows)


def service(scale: float) -> None:
    """Connectivity-service table (DESIGN.md §7): two live tenants (a
    social/kron R-MAT and a road grid), a mixed stream of insert and
    query requests through the slot-based engine. The counterfactual a
    query service without live labels would pay — one full adaptive
    recompute of the accumulated edge set per query request — is
    measured for real (same engine, same inputs). hook_ops is the
    hardware-independent signal; every service query is answered from
    the live label array (zero recomputes)."""
    from repro import obs
    from repro.api import solve
    from repro.connectivity.policy import AutotuneCache, warm_start
    from repro.connectivity.registry import GraphRegistry
    from repro.connectivity.service import (QUERY_KINDS,
                                            ConnectivityService)
    from repro.core.unionfind import connected_components_oracle
    from repro.graphs.generators import grid_road, rmat

    side = max(8, int((24e6 * scale) ** 0.5))
    sc = max(8, int(np.log2(max(5e6 * scale, 2))))
    tenants = {
        "social": rmat(sc, 7, a=0.45, b=0.22, c=0.22, seed=1,
                       name="social"),
        "road": grid_road(side, extra_prob=0.02, seed=1, name="road"),
    }

    os.makedirs(RESULTS_DIR, exist_ok=True)
    cache_path = os.path.join(RESULTS_DIR, "autotune_cache.json")
    policy_cache = warm_start(tenants.values(), AutotuneCache(cache_path))

    n_rounds, queries_per_round = 6, 4
    pairs_per_query = 64

    def run_stream(collect_counterfactual: bool):
        registry = GraphRegistry(policy_cache=policy_cache)
        svc = ConnectivityService(registry, slots=32)
        rng = np.random.default_rng(0)
        counter_ops = 0
        for name, g in tenants.items():
            registry.create(name, g.num_nodes)
        splits = {name: np.array_split(
            rng.permutation(g.num_edges), n_rounds)
            for name, g in tenants.items()}
        for rnd in range(n_rounds):
            for name, g in tenants.items():
                edges = np.asarray(g.edges)
                svc.submit_insert(name, edges[splits[name][rnd]])
                for _ in range(queries_per_round):
                    pairs = rng.integers(0, g.num_nodes,
                                         (pairs_per_query, 2))
                    svc.submit_query(name, "same_component", pairs)
                svc.submit_query(name, "count_components")
            svc.run()
            if collect_counterfactual:
                for name, g in tenants.items():
                    acc = np.concatenate(
                        [np.asarray(g.edges)[s]
                         for s in splits[name][: rnd + 1]], axis=0)
                    res = solve(acc, g.num_nodes, method="adaptive")
                    counter_ops += (queries_per_round + 1) * int(
                        res.work.hook_ops)
        return svc, counter_ops

    # the stream runs with span tracing + on-device metrics ENABLED —
    # the SLO table below prices the instrumented service, and the
    # trace/SLO exports are the CI artifacts
    tracer = obs.enable(capacity=1 << 14)
    tracer.reset()
    svc, counter_ops = run_stream(True)
    # correctness gate: final labels equal the union-find oracle
    for name, g in tenants.items():
        want = connected_components_oracle(g.edges, g.num_nodes)
        got = np.asarray(svc.registry.get(name).labels)
        assert np.array_equal(got, want), name

    # export the counted run's telemetry before the timed reruns
    # overwrite the ring buffer
    trace_path = os.path.join(RESULTS_DIR, "service_trace.jsonl")
    tracer.export_jsonl(trace_path)
    with open(os.path.join(RESULTS_DIR, "service_slo.json"), "w") as fh:
        json.dump(svc.obs_summary(), fh, indent=1, sort_keys=True)

    t = _bench(lambda: run_stream(False)[0].registry.get(
        "road").labels, reps=2)
    obs.disable()
    service_ops = sum(s["hook_ops"] for s in svc.registry.stats().values())
    assert service_ops < counter_ops, (service_ops, counter_ops)

    def q_ms(quantile, tenant=None):
        return round(svc.slo.percentile(quantile, tenant=tenant,
                                        kinds=QUERY_KINDS) * 1e3, 4)

    counters = tracer.counters
    st = svc.stats
    rows = [{
        "workload": "mixed-insert-query",
        "tenants": len(tenants),
        "rounds": n_rounds,
        "insert_requests": st["inserts_absorbed"],
        "query_requests": st["queries_served"],
        "pairs_answered": st["pairs_answered"],
        "query_calls": st["query_calls"],
        "ms_stream": round(t * 1e3, 2),
        "queries_per_s": round(st["queries_served"] / t, 1),
        "recomputes_avoided": st["recomputes_avoided"],
        "hook_ops_service": service_ops,
        "hook_ops_perquery_recompute": counter_ops,
        "hook_ops_saved_x": round(counter_ops / max(service_ops, 1), 2),
        "autotune_cache": os.path.basename(cache_path),
        # latency SLOs (repro.obs; query kinds only, milliseconds):
        # per-tenant + exact merged global p50/p99
        **{f"p{int(p * 100)}_ms_query_{name}": q_ms(p, name)
           for name in tenants for p in (0.50, 0.99)},
        "p50_ms_query_global": q_ms(0.50),
        "p99_ms_query_global": q_ms(0.99),
        "autotune_hits": counters.get("autotune.hit", 0),
        "autotune_misses": counters.get("autotune.miss", 0),
        "trace_spans": tracer.log.total,
    }]
    _emit_bench("service", rows)


def dynamic(scale: float) -> None:
    """Fully-dynamic table (DESIGN.md §9 + §14): interleaved
    insert/delete churn absorbed by a ``Solver`` streaming session on
    THREE delete designs — the maintained-forest tree-aware route
    (classify against the device-resident forest, short-circuit
    all-non-tree batches, skeleton + crossing reconnection otherwise),
    the plain scoped recompute (PR 5), and the full-recompute
    counterfactual (a from-scratch adaptive run over the survivors
    after EVERY mutation batch) — swept across delete:insert ratios.

    The forest stream runs only on the graphs the policy actually
    routes to it (``tree_edge_ratio <= FOREST_TREE_RATIO``): on
    road-like graphs nearly every edge IS a tree edge, so a skeleton
    the size of the edge set cannot beat the scoped recompute and the
    router sends them down the plain path; their forest columns are
    null. On routed graphs deletes arrive as a stream of micro-batches
    (at most ~9 ticks per round) — the steady-state shape the
    maintained forest is for, and what makes ``tree_hit_ratio``
    meaningful — while unrouted graphs keep the one-batch-per-round
    stream of the PR 5 table.

    hook_ops is the hardware-independent signal; the acceptance bars
    are (a) the forest route billing >= 5x fewer delete-side hook_ops
    than the scoped recompute at 1:20 and 1:10 churn on every routed
    graph, (b) an explicitly all-non-tree batch billing ZERO hook work
    (the lax.cond short-circuit), and (c) the 1:20 forest stream
    beating the scoped stream on wall clock across the routed graphs
    (the BENCH_dynamic smoke gate — the ratio-insensitive ms plateau
    this PR is motivated against). Labels are oracle-checked at the
    end of every stream. The steady-state delete tick's zero-transfer
    property is pinned by the facade/service transfer-guard tests,
    not here."""
    from repro.api import Solver, solve
    from repro.connectivity import policy
    from repro.core.unionfind import DynamicConnectivityOracle

    FOREST, SCOPED = policy.DYNAMIC_DELETE_FOREST, policy.DYNAMIC_DELETE
    n_rounds = 6
    ratios = (0.05, 0.1, 0.25, 1.0)       # delete:insert per round
    smoke_ratio = 0.05                    # the 1:20 wall-clock gate
    micro_batch = 64                      # steady-state delete tick size
    rows = []
    gate_ms = {FOREST: 0.0, SCOPED: 0.0}
    for g in graphs_for_scale(scale):
        edges, n = np.asarray(g.edges, np.int32), g.num_nodes
        order = np.random.default_rng(0).permutation(edges.shape[0])
        splits = np.array_split(order, n_rounds)
        forest_routed = policy.extract_features(
            n, edges.shape[0]).tree_edge_ratio <= policy.FOREST_TREE_RATIO
        forest_dyn = None                 # last counted forest session
        for ratio in ratios:
            # Build the mutation schedule ONCE per (graph, ratio): the
            # per-round insert chunks, the micro-batched kill stream
            # (drawn from the oracle's evolving live set), the
            # full-recompute counterfactual bill (route-independent —
            # it depends only on the mutation stream), and the expected
            # end labels. Timed replays below then drive ONLY the
            # solver, so the forest-vs-scoped wall comparison measures
            # engine work rather than shared oracle bookkeeping.
            rng = np.random.default_rng(1)
            oracle = DynamicConnectivityOracle(n)
            sched = []
            full_ops = 0
            for s in splits:
                chunk = edges[s]
                oracle.insert(chunk)
                r = solve(oracle.alive(), n, method="adaptive")
                full_ops += int(r.work.hook_ops)
                k = max(1, int(round(ratio * chunk.shape[0])))
                live = oracle.alive()
                kills = live[rng.integers(0, live.shape[0], k)]
                # routed graphs: stream the round quota in bounded
                # micro-batches; both routes replay the SAME ticks
                step = max(micro_batch, -(-k // 8)) if forest_routed \
                    else k
                batches = [kills[lo:lo + step] for lo in range(0, k, step)]
                for batch in batches:
                    oracle.delete(batch)
                r = solve(oracle.alive(), n, method="adaptive")
                full_ops += int(r.work.hook_ops)
                sched.append((chunk, batches))
            want_labels = oracle.labels()

            def run_stream(route: str, count_deletes: bool = False):
                dyn = Solver.open(num_nodes=n, delete_route=route)
                del_ops = 0
                for chunk, batches in sched:
                    dyn.insert(chunk)
                    if route == FOREST:
                        # the bulk first insert adopts (forest stales);
                        # repair on the insert side so delete billing
                        # prices the steady state, not the one-off
                        dyn.state.ensure_forest()
                    for batch in batches:
                        if count_deletes:
                            before = dyn.work["hook_ops"]
                        dyn.delete(batch)
                        if count_deletes:
                            del_ops += dyn.work["hook_ops"] - before
                return dyn, del_ops

            sdyn, scoped_del_ops = run_stream(SCOPED, count_deletes=True)
            assert np.array_equal(np.asarray(sdyn.labels),
                                  want_labels), g.name
            scoped_ops = sdyn.work["hook_ops"]
            t_scoped = _bench(
                lambda: np.asarray(run_stream(SCOPED)[0].labels),
                reps=2 if ratio == smoke_ratio else 1)
            forest_ops = forest_del_ops = tree_hit_ratio = None
            t_forest = None
            if forest_routed:
                fdyn, forest_del_ops = run_stream(
                    FOREST, count_deletes=True)
                assert np.array_equal(np.asarray(fdyn.labels),
                                      want_labels), g.name
                forest_ops = fdyn.work["hook_ops"]
                rc = fdyn.state.delete_route_counts()
                ticks = rc["nontree_shortcircuit"] + rc["tree_scoped"]
                tree_hit_ratio = rc["tree_scoped"] / max(ticks, 1)
                if ratio <= 0.1:          # the ISSUE 9 bar: >= 5x
                    assert forest_del_ops * 5 <= scoped_del_ops, \
                        (g.name, ratio, forest_del_ops, scoped_del_ops)
                t_forest = _bench(
                    lambda: np.asarray(run_stream(FOREST)[0].labels),
                    reps=2 if ratio == smoke_ratio else 1)
                if ratio == smoke_ratio:
                    gate_ms[FOREST] += t_forest
                    gate_ms[SCOPED] += t_scoped
                forest_dyn = fdyn
            engine_ops = forest_ops if forest_routed else scoped_ops
            t_engine = t_forest if forest_routed else t_scoped
            if ratio <= 0.1:    # the PR-5 bar, on the routed engine:
                # under micro-batched churn the E-wide scoped baseline
                # legitimately loses to full recompute — the plateau
                # the maintained forest removes
                assert engine_ops < full_ops, (g.name, ratio,
                                               engine_ops, full_ops)
            rows.append({
                "graph": g.name, "nodes": n,
                "edges_inserted": int(edges.shape[0]),
                "rounds": n_rounds,
                "delete_insert_ratio": ratio,
                "edges_deleted": int(sdyn.state.num_edges_deleted),
                "partition_changes": int(sdyn.version),
                "forest_routed_by_policy": forest_routed,
                "tree_hit_ratio": None if tree_hit_ratio is None
                else round(tree_hit_ratio, 4),
                "ms_stream": round(t_engine * 1e3, 2),
                "ms_stream_scoped": round(t_scoped * 1e3, 2),
                "hook_ops_dynamic": engine_ops,
                "hook_ops_deletes_forest": forest_del_ops,
                "hook_ops_deletes_scoped": scoped_del_ops,
                "hook_ops_full_recompute": full_ops,
                "hook_ops_saved_x": round(full_ops / max(engine_ops, 1), 2),
                "delete_hook_ops_saved_x": None if forest_del_ops is None
                else round(scoped_del_ops / max(forest_del_ops, 1), 2),
            })

        # the all-non-tree short-circuit bills ZERO hook work: kill a
        # batch drawn from the alive NON-forest edges of the last
        # counted forest session (host set-difference against the
        # maintained forest) and assert the counters did not move
        if forest_dyn is not None:
            st = forest_dyn.state
            st.ensure_forest()
            parents = np.asarray(st.forest[0])
            tree = {tuple(sorted(map(int, parents[r])))
                    for r in np.flatnonzero(parents[:, 0] >= 0)}
            log_e = np.asarray(st.log.edges)[:st.log.rows]
            log_a = np.asarray(st.log.alive)[:st.log.rows]
            alive_pairs = {tuple(sorted(map(int, e)))
                           for e, a in zip(log_e, log_a) if a}
            non_tree = sorted(alive_pairs - tree)[:16]
            if non_tree:
                before = forest_dyn.work["hook_ops"]
                forest_dyn.delete(np.asarray(non_tree, np.int32))
                assert forest_dyn.work["hook_ops"] == before, g.name

    # BENCH_dynamic smoke gate: at 1:20 churn the forest route must
    # beat the scoped recompute on wall clock across the routed graphs
    if gate_ms[SCOPED]:
        assert gate_ms[FOREST] < gate_ms[SCOPED], gate_ms
    _emit_bench("dynamic", rows)


def fused(scale: float) -> None:
    """Fused-vs-per-round Pallas backend (DESIGN.md §8). The per-round
    backend launches one hook kernel per segment plus one multi_jump
    kernel per compress sweep (``num_segments + jump_sweeps`` per
    segment scan); the fused ``cc_fused`` kernel runs the whole scan in
    ONE pallas_call with scalar-prefetched segment boundaries. Launch
    counts are the hardware-independent signal — CPU interpret-mode
    wall-clock (reported for completeness) does not price launch
    overhead the way a real accelerator does."""
    import jax.numpy as jnp
    from repro.api import Solver, solve
    from repro.core import rounds as R
    from repro.core.segmentation import plan_segmentation
    from repro.core.unionfind import connected_components_oracle
    from repro.kernels.cc_fused.ops import fused_segment_scan

    rows = []
    for g in graphs_for_scale(scale):
        edges, n = g.edges, g.num_nodes
        plan = plan_segmentation(g.num_edges, n)
        want = connected_components_oracle(edges, n)
        solver = Solver.open(g)
        fused_res = solver.solve(backend="pallas_fused")
        assert np.array_equal(np.asarray(fused_res.labels), want), g.name
        assert np.array_equal(
            np.asarray(solver.solve(backend="pallas",
                                    interpret=True).labels),
            want), g.name
        # SCAN-ONLY sweep count from the fused kernel's per-segment
        # counters (bit-compatible with the jnp composition) — the
        # trailing cleanup rounds cost extra launches on BOTH backends
        # and are excluded so the per-scan ratio is honest
        segs = R.pad_and_segment(
            jnp.asarray(np.asarray(edges), jnp.int32).reshape(-1, 2),
            plan)
        counts = R.segment_true_counts(plan.num_edges, plan)
        pi0 = jnp.arange(n, dtype=jnp.int32)
        _, sweeps = fused_segment_scan(pi0, segs, counts, interpret=True)
        scan_sweeps = int(sweeps.sum())
        # time BOTH backends in interpret mode (the fused public path
        # resolves interpret from the backend, which on a TPU host
        # would compare a compiled kernel against the emulated
        # baseline under a column name claiming otherwise)
        from repro.core.cc import _cc_fused_jit
        ej = jnp.asarray(np.asarray(edges), jnp.int32).reshape(-1, 2)
        t_perround = _bench(lambda: solver.solve(
            backend="pallas", interpret=True).labels, reps=1)
        t_fused = _bench(lambda: _cc_fused_jit(
            ej, None, num_nodes=n, num_segments=plan.num_segments,
            interpret=True).labels, reps=1)
        launches_old = plan.num_segments + scan_sweeps
        rows.append({
            "graph": g.name, "nodes": n, "edges": g.num_edges,
            "num_segments": plan.num_segments,
            "scan_jump_sweeps": scan_sweeps,
            # 1 hook launch/segment + 1 multi_jump launch/sweep
            "launches_perround_scan": launches_old,
            "launches_fused_scan": 1,
            "launch_reduction_x": launches_old,
            "ms_perround_interpret": round(t_perround * 1e3, 2),
            "ms_fused_interpret": round(t_fused * 1e3, 2),
            "hook_ops": int(fused_res.work.hook_ops),
        })
    _emit_bench("fused", rows)


def sampled(scale: float) -> None:
    """Sampling-accelerated table (DESIGN.md §13): the k-out sampling
    phase + residue-only adaptive scan (``sampled`` / ``sampled_fused``)
    vs the full-scan jnp ``adaptive`` and ``pallas_fused`` backends, on
    the Table I stand-ins. The skewed classes (soc/kron R-MATs) are the
    sampling phase's home turf — two cheap k-out rounds collapse the
    giant component and the expensive scan touches only the residue;
    the road grids are the contrast rows where sampling does NOT pay
    and the degree-skew policy keeps ``auto`` off it. hook_ops is the
    hardware-independent signal (Pallas wall-clock is interpret-mode,
    same caveat as the fused table)."""
    from repro.api import Solver
    from repro.connectivity.policy import AutotuneCache
    from repro.core.unionfind import connected_components_oracle

    skewed_classes = {"soc-live-journal", "kron-logn21"}
    rows = []
    for g in graphs_for_scale(scale):
        is_skewed = g.name in skewed_classes
        want = connected_components_oracle(g.edges, g.num_nodes)
        solver = Solver.open(g, policy_cache=AutotuneCache())

        res = {}
        ms = {}
        for backend in ("adaptive", "sampled", "sampled_fused"):
            res[backend] = solver.solve(backend=backend)
            assert np.array_equal(np.asarray(res[backend].labels),
                                  want), (g.name, backend)
            if backend == "sampled":
                stats = {k: int(v) for k, v in solver.last_plan
                         .artifacts["sampled_stats"].items()}
            ms[backend] = _bench(
                lambda b=backend: solver.solve(backend=b).labels,
                reps=1 if backend == "sampled_fused" else 2)
        res["pallas_fused"] = solver.solve(backend="pallas_fused")
        assert np.array_equal(np.asarray(res["pallas_fused"].labels),
                              want), g.name
        ms["pallas_fused"] = _bench(
            lambda: solver.solve(backend="pallas_fused").labels, reps=1)

        full_ops = int(res["adaptive"].work.hook_ops)
        samp_ops = int(res["sampled"].work.hook_ops)
        # phase billing folds exactly into the total (bit-exact gate)
        assert stats["sample_hook_ops"] + stats["residue_hook_ops"] \
            == samp_ops, g.name
        # the satellite's acceptance signal: on skewed inputs the
        # sampling phase shrinks the scan — the residue pays less than
        # the full scan did, and the TOTAL (sampling included) wins too
        if is_skewed:
            assert stats["residue_hook_ops"] < full_ops, (
                g.name, stats["residue_hook_ops"], full_ops)
            assert samp_ops < full_ops, (g.name, samp_ops, full_ops)
        # ...and the degree-skew feature routes "auto" accordingly
        auto = solver.plan().backend
        if is_skewed:
            assert auto == "sampled", (g.name, auto)
        else:
            assert auto != "sampled", (g.name, auto)

        rows.append({
            "graph": g.name, "nodes": g.num_nodes, "edges": g.num_edges,
            "skewed": int(is_skewed),
            "auto_backend": auto,
            "ms_adaptive": round(ms["adaptive"] * 1e3, 2),
            "ms_pallas_fused_interpret":
                round(ms["pallas_fused"] * 1e3, 2),
            "ms_sampled": round(ms["sampled"] * 1e3, 2),
            "ms_sampled_fused_interpret":
                round(ms["sampled_fused"] * 1e3, 2),
            "hook_ops_adaptive": full_ops,
            "hook_ops_sampled": samp_ops,
            "hook_ops_saved_x": round(full_ops / max(samp_ops, 1), 2),
            "sample_hook_ops": stats["sample_hook_ops"],
            "residue_hook_ops": stats["residue_hook_ops"],
            "n_residue": stats["n_residue"],
            "giant_size": stats["giant_size"],
        })
    _emit_bench("sampled", rows)


def api(scale: float) -> None:
    """Facade-overhead table (DESIGN.md §10): ``repro.api.solve``
    (plan construction + policy lookup + registry dispatch) vs calling
    the engine entry (``cc.solve_static``) directly on the SAME
    pre-coerced DeviceGraph. The facade's per-call cost is pure host
    Python — planning is also timed standalone (µs) to show it never
    touches the device. Asserts dispatch adds no measurable per-call
    overhead (way under the noise floor of one jitted solve)."""
    from repro import obs
    from repro.api import Solver, solve
    from repro.core import cc as cc_mod
    from repro.graphs.device import as_device_graph

    # disabled-mode tracing cost: one no-op span (flag check + shared
    # null context manager), measured standalone so the <=5% gate below
    # is deterministic instead of a wall-clock diff in CI-runner noise
    obs.disable()
    noop_reps = 100_000
    t0 = time.perf_counter()
    for _ in range(noop_reps):
        with obs.span("noop", backend="adaptive", reason="forced",
                      bucket="v0_e0"):
            pass
    noop_span_ns = (time.perf_counter() - t0) / noop_reps * 1e9

    rows = []
    for g in graphs_for_scale(scale):
        dg = as_device_graph(g)
        solver = Solver.open(dg)
        t_direct = _bench(lambda: cc_mod.solve_static(
            dg, method="adaptive").labels, reps=5)
        t_facade = _bench(lambda: solver.solve("adaptive").labels,
                          reps=5)
        # instrumented column: same dispatch with span tracing ON
        tracer = obs.enable(capacity=1 << 15)
        t_traced = _bench(lambda: solver.solve("adaptive").labels,
                          reps=5)
        tracer.log.clear()
        solver.solve("adaptive").labels.block_until_ready()
        spans_per_solve = len(tracer.log)
        obs.disable()
        # planning alone: host metadata only (µs-scale)
        reps = 200
        t0 = time.perf_counter()
        for _ in range(reps):
            solver.plan("adaptive")
        plan_us = (time.perf_counter() - t0) / reps * 1e6
        overhead_ms = (t_facade - t_direct) * 1e3
        # "no measurable overhead": the deterministic signal is the
        # plan's host-only cost (µs-scale); the wall-clock ratio gate
        # is deliberately loose — shared CI runners jitter, and every
        # other table in this file gates on deterministic counters
        assert plan_us < 2000, (g.name, plan_us)
        assert t_facade <= t_direct * 2.5 + 5e-3, (g.name, t_facade,
                                                   t_direct)
        # the PR-7 overhead gate: disabled-mode tracing (no-op spans x
        # instrumented sites on this dispatch) must cost <= 5% of the
        # facade call — upper-bounded from the standalone no-op cost,
        # so the gate cannot pass by timing luck
        disabled_obs_pct = 100 * (noop_span_ns * spans_per_solve) \
            / max(t_facade * 1e9, 1e-9)
        assert disabled_obs_pct <= 5.0, (g.name, disabled_obs_pct,
                                         noop_span_ns, spans_per_solve)
        rows.append({
            "graph": g.name, "nodes": g.num_nodes, "edges": g.num_edges,
            "ms_direct_engine": round(t_direct * 1e3, 3),
            "ms_facade": round(t_facade * 1e3, 3),
            "ms_facade_traced": round(t_traced * 1e3, 3),
            "overhead_ms": round(overhead_ms, 3),
            "overhead_pct": round(100 * overhead_ms /
                                  max(t_direct * 1e3, 1e-9), 1),
            "plan_us": round(plan_us, 1),
            "spans_per_solve": spans_per_solve,
            "noop_span_ns": round(noop_span_ns, 1),
            "disabled_obs_pct": round(disabled_obs_pct, 3),
        })
    _emit_bench("api", rows)


def fleet(scale: float) -> None:
    """Fleet serving table (DESIGN.md §15): the SAME mixed multi-tenant
    open-loop arrival stream driven through (a) the mesh-wide
    ``FleetService`` — per-device shards, cross-tenant batched query
    kernels, double-buffered pipelined ticks, one sharded whale tenant
    across the whole mesh — and (b) the single-device
    ``ConnectivityService`` baseline holding every tenant. Open loop:
    the schedule is pre-drawn and advanced by TICK, not by completion,
    so arrival pressure is identical for both paths and queue wait is
    part of the measured latency.

    Acceptance gate (ISSUE 10): on an 8-device mesh the fleet's
    aggregate request throughput must be >= 2x the single-device
    service on the same workload. The win on a host-parallelism-free
    CPU mesh comes from dispatch structure, not cores: the baseline
    pays one kernel launch + one version sync + one device->host
    materialization per (tenant, kind) group per tick, the fleet pays
    ~one stacked launch per (shard, kind, |V|) group and syncs a whole
    tick's answers one tick later. Query answers are cross-checked
    request-by-request between the two paths, and final labels against
    the union-find oracle."""
    import jax
    from repro import obs
    from repro.connectivity.service import (QUERY_KINDS,
                                            ConnectivityService)
    from repro.core.unionfind import connected_components_oracle
    from repro.fleet import FleetService

    n_dev = len(jax.devices())
    names = [f"t{i:04d}" for i in range(128 * n_dev)]
    n = max(64, int(2e5 * scale))
    whale_nodes = max(1 << 11, 4 * n)
    ticks, pairs_per_q, ins_edges = 6, 128, 24

    rng = np.random.default_rng(0)
    base_edges = {t: rng.integers(0, n, (n // 2, 2)).astype(np.int32)
                  for t in names}
    whale_edges = np.stack(
        [np.arange(4 * n, dtype=np.int32),
         np.arange(1, 4 * n + 1, dtype=np.int32)], axis=1)
    # pre-drawn open-loop arrivals, per tick: query-heavy with a
    # round-robin trickle of inserts (each tick a different slice of
    # tenants mutates). Both serving planes coalesce per (tenant,
    # kind), so per tick the baseline dispatches one kernel per tenant
    # per query kind while the fleet dispatches one STACKED kernel per
    # shard per query kind — the tenants-per-device ratio is the
    # dispatch-amplification the fleet removes.
    schedule = []
    for tick in range(ticks):
        arrivals = []
        for i, t in enumerate(names):
            if i % 256 == tick % 256:
                arrivals.append((t, "insert", rng.integers(
                    0, n, (ins_edges, 2)).astype(np.int32)))
            arrivals.append((t, "same_component", rng.integers(
                0, n, (pairs_per_q, 2)).astype(np.int32)))
            arrivals.append((t, "component_size", rng.integers(
                0, n, (pairs_per_q,)).astype(np.int32)))
        arrivals.append(("whale", "same_component", rng.integers(
            0, whale_nodes, (pairs_per_q, 2)).astype(np.int32)))
        schedule.append(arrivals)
    n_requests = sum(len(a) for a in schedule)

    probe = np.zeros((pairs_per_q, 2), np.int32)

    def preload(submit, submit_insert, run):
        for t in names:
            submit_insert(t, base_edges[t])
        submit_insert("whale", whale_edges)
        run()
        # one probe query per tenant: resolves every label array and
        # (fleet path) builds the cached label planes, so the timed
        # stream starts from serving steady state on BOTH paths
        for t in names:
            submit(t, "same_component", probe)
        submit("whale", "same_component", probe)
        run()

    def drive(submit, step, run):
        """Replay the open-loop schedule; returns {(tenant, kind, i):
        i-th answer of that kind} so the two paths cross-check exactly.
        Request uids are per-shard (not fleet-global), but retirement
        is FIFO per (tenant, kind), so the sequence number is a stable
        key even though the two paths interleave kinds differently."""
        retired = []
        for arrivals in schedule:
            for t, kind, payload in arrivals:
                submit(t, kind, payload)
            retired.extend(step())
        retired.extend(run())
        answers, seq = {}, {}
        for r in retired:
            assert r.error is None, (r.tenant, r.kind, r.error)
            if r.kind in QUERY_KINDS:
                i = seq.get((r.tenant, r.kind), 0)
                seq[(r.tenant, r.kind)] = i + 1
                answers[(r.tenant, r.kind, i)] = np.asarray(r.result)
        return answers

    shared_runners = []   # one compiled shard_map cache, every rep

    def build_fleet():
        fs = FleetService(slots_per_device=1024, rebalance_every=0,
                          shard_threshold=whale_nodes,
                          runners=shared_runners[0] if shared_runners
                          else None)
        if not shared_runners:
            shared_runners.append(fs.runners)
        for t in names:
            fs.admit(t, n, expected_edges=n)
        fs.admit("whale", whale_nodes, expected_edges=4 * n)
        assert fs.placement_of("whale") == "mesh"
        preload(fs.submit, fs.submit_insert, fs.run)
        return fs

    def build_single():
        svc = ConnectivityService(slots=4096)
        for t in names:
            svc.registry.create(t, n)
        svc.registry.create("whale", whale_nodes)
        preload(svc.submit, svc.submit_insert, svc.run)
        return svc

    def run_fleet():
        fs = build_fleet()
        return fs, drive(fs.submit, fs.step, fs.run)

    def run_single():
        svc = build_single()
        return svc, drive(svc.submit, svc.step, svc.run)

    def bench_streams(reps: int = 5):
        """Median wall time of the serving STREAM only, for both
        paths. A fresh service is built and preloaded per rep (tenant
        state mutates during the stream) but admission + bulk load are
        setup, not arrival traffic, so they stay outside the clock.
        The two paths' reps INTERLEAVE so machine-wide drift between
        measurement blocks cancels out of the throughput ratio, and
        the reported speedup is the MEDIAN OF PER-REP PAIRWISE ratios
        — each ratio compares adjacent-in-time runs, so slow-machine
        episodes hit both sides of the division."""
        ts = {"fleet": [], "single": []}
        for _ in range(reps):
            for label, build in (("fleet", build_fleet),
                                 ("single", build_single)):
                svc = build()
                t0 = time.perf_counter()
                drive(svc.submit, svc.step, svc.run)
                ts[label].append(time.perf_counter() - t0)
        ratio = float(np.median([s / f for f, s in
                                 zip(ts["fleet"], ts["single"])]))
        return (float(np.median(ts["fleet"])),
                float(np.median(ts["single"])), ratio)

    # warmup pass, identical shapes: compiles every kernel (including
    # the whale's shard_map program) so neither the counted SLO run
    # nor the timed reps pay compile time
    run_fleet()
    run_single()

    # counted run, tracing on: SLO percentiles + correctness
    tracer = obs.enable(capacity=1 << 14)
    tracer.reset()
    fs, fleet_answers = run_fleet()
    _, single_answers = run_single()
    assert fleet_answers.keys() == single_answers.keys()
    for k in fleet_answers:
        np.testing.assert_array_equal(fleet_answers[k],
                                      single_answers[k], err_msg=str(k))
    # oracle gate on one packed tenant + the sharded whale
    t0 = names[0]
    acc = np.concatenate([base_edges[t0]] + [
        p for a in schedule for (t, kind, p) in a
        if t == t0 and kind == "insert"])
    shard = fs.shards[fs.placement_of(t0)]
    np.testing.assert_array_equal(
        np.asarray(shard.registry.get(t0).labels),
        connected_components_oracle(acc, n))
    np.testing.assert_array_equal(
        np.asarray(fs._sharded["whale"].labels),
        connected_components_oracle(whale_edges, whale_nodes))
    fleet_slo = fs.slo()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "fleet_slo.json"), "w") as fh:
        json.dump(fs.slo_summary(), fh, indent=1, sort_keys=True)
    obs.disable()

    # timed runs, tracing off for both paths (identical settings)
    t_fleet, t_single, throughput_x = bench_streams()
    tput_fleet = n_requests / t_fleet
    tput_single = n_requests / t_single

    def q_ms(quantile):
        return round(fleet_slo.percentile(
            quantile, kinds=("same_component",)) * 1e3, 4)

    def tenant_q_ms(tenant):
        # per-tenant query percentiles for the BENCH row: one
        # representative packed tenant + the sharded whale (the full
        # per-tenant table is results/fleet_slo.json)
        return {q: round(fleet_slo.percentile(
                    p, tenant=tenant, kinds=("same_component",)) * 1e3, 4)
                for q, p in (("p50_ms", 0.50), ("p99_ms", 0.99))}

    engine = fs.engine.stats
    rows = [{
        "workload": "open-loop-mixed",
        "devices": n_dev,
        "tenants": len(names) + 1,
        "sharded_tenants": 1,
        "ticks": ticks,
        "requests": n_requests,
        "ms_fleet": round(t_fleet * 1e3, 2),
        "ms_single_device": round(t_single * 1e3, 2),
        "requests_per_s_fleet": round(tput_fleet, 1),
        "requests_per_s_single": round(tput_single, 1),
        "throughput_x": round(throughput_x, 2),
        "batched_dispatches": engine["batched_dispatches"],
        "query_calls_fleet": sum(s.stats["query_calls"]
                                 for s in fs.shards),
        "runner_cache": dict(fs.runners.stats),
        "p50_ms_query_fleet": q_ms(0.50),
        "p99_ms_query_fleet": q_ms(0.99),
        "per_tenant_query_ms": {t: tenant_q_ms(t)
                                for t in (names[0], "whale")},
        "per_tenant_slo_table": "results/fleet_slo.json",
    }]
    # the ISSUE 10 acceptance bar: >= 2x aggregate throughput on the
    # 8-device mesh (single-device runs report the ratio, no gate)
    if n_dev >= 8:
        assert throughput_x >= 2.0, rows
    _emit_bench("fleet", rows)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["table1", "fig5", "fig6", "kernels",
                             "batched", "incremental", "service",
                             "dynamic", "fused", "sampled", "api",
                             "fleet"])
    ap.add_argument("--scale", type=float, default=1 / 256,
                    help="Table I graph scale factor")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jobs = {"table1": lambda: table1(args.scale),
            "fig5": lambda: fig5(args.scale),
            "fig6": lambda: fig6(args.scale),
            "kernels": kernels,
            "batched": batched,
            "incremental": lambda: incremental(args.scale),
            "service": lambda: service(args.scale),
            "dynamic": lambda: dynamic(args.scale),
            "fused": lambda: fused(args.scale),
            "sampled": lambda: sampled(args.scale),
            "api": lambda: api(args.scale),
            "fleet": lambda: fleet(args.scale)}
    for name, job in jobs.items():
        if args.only and name != args.only:
            continue
        job()


if __name__ == "__main__":
    main()
