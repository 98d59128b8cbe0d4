"""Solver — the one front door to every execution mode (DESIGN.md §10).

``Solver.open(graph_or_edges, **opts)`` returns a session that handles:

  * **static solve** — ``solve()`` routes through the adaptive policy
    (``method="auto"``: autotune cache, then the paper's density
    heuristic) or any forced method/backend, dispatching through the
    ``BACKENDS`` registry;
  * **streaming mutation** — ``insert()`` / ``delete()`` lazily promote
    the session to the fully-dynamic engine and route every batch
    through ``policy.select_for`` (small insert → incremental absorb,
    bulk → static rebuild + adopt; small delete → tombstone + scoped
    recompute, bulk drop → rebuild over survivors). Steady-state
    mutation with ``DeviceGraph`` payloads is transfer-free under
    ``jax.transfer_guard("disallow")`` — same contract as the service
    tick, pinned in tests;
  * **queries** — every ``connectivity.queries`` lookup
    (``same_component`` / ``component_size`` / ``num_components`` /
    ``component_histogram``), answered from the live canonical label
    array, batches padded to the shared pow2 jit buckets;
  * **inspection** — ``plan()`` reifies the adaptive decision as an
    ``ExecutionPlan`` whose ``explain()`` shows the chosen backend, the
    pow2 shape bucket, the segmentation plan, and the predicted work,
    BEFORE anything runs.

One-shot convenience: ``repro.api.solve(graph, ...) -> CCResult``;
fleets: ``Solver.solve_batch(graphs)``; meshes:
``Solver.open(graph, mesh=mesh).solve()``.

>>> from repro.api import Solver
>>> s = Solver.open([[0, 1], [1, 2]], num_nodes=4)
>>> s.plan().backend
'atomic_hook'
>>> int(s.num_components())
2
>>> _ = s.insert([[2, 3]])
>>> s.connected(0, 3)
True
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import numpy as np

from repro.api.plan import ExecutionPlan
from repro.api.registry import get_backend
from repro.connectivity import policy, queries
from repro.core.batch import bucket_shape, pad_rows_pow2
from repro.core.cc import ALL_METHODS, CCResult
from repro.core.segmentation import plan_segmentation
from repro.graphs.device import (DeviceGraph, as_device_graph,
                                 validate_edge_bounds)
from repro.obs import trace as obs

# method spellings a plan accepts beyond "auto" (each is a backend name)
_PLANNABLE = tuple(ALL_METHODS) + ("pallas", "hostloop")

# per-call backend options plan()/solve() accept via **opts — validated
# so a typo'd tuning kwarg (lift_step, interpert, ...) raises instead of
# silently running with defaults, matching the legacy entrypoints'
# TypeError strictness
_KNOWN_OPTS = frozenset({"interpret", "hostloop_method"})


class Solver:
    """A connectivity session over one vertex set. Use ``open()``."""

    def __init__(self, graph: Optional[DeviceGraph], num_nodes: int, *,
                 lift_steps: int = 2, num_segments: int | None = None,
                 mesh=None, axis_names=("data",),
                 policy_cache: policy.AutotuneCache | None = None,
                 scan_method: str | None = None,
                 delete_route: str | None = None, name: str = "solver",
                 device=None):
        self._graph = graph            # opened static snapshot (or None)
        self._device = device          # pinned device (None = default)
        self.num_nodes = int(num_nodes)
        self.lift_steps = lift_steps
        self.num_segments = num_segments
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.policy_cache = policy_cache
        self._scan_method = scan_method   # force the scoped-scan backend
        if delete_route is not None \
                and delete_route not in policy.DELETE_METHODS:
            raise ValueError(f"unknown delete_route {delete_route!r}; "
                             f"choose from {policy.DELETE_METHODS} or "
                             "None (policy-routed)")
        self._delete_route = delete_route  # force the delete-side route
        self.name = name
        self._dyn = None               # live dynamic state (lazy)
        self._labels = None            # cached static-solve labels
        # cached (method, ForestResult, label version at build): kept
        # while the version is unchanged — an absorb that merged
        # nothing leaves the partition intact, so the forest still
        # spans it (edges only got added)
        self._forest = None
        self._empty = None             # cached empty DeviceGraph
        self.last_method: str | None = None
        self.last_plan: ExecutionPlan | None = None
        self.stats = {"solves": 0, "inserts": 0, "deletes": 0,
                      "absorbs": 0, "scoped_deletes": 0,
                      "forest_deletes": 0, "rebuilds": 0}

    # -- session lifecycle ---------------------------------------------------

    @classmethod
    def open(cls, graph=None, num_nodes: int | None = None, *,
             lift_steps: int = 2, num_segments: int | None = None,
             mesh=None, axis_names=("data",),
             policy_cache: policy.AutotuneCache | None = None,
             scan_method: str | None = None,
             delete_route: str | None = None,
             name: str = "solver", device=None) -> "Solver":
        """Open a session.

        Args:
          graph: a ``DeviceGraph``, a host ``Graph``, or a raw [E, 2]
            edge array (then ``num_nodes`` is required) — or ``None``
            for an empty streaming session over ``num_nodes`` vertices.
          num_nodes: |V| for raw arrays / empty sessions.
          lift_steps: accepted and has no effect. Every engine hooks
            flat labels (a full compress runs before each hook), so a
            root chase could never move a label and none is issued.
          num_segments: override the s = 2|E|/|V| heuristic.
          mesh: a ``jax.sharding.Mesh`` — plans default to the
            ``distributed`` backend over ``axis_names``.
          policy_cache: autotune cache for ``method="auto"`` routing
            (None = the process-wide default cache).
          scan_method: force the dynamic engine's scoped-scan backend
            (``"jnp"`` | ``"pallas_fused"``; None = policy-routed).
          delete_route: force the delete-side route (a
            ``policy.DELETE_METHODS`` entry; None = policy-routed by
            the delete-rate + tree-edge-ratio features). Benchmarks
            use this to compare routes on identical streams.
          name: label for introspection.
          device: pin the session to ONE device: host payloads
            device_put there, dynamic state allocated there, static
            solves/rebuilds dispatched there. This is the fleet's
            per-device shell mode (``repro.fleet`` packs many pinned
            sessions across a mesh); None keeps the process default.
        """
        if graph is None:
            if num_nodes is None:
                raise ValueError("Solver.open() needs a graph or "
                                 "num_nodes")
            g, n = None, int(num_nodes)
        else:
            with obs.span("solver.open", tenant=name):
                g = as_device_graph(graph, num_nodes,
                                    num_segments=num_segments)
            n = g.num_nodes
        return cls(g, n, lift_steps=lift_steps, num_segments=num_segments,
                   mesh=mesh, axis_names=axis_names,
                   policy_cache=policy_cache, scan_method=scan_method,
                   delete_route=delete_route, name=name, device=device)

    def _device_scope(self):
        """``jax.default_device`` context for a pinned session (a
        no-op context when unpinned) — wraps every path that CREATES
        device state (dynamic-state init, static solves), so a fleet
        shard's arrays land on its own device without per-array puts."""
        if self._device is None:
            return contextlib.nullcontext()
        import jax
        return jax.default_device(self._device)

    def graph(self) -> DeviceGraph:
        """The CURRENT edge set as a DeviceGraph: the dynamic log's
        surviving (compacted) view once the session has mutated, else
        the opened snapshot (an empty graph for bare sessions)."""
        if self._dyn is not None and self._dyn.log.rows > 0:
            return self._dyn.graph()
        if self._dyn is None and self._graph is not None:
            return self._graph
        if self._empty is None:
            self._empty = DeviceGraph.from_edges(
                np.zeros((0, 2), np.int32), self.num_nodes,
                name=self.name)
        return self._empty

    @property
    def num_edges(self) -> int:
        """Host-known edge count (no sync): inserted-edge total for a
        mutated session (an upper bound under churn — the policy's size
        feature, same contract as the registry), else the opened
        graph's true count."""
        if self._dyn is not None:
            return self._dyn.num_edges_inserted
        return self._graph.num_edges if self._graph is not None else 0

    # -- planning ------------------------------------------------------------

    def plan(self, method: str = "auto", *, backend: str | None = None,
             num_segments: int | None = None, **opts) -> ExecutionPlan:
        """Build the ``ExecutionPlan`` a ``solve()`` with the same
        arguments would run — the adaptive decision, inspectable before
        any device work. ``backend=`` forces a registry entry verbatim;
        a non-"auto" ``method`` maps to its same-named backend; "auto"
        asks the policy (autotune cache, then heuristic). Passing BOTH
        a named method and a backend is a conflict and raises."""
        plan = self._build_plan(method, backend=backend,
                                num_segments=num_segments, **opts)
        self.last_plan = plan
        return plan

    def _build_plan(self, method: str = "auto", *,
                    backend: str | None = None,
                    num_segments: int | None = None,
                    **opts) -> ExecutionPlan:
        if backend is not None and method not in (None, "auto"):
            raise ValueError(
                f"pass method={method!r} OR backend={backend!r}, not "
                "both — a forced backend must not silently reroute a "
                "named method")
        unknown = set(opts) - _KNOWN_OPTS
        if unknown:
            raise TypeError(
                f"unknown option(s) {sorted(unknown)}; per-call backend "
                f"options are {sorted(_KNOWN_OPTS)}")
        g = self.graph()
        num_segments = self.num_segments if num_segments is None \
            else num_segments
        # policy features come from the HOST-tracked edge count (true
        # count for static sessions, inserted total for streaming ones
        # — the same feature every mutation-path policy call uses), NOT
        # from the log view's stored row count, which is pow2 capacity
        # padding once the session has mutated
        n, e = self.num_nodes, self.num_edges
        if backend is not None:
            caps = get_backend(backend).capabilities   # validates early
            if caps.batched:
                raise ValueError(
                    f"backend {backend!r} runs fleets, not single "
                    "graphs — use Solver.solve_batch(graphs)")
            if caps.sharded and self.mesh is None:
                raise ValueError(
                    f"backend {backend!r} needs a mesh — open the "
                    "session with Solver.open(graph, mesh=...)")
            chosen, reason = backend, "forced"
        elif method not in (None, "auto"):
            # an explicitly forced method wins over the mesh default —
            # a mesh session must not silently reroute (or accept) a
            # named method
            if method not in _PLANNABLE:
                raise ValueError(f"unknown method {method!r}; choose "
                                 f"from {('auto',) + _PLANNABLE} or "
                                 "force a backend= from "
                                 "repro.api.BACKENDS")
            chosen, reason = method, "forced"
        elif self.mesh is not None:
            chosen, reason = "distributed", "sharded"
        else:
            # the skew feature rides the graph's static pytree metadata
            # (measured once at host ingest; None for device-resident
            # edge arrays) — it routes kron/soc-style graphs to the
            # sampled engine at scale and costs road-like graphs nothing
            chosen, reason = policy.select_static_explained(
                n, e, degree_skew=g.degree_skew,
                cache=self.policy_cache)
        seg = g.plan if num_segments is None else plan_segmentation(
            int(g.edges.shape[0]), n, num_segments)
        predicted = {"hook_ops_per_round": e,
                     "jump_ops_per_sweep": n,
                     "segments": seg.num_segments}
        if g.degree_skew is not None:
            predicted["degree_skew"] = round(float(g.degree_skew), 3)
        plan = ExecutionPlan(
            backend=chosen, reason=reason, num_nodes=n, num_edges=e,
            bucket=bucket_shape(n, e), segmentation=seg,
            lift_steps=self.lift_steps, num_segments=num_segments,
            graph=g,
            opts={"mesh": self.mesh, "axis_names": self.axis_names,
                  **opts},
            predicted=predicted)
        return plan

    # -- static solve --------------------------------------------------------

    def solve(self, method: str = "auto", *, backend: str | None = None,
              num_segments: int | None = None, **opts) -> CCResult:
        """Solve the current edge set; returns ``CCResult(labels,
        work)`` with canonical min-id labels. Routing == ``plan()``."""
        plan = self.plan(method, backend=backend,
                         num_segments=num_segments, **opts)
        with self._device_scope():
            if obs.enabled():
                with obs.span("solver.solve", tenant=self.name,
                              **plan.trace_tags()):
                    res = plan.run()
                # device scalars, kept unsynced until counters are read
                obs.count_device("solve.hook_rounds", res.work.hook_rounds)
                obs.count_device("solve.jump_sweeps", res.work.jump_sweeps)
            else:
                res = plan.run()
        self.stats["solves"] += 1
        self.last_method = plan.backend
        self._labels = res.labels
        return res

    def spanning_forest(self, method: str | None = None):
        """Labels PLUS the spanning forest the hook rounds record —
        ``ForestResult(labels, parents, work)`` where ``parents`` is
        int32 [V, 2]: row r holds the original graph edge whose hook
        retired root r, (-1, -1) for the one root per component (the
        component minimum). Exactly |V| - C rows are recorded and they
        form a spanning forest whose partition equals ``labels``
        (property-tested; ``connectivity.queries.spanning_forest_stats``
        validates one on device).

        ``method=None`` asks the policy and falls back to ``adaptive``
        when the chosen backend does not record a forest (capability
        ``spanning_forest``); forcing a non-recording method raises.

        The result is cached per method, keyed on the label VERSION at
        build time: an ``insert()`` whose absorb provably merged
        nothing (version unchanged) leaves the partition intact, and a
        spanning forest of the old edge set still spans the new one —
        the cache survives. ``delete()`` always invalidates: a deleted
        tree edge with a surviving replacement keeps the version
        unticked yet kills a cached forest edge."""
        from repro.core import cc as cc_mod
        if method is None:
            g = self.graph()
            chosen, _ = policy.select_static_explained(
                self.num_nodes, self.num_edges,
                degree_skew=g.degree_skew, cache=self.policy_cache)
            method = chosen if chosen in cc_mod.FOREST_METHODS \
                else "adaptive"
        if self._forest is not None and self._forest[0] == method \
                and self._forest[2] == self.version:
            return self._forest[1]
        with obs.span("solver.spanning_forest", tenant=self.name,
                      method=method):
            res = cc_mod.solve_forest(self.graph(), method=method,
                                      num_segments=self.num_segments)
        self._forest = (method, res, self.version)
        return res

    @classmethod
    def solve_batch(cls, graphs: Sequence, *,
                    num_segments: int | None = None,
                    lift_steps: int = 2) -> list[CCResult]:
        """Fleet solve through the ``batched`` backend: one device
        program per pow2 shape bucket, one ``CCResult`` per graph in
        input order, bit-identical to per-graph solves. ``lift_steps``
        has no effect (see ``open``)."""
        graphs = list(graphs)
        sizes = [(g.num_nodes, g.num_edges)
                 if hasattr(g, "num_nodes")
                 else (int(g[1]), int(np.asarray(g[0]).reshape(-1, 2)
                                      .shape[0]))
                 for g in graphs]
        n = max((s[0] for s in sizes), default=0)
        e = sum(s[1] for s in sizes)
        plan = ExecutionPlan(
            backend="batched", reason="forced", num_nodes=n, num_edges=e,
            bucket=bucket_shape(n, e), segmentation=None,
            lift_steps=lift_steps, num_segments=num_segments,
            graphs=graphs, predicted={"n_graphs": len(graphs)})
        return plan.run()

    # -- streaming mutation (policy-routed, transfer-free steady state) ------

    def _coerce(self, edges) -> DeviceGraph:
        """Host arrays are validated + device_put; DeviceGraphs pass
        through untouched (no sync — the caller owns bounds there)."""
        if isinstance(edges, DeviceGraph):
            if edges.num_nodes != self.num_nodes:
                raise ValueError(f"delta num_nodes {edges.num_nodes} != "
                                 f"{self.num_nodes}")
            return edges
        arr = np.asarray(edges, np.int32).reshape(-1, 2)
        validate_edge_bounds(arr, self.num_nodes)
        return DeviceGraph.from_edges(arr, self.num_nodes,
                                      name=self.name,
                                      device=self._device)

    @property
    def state(self):
        """The live dynamic engine (``DynamicCC``), created on first
        use via the ``dynamic`` backend's ``make_state`` — opening a
        session with edges routes that snapshot through the policy as
        its first (bulk) insert."""
        return self._ensure_dyn()

    def _ensure_dyn(self):
        if self._dyn is None:
            # pinned sessions allocate the dynamic state (labels, edge
            # log, forest) under their device scope: the init jits run
            # there, so the state commits to the shard's device and
            # every later mutation jit follows it — no per-tick puts
            with self._device_scope():
                self._dyn = get_backend("dynamic").make_state(
                    self.num_nodes, scan_method=self._scan_method)
            if obs.enabled():
                # span tracing on => carry the on-device Metrics pytree
                # through every mutation jit (still transfer-free; host
                # materialization only at metrics_summary())
                self._dyn.enable_metrics()
            seed, self._graph = self._graph, None
            if seed is not None and seed.num_edges:
                # the opened snapshot routes through the policy as the
                # session's first (bulk) insert — counted as one, so
                # inserts == absorbs + insert-side rebuilds stays true
                self.stats["inserts"] += 1
                self._route_insert(seed)
        return self._dyn

    def _rebuild(self, method: str) -> CCResult:
        """Static rebuild over the current (staged) edge set via the
        policy-chosen backend — the bulk-mutation route."""
        plan = self.plan(method)
        plan.reason = "policy"
        self.last_plan = plan
        with self._device_scope():
            return plan.run()

    def _route_insert(self, delta: DeviceGraph) -> None:
        dyn = self._dyn
        method = policy.select_for(self.num_nodes, self.num_edges, delta,
                                   cache=self.policy_cache)
        self.last_method = method
        if method == policy.INCREMENTAL_ABSORB:
            dyn.insert_graph(delta)
            self.stats["absorbs"] += 1
        else:
            # bulk load: the accumulated set is mostly this batch — the
            # chosen static engine (segmentation and all) beats hooking
            # a huge unsegmented delta through the absorb loop
            dyn.stage(delta)
            res = self._rebuild(method)
            dyn.adopt(res.labels, work=res.work,
                      num_edges=delta.num_edges)
            self.stats["rebuilds"] += 1

    def insert(self, edges):
        """Insert an edge batch (DeviceGraph or host array); returns
        the label version as a DEVICE scalar — the steady-state path
        never syncs (``int(...)`` it to observe). Routed by
        ``policy.select_for``: small delta → incremental absorb, bulk
        load → static rebuild + adopt."""
        delta = self._coerce(edges)
        self._ensure_dyn()
        self.stats["inserts"] += 1
        # the spanning-forest cache is NOT cleared here: it is keyed on
        # the label version, and an absorb that merged nothing leaves
        # the cached forest valid (see spanning_forest())
        with obs.span("solver.insert", tenant=self.name,
                      edges=delta.num_edges) as sp:
            self._route_insert(delta)
            sp.tag(route=self.last_method)
        return self._dyn.version_device

    def delete(self, edges):
        """Delete an edge batch (each row retires every alive copy of
        that undirected edge; absent rows are no-ops); returns the
        label version as a DEVICE scalar (never syncs). Routed by the
        delete-rate policy: small batch → tombstone + scoped recompute
        in ONE device program (version ticks iff a component actually
        split), bulk drop → static rebuild over the survivors."""
        delta = self._coerce(edges)
        dyn = self._ensure_dyn()
        self.stats["deletes"] += 1
        self._forest = None            # edge set changed: forest stale
        with obs.span("solver.delete", tenant=self.name,
                      edges=delta.num_edges) as sp:
            method = self._delete_route if self._delete_route is not None \
                else policy.select_for(self.num_nodes, self.num_edges,
                                       delta, delete=True,
                                       cache=self.policy_cache)
            self.last_method = method
            sp.tag(route=method)
            if method == policy.DYNAMIC_DELETE_FOREST:
                # tree-aware route (DESIGN.md §14): classify against
                # the maintained forest, short-circuit all-non-tree
                # batches, scope reconnection to split components
                dyn.delete_graph_forest(delta)
                self.stats["forest_deletes"] += 1
                self.stats["scoped_deletes"] += 1
            elif method in policy.DELETE_METHODS:
                if self._scan_method is None:
                    dyn.scan_method = "pallas_fused" \
                        if method == policy.DYNAMIC_DELETE_FUSED else "jnp"
                dyn.delete_graph(delta)
                self.stats["scoped_deletes"] += 1
            else:
                obs.count("dynamic.deletes.rebuild")
                dyn.tombstone_graph(delta)
                res = self._rebuild(method)
                dyn.adopt(res.labels, work=res.work)
                self.stats["rebuilds"] += 1
        return dyn.version_device

    # -- live state views ----------------------------------------------------

    @property
    def labels(self):
        """Canonical min-id labels for the current edge set (device).
        Mutated sessions read the live dynamic state; static sessions
        solve lazily (``method="auto"``) on first access — WITHOUT
        touching ``stats``/``last_method``/``last_plan`` (a property
        read must not look like a routing decision to introspection)."""
        if self._dyn is not None:
            return self._dyn.labels
        if self._labels is None:
            with self._device_scope():
                self._labels = self._build_plan().run().labels
        return self._labels

    @property
    def version(self) -> int:
        """Label version as a host int (syncs). Ticks exactly when a
        mutation changed the partition (merge or split)."""
        return self._dyn.version if self._dyn is not None else 0

    @property
    def version_device(self):
        """Label version as a device scalar (no sync)."""
        if self._dyn is not None:
            return self._dyn.version_device
        import jax.numpy as jnp
        return jnp.zeros((), jnp.int32)

    @property
    def work(self) -> dict:
        """Accumulated mutation work counters (host ints; syncs).
        Zeroed — not empty — before the first mutation, so counter
        reads never KeyError on a fresh session."""
        if self._dyn is not None:
            return self._dyn.work
        from repro.core.rounds import WorkCounters
        return {k: 0 for k in WorkCounters._fields}

    def enable_metrics(self) -> None:
        """Attach the on-device ``repro.obs`` Metrics accumulators to
        the dynamic engine (automatic when tracing was enabled before
        the first mutation; call this to opt in later). Device-only
        until ``metrics_summary()``."""
        self._ensure_dyn().enable_metrics()

    @property
    def metrics(self):
        """The live on-device ``Metrics`` pytree (None unless
        attached). Reading never syncs."""
        return self._dyn.metrics if self._dyn is not None else None

    def metrics_summary(self) -> dict | None:
        """Materialize the accumulators on the host (the one explicit
        sync, via the audited ``queries.to_host`` sink); None when no
        metrics are attached."""
        m = self.metrics
        if m is None:
            return None
        from repro.obs import metrics as obs_metrics
        return obs_metrics.flush(m)

    # -- queries (on-device kernels over the live labels) --------------------

    def _check_vertices(self, batch: np.ndarray) -> None:
        if batch.size and (batch.min() < 0
                           or batch.max() >= self.num_nodes):
            raise ValueError(
                f"vertex out of range [0, {self.num_nodes})")

    def same_component(self, pairs) -> np.ndarray:
        """bool [Q] for an int [Q, 2] pair batch (pow2-padded so every
        same-shape batch shares one jit cache entry)."""
        pairs = np.asarray(pairs, np.int32).reshape(-1, 2)
        self._check_vertices(pairs)
        q = pairs.shape[0]
        with obs.span("solver.query.same_component", tenant=self.name,
                      rows=q):
            return queries.to_host(queries.same_component(
                self.labels, pad_rows_pow2(pairs)))[:q]

    def connected(self, u: int, v: int) -> bool:
        """Scalar convenience over ``same_component``."""
        return bool(self.same_component([[u, v]])[0])

    def component_size(self, vertices) -> np.ndarray:
        """int32 [Q] component sizes for a vertex batch."""
        vertices = np.asarray(vertices, np.int32).reshape(-1)
        self._check_vertices(vertices)
        q = vertices.shape[0]
        with obs.span("solver.query.component_size", tenant=self.name,
                      rows=q):
            return queries.to_host(queries.component_size(
                self.labels, pad_rows_pow2(vertices)))[:q]

    def component_sizes(self):
        """int32 [V] size of every vertex's component (device)."""
        return queries.component_sizes(self.labels)

    def num_components(self) -> int:
        """Distinct-component count (one on-device sort/segment
        kernel — the single counting implementation every layer
        delegates to)."""
        with obs.span("solver.query.num_components", tenant=self.name):
            return int(queries.count_components(self.labels))

    def component_histogram(self) -> np.ndarray:
        """Components per power-of-two size bin."""
        with obs.span("solver.query.component_histogram",
                      tenant=self.name):
            return queries.to_host(
                queries.component_histogram(self.labels))

    def __repr__(self) -> str:
        mode = "dynamic" if self._dyn is not None else "static"
        return (f"Solver(name={self.name!r}, |V|={self.num_nodes}, "
                f"|E|~{self.num_edges}, mode={mode})")


def solve(graph, num_nodes: int | None = None, method: str = "auto", *,
          backend: str | None = None, num_segments: int | None = None,
          lift_steps: int = 2, mesh=None, axis_names=("data",),
          policy_cache: policy.AutotuneCache | None = None,
          **opts) -> CCResult:
    """One-shot facade solve: ``Solver.open(...).solve(...)``
    (``lift_steps`` has no effect; see ``Solver.open``)."""
    return Solver.open(graph, num_nodes, lift_steps=lift_steps,
                       num_segments=num_segments, mesh=mesh,
                       axis_names=axis_names,
                       policy_cache=policy_cache).solve(
        method, backend=backend, **opts)
