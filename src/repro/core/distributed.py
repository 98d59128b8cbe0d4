"""Multi-device Connected Components via ``shard_map``.

Spatial reinterpretation of the paper's segmentation (DESIGN.md §5):

  * edges are sharded over the mesh's data-parallel axes — each chip owns
    an edge partition (a "segment" in the paper's vocabulary);
  * the parent array π (the |V| workspace) is replicated;
  * each round every chip hooks its own segment (scatter-min over flat
    labels: π is identity or compressed at each round's start, and the
    segment scan compresses after every segment hook), the per-chip π
    copies are merged with an elementwise
    ``pmin`` all-reduce — valid because scatter-min updates are monotone
    decreasing, so the elementwise min of per-chip results equals the
    result of hooking the union of the segments — then every chip runs the
    identical fused Multi-Jump compress;
  * convergence (all local edges consistent) is combined with a global
    ``pmin`` so the device-side while loop terminates simultaneously
    everywhere. The entire multi-round program is ONE jit call: zero
    host round-trips, the paper's device-centric property preserved
    across a pod.

Scale posture: replicated π costs |V|·4 bytes per chip (4 GB at |V|=1e9);
beyond that the design shards π over 'model' and turns the pmin into a
reduce-scatter + all-gather pair. That variant is sketched in
EXPERIMENTS.md §Perf; the replicated form is what ships here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental.shard_map import shard_map

from repro.core import rounds as rounds_lib
from repro.core.rounds import WorkCounters, compress, edges_consistent
from repro.core.segmentation import plan_segmentation

# Global merge rounds to convergence measured on all four Table I graph
# classes: 2-4 (EXPERIMENTS.md §Perf). Fuel 8 is a 2x safety margin; the
# roofline's static loop bound (and the worst case) tightens 8x vs the
# original 64 fuel.
_MAX_ROUNDS = 8


def build_distributed_cc(graph, mesh: Mesh,
                         axis_names: tuple[str, ...] = ("data",),
                         local_segments: int | None = None):
    """Build a jitted distributed-CC callable for a sharded DeviceGraph
    (engine entry for the facade's ``distributed`` backend; callers
    should go through ``repro.api.Solver.open(graph, mesh=mesh)``).

    Args:
      graph: a ``DeviceGraph`` already sharded over ``mesh`` via
        ``DeviceGraph.shard(mesh, axis_names)`` — its (padded) edge
        array divides evenly into per-chip partitions. The callable is
        specialized to this graph's static shape/plan; run it on the
        graph itself or any same-shape sharded DeviceGraph.
      mesh: device mesh; edges are sharded over ``axis_names`` (flattened).
      axis_names: mesh axes the edge list is sharded over.
      local_segments: per-chip segmentation (None = paper heuristic on the
        per-chip subproblem).

    Returns:
      fn(graph: DeviceGraph) -> labels [V] (replicated).
    """
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    num_nodes = graph.num_nodes
    total = int(graph.edges.shape[0])
    if total % n_shards:
        raise ValueError(
            f"edge count {total} does not divide into {n_shards} shards; "
            "shard the graph with DeviceGraph.shard(mesh, axis_names)")
    edges_per_shard = total // n_shards
    segs = local_segments or plan_segmentation(
        edges_per_shard, num_nodes).num_segments
    segs = max(1, min(segs, edges_per_shard))
    # per-chip plan; the paper's segment scan over the local partition is
    # the shared rounds core (padding with (0,0) no-ops — the old local
    # scan silently truncated the remainder when edges_per_shard wasn't
    # divisible by the segment count).
    plan = plan_segmentation(edges_per_shard, num_nodes, segs)
    ops = rounds_lib.jnp_round_ops()

    def shard_fn(edges_local):
        # edges_local: [1 per sharded axis..., edges_per_shard, 2]
        edges_local = edges_local.reshape(edges_per_shard, 2)
        segments = rounds_lib.pad_and_segment(edges_local, plan)
        pi0 = jnp.arange(num_nodes, dtype=jnp.int32)

        def cond(state):
            _, done, rounds = state
            return jnp.logical_and(~done, rounds < _MAX_ROUNDS)

        def body(state):
            pi, _, rounds = state
            pi, _ = rounds_lib.segment_scan(pi, segments, ops,
                                            WorkCounters.zeros())
            # merge the monotone per-chip workspaces
            for ax in axis_names:
                pi = jax.lax.pmin(pi, ax)
            pi, _ = compress(pi, WorkCounters.zeros())
            local_ok = edges_consistent(pi, edges_local)
            ok = jnp.asarray(local_ok, jnp.int32)
            for ax in axis_names:
                ok = jax.lax.pmin(ok, ax)
            return pi, ok.astype(bool), rounds + 1

        pi, _, _ = jax.lax.while_loop(
            cond, body, (pi0, jnp.asarray(False), jnp.zeros((), jnp.int32)))
        return pi[None]  # leading axis collapses to the replicated out-spec

    in_spec = P(axis_names if len(axis_names) > 1 else axis_names[0], None)
    fn = shard_map(shard_fn, mesh=mesh, in_specs=(in_spec,),
                   out_specs=P(axis_names if len(axis_names) > 1
                               else axis_names[0], None),
                   check_rep=False)

    def run(edges_sharded):
        out = fn(edges_sharded)          # [n_shards, V] identical rows
        return out[0]

    jitted = jax.jit(run)

    def call(g):
        from repro.graphs.device import as_device_graph
        return jitted(as_device_graph(g, num_nodes).edges)

    # the raw edges-level entry point ([n_shards*edges_per_shard, 2] ->
    # labels), for AOT lowering over ShapeDtypeStructs (launch.dryrun)
    call.on_edges = jitted
    return call


class DistributedRunnerCache:
    """Per-shape cache of ``build_distributed_cc`` callables.

    ``build_distributed_cc`` specializes to one (padded-rows, |V|)
    shape and is reusable on any same-shape sharded DeviceGraph — a
    property the fleet's sharded-tenant path leans on hard: a tenant's
    tombstone log re-solves after every mutated tick over a view whose
    pow2 capacity changes only on growth, so the builder (shard_map
    construction + jit entry) amortizes to one per shape bucket instead
    of one per tick. Host-side dict only; hit/miss counters ride in
    ``stats`` for the fleet benchmark."""

    def __init__(self, mesh: Mesh, axis_names=("data",)):
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self._runners: dict = {}
        self.stats = {"hits": 0, "misses": 0}

    def runner(self, graph):
        """The cached callable for this graph's (rows, |V|) bucket —
        the graph must already be sharded over the cache's mesh."""
        key = (int(graph.edges.shape[0]), graph.num_nodes)
        fn = self._runners.get(key)
        if fn is None:
            self.stats["misses"] += 1
            fn = self._runners[key] = build_distributed_cc(
                graph, self.mesh, axis_names=self.axis_names)
        else:
            self.stats["hits"] += 1
        return fn

    def run(self, graph):
        """labels [V] (replicated) for a sharded DeviceGraph."""
        return self.runner(graph)(graph)

    def solve(self, graph):
        """Shard an unsharded DeviceGraph over the mesh, then run."""
        return self.run(graph.shard(self.mesh, self.axis_names))


def solve_distributed(graph, mesh: Mesh, axis_names=("data",)):
    """Shard a graph (host ``Graph``, raw arrays, or an unsharded
    ``DeviceGraph``) over ``mesh`` and run (engine entry for the
    facade's ``distributed`` backend)."""
    from repro.graphs.device import as_device_graph
    dg = as_device_graph(graph).shard(mesh, axis_names)
    fn = build_distributed_cc(dg, mesh, axis_names=axis_names)
    return fn(dg)


def make_distributed_cc(graph, mesh: Mesh,
                        axis_names: tuple[str, ...] = ("data",),
                        lift_steps: int = 2,
                        local_segments: int | None = None):
    """DEPRECATED legacy entrypoint — forwards to the engine builder
    the facade's ``distributed`` backend uses. ``lift_steps`` is
    accepted and has no effect: every engine hooks flat labels."""
    from repro._deprecation import warn_once
    warn_once("repro.core.distributed.make_distributed_cc",
              "repro.api.Solver.open(graph, mesh=mesh)")
    del lift_steps
    return build_distributed_cc(graph, mesh, axis_names=axis_names,
                                local_segments=local_segments)


def distributed_connected_components(graph, mesh: Mesh,
                                     axis_names=("data",),
                                     lift_steps: int = 2):
    """DEPRECATED legacy entrypoint — forwards through the facade's
    ``distributed`` backend, bit-identical results."""
    from repro._deprecation import warn_once
    from repro.api import Solver
    warn_once("repro.core.distributed.distributed_connected_components",
              "repro.api.Solver.open(graph, mesh=mesh).solve()")
    res = Solver.open(graph, mesh=mesh, axis_names=axis_names,
                      lift_steps=lift_steps).solve()
    return res.labels
