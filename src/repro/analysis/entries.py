"""Non-backend traceable entries + the entry collector.

``repro.api.backends`` registers one trace spec per execution backend;
this module adds the two surfaces the serving layer runs that are NOT
a backend's own program:

* **the service tick** — what ``ConnectivityService._run_mutations``
  stages per tenant per tick: coalesce payload graphs with
  ``DeviceGraph.concat``, bucket with ``pad_pow2``, absorb through
  ``_absorb_jit`` (inserts) or tombstone through ``_delete_jit``
  (deletes). The tick is the hottest transfer-free contract in the
  repo — a host sync here blocks every tenant in the slot;
* **the query kernels** — ``repro.connectivity.queries``; all four are
  contracted transfer-free (results are materialized only through the
  audited ``to_host`` sink, *after* the kernel).

``all_entries()`` is the one discovery point the runner and the tests
use: it imports the spec-bearing modules for their registration side
effects and returns every ``TraceEntry`` in name order.
"""
from __future__ import annotations

from repro.api.registry import (TraceEntry, VarInfo, register_trace_spec,
                                trace_entries)

_TF = frozenset({"transfer_free", "bucketed"})


@register_trace_spec("service")
def _service_specs():
    import jax
    import jax.numpy as jnp

    from repro.core import incremental as inc_mod
    from repro.core.segmentation import adaptive_num_segments
    from repro.graphs.device import DeviceGraph

    def build_insert_tick(v, e):
        half = max(e // 2, 8)

        def fn(pi, edges_a, edges_b, version):
            # two coalesced payloads, as _run_mutations stages them
            batch = DeviceGraph.concat([
                DeviceGraph.from_edges(edges_a, v),
                DeviceGraph.from_edges(edges_b, v),
            ]).pad_pow2()
            return inc_mod._absorb_jit(
                pi, batch.edges, batch.true_edges_device(), version)
        return (fn,
                (jax.ShapeDtypeStruct((v,), jnp.int32),
                 jax.ShapeDtypeStruct((half, 2), jnp.int32),
                 jax.ShapeDtypeStruct((half, 2), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32)),
                [VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1)),
                 VarInfo()])

    def build_delete_tick(v, e):
        d = max(e // 8, 8)

        def fn(edges, alive, pi, dels_a, dels_b, version, deleted):
            batch = DeviceGraph.concat([
                DeviceGraph.from_edges(dels_a, v),
                DeviceGraph.from_edges(dels_b, v),
            ]).pad_pow2()
            return inc_mod._delete_jit(
                edges, alive, pi, batch.edges,
                batch.true_edges_device(), version, deleted,
                num_segments=adaptive_num_segments(e, v),
                scan_method="jnp", interpret=True)
        return (fn,
                (jax.ShapeDtypeStruct((e, 2), jnp.int32),
                 jax.ShapeDtypeStruct((e,), jnp.bool_),
                 jax.ShapeDtypeStruct((v,), jnp.int32),
                 jax.ShapeDtypeStruct((d, 2), jnp.int32),
                 jax.ShapeDtypeStruct((d, 2), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32)),
                [VarInfo(range=(0, v - 1), padded=True),
                 VarInfo(mask=True),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(),
                 VarInfo()])

    def build_delete_forest_tick(v, e):
        d = max(e // 8, 8)

        def fn(edges, alive, pi, parents, parent_eidx, dels_a, dels_b,
               version, deleted, routes):
            batch = DeviceGraph.concat([
                DeviceGraph.from_edges(dels_a, v),
                DeviceGraph.from_edges(dels_b, v),
            ]).pad_pow2()
            return inc_mod._delete_forest_jit(
                edges, alive, pi, parents, parent_eidx, batch.edges,
                batch.true_edges_device(), version, deleted, routes)
        return (fn,
                (jax.ShapeDtypeStruct((e, 2), jnp.int32),
                 jax.ShapeDtypeStruct((e,), jnp.bool_),
                 jax.ShapeDtypeStruct((v,), jnp.int32),
                 jax.ShapeDtypeStruct((v, 2), jnp.int32),
                 jax.ShapeDtypeStruct((v,), jnp.int32),
                 jax.ShapeDtypeStruct((d, 2), jnp.int32),
                 jax.ShapeDtypeStruct((d, 2), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((2,), jnp.int32)),
                [VarInfo(range=(0, v - 1), padded=True),
                 VarInfo(mask=True),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(range=(-1, v - 1)),
                 VarInfo(range=(-1, e - 1)),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(),
                 VarInfo(),
                 VarInfo()])

    return [TraceEntry("service.tick.insert", build_insert_tick, _TF),
            TraceEntry("service.tick.delete", build_delete_tick, _TF),
            TraceEntry("service.tick.delete_forest",
                       build_delete_forest_tick, _TF)]


@register_trace_spec("obs")
def _obs_specs():
    """The INSTRUMENTED service tick: the PR-7 telemetry contract that
    carrying the ``repro.obs`` Metrics pytree through the mutation jits
    keeps the steady-state tick transfer-free. Same staging as the
    ``service.tick.*`` entries plus the ``record_mutation`` fold — if
    the metrics update ever grows a host sync or a callback, the
    ``transfer`` pass flags it here."""
    import jax
    import jax.numpy as jnp

    from repro.core import incremental as inc_mod
    from repro.core.segmentation import adaptive_num_segments
    from repro.graphs.device import DeviceGraph
    from repro.obs import metrics as obs_metrics

    n_slots = 16                          # Metrics.counts leading dim
    n_kinds = len(obs_metrics.HIST_KINDS)
    n_bins = obs_metrics.WORK_SPEC.num_bins

    def metrics_args():
        return ((jax.ShapeDtypeStruct((n_slots,), jnp.int32),
                 jax.ShapeDtypeStruct((n_kinds, n_bins), jnp.int32)),
                [VarInfo(), VarInfo()])

    def build_insert_tick(v, e):
        half = max(e // 2, 8)
        (m_avals, m_infos) = metrics_args()

        def fn(pi, edges_a, edges_b, version, counts, hist):
            metrics = obs_metrics.Metrics(counts, hist)
            batch = DeviceGraph.concat([
                DeviceGraph.from_edges(edges_a, v),
                DeviceGraph.from_edges(edges_b, v),
            ]).pad_pow2()
            true_count = batch.true_edges_device()
            pi1, version1, work = inc_mod._absorb_jit(
                pi, batch.edges, true_count, version)
            metrics = obs_metrics.record_mutation(
                metrics, work, true_count, version, version1,
                kind="insert")
            return pi1, version1, metrics
        return (fn,
                (jax.ShapeDtypeStruct((v,), jnp.int32),
                 jax.ShapeDtypeStruct((half, 2), jnp.int32),
                 jax.ShapeDtypeStruct((half, 2), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32)) + m_avals,
                [VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1)),
                 VarInfo()] + m_infos)

    def build_delete_tick(v, e):
        d = max(e // 8, 8)
        (m_avals, m_infos) = metrics_args()

        def fn(edges, alive, pi, dels, version, deleted, counts, hist):
            metrics = obs_metrics.Metrics(counts, hist)
            batch = DeviceGraph.from_edges(dels, v).pad_pow2()
            true_count = batch.true_edges_device()
            pi1, alive1, version1, deleted1, work = inc_mod._delete_jit(
                edges, alive, pi, batch.edges, true_count, version,
                deleted, num_segments=adaptive_num_segments(e, v),
                scan_method="jnp", interpret=True)
            metrics = obs_metrics.record_mutation(
                metrics, work, true_count, version, version1,
                kind="delete")
            return pi1, alive1, version1, deleted1, metrics
        return (fn,
                (jax.ShapeDtypeStruct((e, 2), jnp.int32),
                 jax.ShapeDtypeStruct((e,), jnp.bool_),
                 jax.ShapeDtypeStruct((v,), jnp.int32),
                 jax.ShapeDtypeStruct((d, 2), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32)) + m_avals,
                [VarInfo(range=(0, v - 1), padded=True),
                 VarInfo(mask=True),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1)),
                 VarInfo(),
                 VarInfo()] + m_infos)

    return [TraceEntry("obs.tick.insert", build_insert_tick, _TF),
            TraceEntry("obs.tick.delete", build_delete_tick, _TF)]


@register_trace_spec("queries")
def _query_specs():
    import jax
    import jax.numpy as jnp

    from repro.connectivity import queries as q

    def labels_arg(v):
        return (jax.ShapeDtypeStruct((v,), jnp.int32),
                VarInfo(range=(0, v - 1)))

    def build_same_component(v, e):
        la, li = labels_arg(v)
        nq = max(e // 16, 8)

        def fn(labels, pairs):
            return q.same_component(labels, pairs)
        return (fn, (la, jax.ShapeDtypeStruct((nq, 2), jnp.int32)),
                [li, VarInfo(range=(0, v - 1), padded=True)])

    def build_component_size(v, e):
        la, li = labels_arg(v)
        nq = max(e // 16, 8)

        def fn(labels, vertices):
            return q.component_size(labels, vertices)
        return (fn, (la, jax.ShapeDtypeStruct((nq,), jnp.int32)),
                [li, VarInfo(range=(0, v - 1), padded=True)])

    def build_count_components(v, e):
        la, li = labels_arg(v)

        def fn(labels):
            return q.count_components(labels)
        return fn, (la,), [li]

    def build_component_histogram(v, e):
        la, li = labels_arg(v)

        def fn(labels):
            return q.component_histogram(labels)
        return fn, (la,), [li]

    def build_forest_stats(v, e):
        la, li = labels_arg(v)

        def fn(labels, parents):
            return q.spanning_forest_stats(labels, parents)
        # parents rows carry -1 sentinels for roots, hence the -1 floor
        return (fn, (la, jax.ShapeDtypeStruct((v, 2), jnp.int32)),
                [li, VarInfo(range=(-1, v - 1))])

    return [
        TraceEntry("queries.same_component", build_same_component, _TF),
        TraceEntry("queries.component_size", build_component_size, _TF),
        TraceEntry("queries.count_components", build_count_components, _TF),
        TraceEntry("queries.component_histogram",
                   build_component_histogram, _TF),
        TraceEntry("queries.spanning_forest_stats", build_forest_stats,
                   _TF),
    ]


@register_trace_spec("fleet")
def _fleet_specs():
    """The fleet's pipelined query phase (DESIGN.md §15): the
    cross-tenant BATCHED kernels ``repro.fleet.engine`` dispatches —
    one stacked program answering a query kind for a whole same-|V|
    tenant group. The mutation phase is deliberately absent here: the
    fleet reuses ``ConnectivityService._run_mutations`` verbatim, so
    the ``service.tick.*`` entries above already pin it. The 4-tenant
    stack mirrors the engine's (kind, |V|) grouping; the batch rows
    are pow2-padded (``padded=True``) exactly as ``_dispatch_batched``
    stages them, and a host sync creeping into the stacked vmap would
    surface in the ``transfer`` pass against these entries."""
    import jax
    import jax.numpy as jnp

    from repro.fleet.engine import _batched_query_jit

    n_tenants = 4

    def build_batched_same_component(v, e):
        qb = max(e // 16, 8)

        def fn(labels, batch):
            return _batched_query_jit(labels, batch,
                                      kind="same_component")
        return (fn,
                (jax.ShapeDtypeStruct((n_tenants, v), jnp.int32),
                 jax.ShapeDtypeStruct((n_tenants, qb, 2), jnp.int32)),
                [VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1), padded=True)])

    def build_batched_component_size(v, e):
        qb = max(e // 16, 8)

        def fn(labels, batch):
            return _batched_query_jit(labels, batch,
                                      kind="component_size")
        return (fn,
                (jax.ShapeDtypeStruct((n_tenants, v), jnp.int32),
                 jax.ShapeDtypeStruct((n_tenants, qb), jnp.int32)),
                [VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1), padded=True)])

    return [TraceEntry("fleet.query.same_component",
                       build_batched_same_component, _TF),
            TraceEntry("fleet.query.component_size",
                       build_batched_component_size, _TF)]


def all_entries() -> list:
    """Every registered ``TraceEntry`` (backends + service + queries +
    fleet), importing the spec-bearing modules for their side
    effects."""
    import repro.api.backends  # noqa: F401  — registers backend specs
    return trace_entries()
