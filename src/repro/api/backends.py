"""The built-in backends — every execution mode, one decorator each.

This module is the whole wiring between the facade and the engines:
each backend is a ``@register_backend`` declaration plus a few lines
delegating to the engine entry in ``repro.core``. Adding an execution
mode to the stack = adding one block here (see DESIGN.md §10 for the
generated capability matrix).

Counter semantics: backends with ``bit_exact_counters=True`` return
exact true-work ``WorkCounters`` (padding never billed); the fused
Pallas backend's are additionally bit-identical to the jnp adaptive
composition (the conformance matrix holds it to that). The per-round
Pallas, hostloop, and distributed backends return labels with
zero/partial counters — their value is wall-clock/launch-count
comparison, not work billing.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.api.plan import ExecutionPlan
from repro.api.registry import (Capabilities, TraceEntry, VarInfo,
                                register_backend, register_trace_spec)
from repro.core import batch as batch_mod
from repro.core import cc as cc_mod
from repro.core import distributed as dist_mod
from repro.core.cc import CCResult
from repro.core.incremental import DynamicCC, IncrementalCC
from repro.core.rounds import WorkCounters

__all__ = []            # nothing public; importing registers everything


# ---------------------------------------------------------------------------
# Single-graph jnp variants (the paper's Fig. 5 ladder)
# ---------------------------------------------------------------------------

def _register_jnp_variant(method: str) -> None:
    @register_backend(
        method,
        Capabilities(static=True, bit_exact_counters=True,
                     spanning_forest=method in cc_mod.FOREST_METHODS))
    def _run(plan: ExecutionPlan, _method=method) -> CCResult:
        return cc_mod.solve_static(plan.graph, method=_method,
                                   num_segments=plan.num_segments)


for _m in cc_mod.METHODS:       # soman multijump atomic_hook adaptive labelprop
    _register_jnp_variant(_m)


# ---------------------------------------------------------------------------
# Pallas kernel backends
# ---------------------------------------------------------------------------

@register_backend("pallas_fused",
                  Capabilities(static=True, bit_exact_counters=True))
def _pallas_fused(plan: ExecutionPlan) -> CCResult:
    """The whole Fig. 4 segment scan in ONE pallas_call (DESIGN.md §8);
    labels AND counters bit-identical to the jnp adaptive composition."""
    return cc_mod.solve_static(plan.graph, method=cc_mod.FUSED_METHOD,
                               num_segments=plan.num_segments)


@register_backend("pallas", Capabilities(static=True,
                                         bit_exact_counters=False))
def _pallas_per_round(plan: ExecutionPlan) -> CCResult:
    """Per-round Pallas kernels (one launch per segment hook / compress
    sweep). Labels only — counters are zeros by contract."""
    labels = cc_mod.solve_pallas(plan.graph,
                                 num_segments=plan.num_segments,
                                 interpret=plan.opts.get("interpret"))
    return CCResult(labels, WorkCounters.zeros())


# ---------------------------------------------------------------------------
# Sampling-accelerated backends (k-out / Afforest-style; DESIGN.md §13)
# ---------------------------------------------------------------------------

def _run_sampled(plan: ExecutionPlan, fused: bool) -> CCResult:
    from repro.core import sampled as sampled_mod
    res = sampled_mod.solve_sampled(plan.graph,
                                    num_segments=plan.num_segments,
                                    fused=fused,
                                    interpret=plan.opts.get("interpret"))
    # phase-split telemetry, left as device scalars: the solve path
    # syncs nothing, a reader pays for the values it reads
    plan.artifacts["sampled_stats"] = dict(res.stats)
    return CCResult(res.labels, res.work)


@register_backend("sampled",
                  Capabilities(static=True, bit_exact_counters=True,
                               spanning_forest=True))
def _sampled(plan: ExecutionPlan) -> CCResult:
    """k-out sampling phase collapses the giant component, then the
    adaptive Fig. 4 scan covers the residue only (Hong et al.). The
    sampled-vs-residue work split lands in
    ``plan.artifacts["sampled_stats"]``."""
    return _run_sampled(plan, fused=False)


@register_backend("sampled_fused",
                  Capabilities(static=True, bit_exact_counters=True))
def _sampled_fused(plan: ExecutionPlan) -> CCResult:
    """``sampled`` with the residue scan routed through the fused
    Pallas kernel (one launch per scan). The kernel does not record
    forest edges, so this variant does not claim ``spanning_forest``."""
    return _run_sampled(plan, fused=True)


# ---------------------------------------------------------------------------
# Host-driven baseline loop (benchmarking: the GPU baseline's syncs)
# ---------------------------------------------------------------------------

@register_backend("hostloop", Capabilities(static=True, device_loop=False,
                                           bit_exact_counters=False))
def _hostloop(plan: ExecutionPlan) -> CCResult:
    """Soman/multijump under HOST control flow — one device round trip
    per convergence check. The raw loop stats land in
    ``plan.artifacts["hostloop_stats"]``."""
    g = plan.graph
    t = g.true_edges_static
    edges = np.asarray(g.edges)
    if t is not None:
        edges = edges[:t]
    labels, stats = cc_mod.solve_hostloop(
        edges, g.num_nodes,
        method=plan.opts.get("hostloop_method", "soman"))
    plan.artifacts["hostloop_stats"] = stats
    work = WorkCounters.zeros().add(
        hook_rounds=stats["hook_rounds"], jump_sweeps=stats["jump_sweeps"],
        sync_rounds=stats["sync_rounds"])
    return CCResult(jnp.asarray(labels), work)


# ---------------------------------------------------------------------------
# Batched engine (many graphs, one device program per shape bucket)
# ---------------------------------------------------------------------------

@register_backend("batched", Capabilities(static=True, batched=True,
                                          bit_exact_counters=True))
def _batched(plan: ExecutionPlan) -> list[CCResult]:
    """Shape-bucketed vmapped engine; one ``CCResult`` per input graph,
    bit-identical to per-graph adaptive runs."""
    return batch_mod.solve_batched(plan.graphs,
                                   num_segments=plan.num_segments)


# ---------------------------------------------------------------------------
# Streaming engines (live state via make_state)
# ---------------------------------------------------------------------------

@register_backend("incremental",
                  Capabilities(static=True, streaming=True,
                               bit_exact_counters=True))
class _Incremental:
    """Insert-only streaming engine (Hong et al.; DESIGN.md §6)."""

    def make_state(self, num_nodes: int, *, lift_steps: int = 2,
                   scan_method: str | None = None) -> IncrementalCC:
        """``lift_steps`` is accepted and has no effect: every engine
        hooks flat labels."""
        return IncrementalCC(num_nodes)

    def run(self, plan: ExecutionPlan) -> CCResult:
        state = self.make_state(plan.num_nodes)
        state.insert_graph(plan.graph)
        return CCResult(state.labels, WorkCounters(**state.work))


@register_backend("dynamic",
                  Capabilities(static=True, streaming=True, deletions=True,
                               bit_exact_counters=True,
                               maintained_forest=True))
class _Dynamic:
    """Fully-dynamic engine: tombstone log + scoped recompute
    (DESIGN.md §9). ``Solver`` sessions get their live state here."""

    def make_state(self, num_nodes: int, *, lift_steps: int = 2,
                   scan_method: str | None = None) -> DynamicCC:
        """``lift_steps`` is accepted and has no effect: every engine
        hooks flat labels."""
        return DynamicCC(num_nodes, scan_method=scan_method or "jnp")

    def run(self, plan: ExecutionPlan) -> CCResult:
        state = self.make_state(plan.num_nodes)
        state.insert_graph(plan.graph)
        return CCResult(state.labels, WorkCounters(**state.work))


# ---------------------------------------------------------------------------
# Distributed engine (spatial segmentation across a mesh)
# ---------------------------------------------------------------------------

@register_backend("distributed",
                  Capabilities(static=True, sharded=True,
                               bit_exact_counters=False))
def _distributed(plan: ExecutionPlan) -> CCResult:
    """shard_map engine over the plan's mesh (DESIGN.md §5). Labels
    only — per-chip counters are not folded globally."""
    mesh = plan.opts.get("mesh")
    if mesh is None:
        raise ValueError("the distributed backend needs a mesh "
                         "(Solver.open(graph, mesh=...))")
    labels = dist_mod.solve_distributed(
        plan.graph, mesh,
        axis_names=plan.opts.get("axis_names", ("data",)))
    return CCResult(labels, WorkCounters.zeros())


# ---------------------------------------------------------------------------
# Traceable entry specs — one per backend (repro.analysis; DESIGN.md §11)
# ---------------------------------------------------------------------------
# Each spec closes the backend's device program over symbolic shape
# buckets (ShapeDtypeStructs — no data is allocated) so the static
# analyzer can hold it to its contracts: transfer-freedom on tick
# paths, int32 range safety at scale-tier shapes, pow2 bucketing, and
# padding-mask discipline. Builders construct DeviceGraphs INSIDE the
# traced function so the flat argument list aligns 1:1 with VarInfo.

def _graph_fn_build(v: int, e: int, run):
    """Shared builder for entries of shape fn(edges, true_edges)."""
    import jax

    from repro.core.segmentation import (adaptive_num_segments,
                                         plan_segmentation)
    from repro.graphs.device import DeviceGraph
    plan = plan_segmentation(e, v, adaptive_num_segments(e, v))

    def fn(edges, true_edges):
        return run(DeviceGraph(edges, v, true_edges, plan))

    args = (jax.ShapeDtypeStruct((e, 2), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    info = [VarInfo(range=(0, v - 1), padded=True),
            VarInfo(range=(0, e), mask=True)]
    return fn, args, info


def _static_solve_entry(method: str) -> TraceEntry:
    def build(v, e, _method=method):
        return _graph_fn_build(
            v, e, lambda g: cc_mod.solve_static(g, method=_method))
    return TraceEntry(name=f"backend.{method}", build=build,
                      backend=method)


@register_trace_spec("static")
def _static_specs():
    return [_static_solve_entry(m)
            for m in cc_mod.METHODS + (cc_mod.FUSED_METHOD,)]


@register_trace_spec("sampled")
def _sampled_specs():
    import jax

    from repro.core import sampled as sampled_mod
    from repro.core.segmentation import adaptive_num_segments

    def build_sample(v, e):
        def fn(edges, true_edges):
            return sampled_mod._sample_phase_jit(
                edges, true_edges, num_nodes=v, k=sampled_mod.SAMPLE_K,
                sample_rounds=sampled_mod.SAMPLE_ROUNDS, forest=False)
        return (fn, (jax.ShapeDtypeStruct((e, 2), jnp.int32),
                     jax.ShapeDtypeStruct((), jnp.int32)),
                [VarInfo(range=(0, v - 1), padded=True),
                 VarInfo(range=(0, e), mask=True)])

    def residue_build(v, e, fused):
        # the labels-only programs the backends run: work starts fresh
        # inside the trace (scalar constants only); pi arrives as the
        # sampling phase's label array
        def fn(edges, true_edges, pi):
            return sampled_mod._residue_scan_jit(
                edges, true_edges, pi, None, WorkCounters.zeros(),
                num_nodes=v, num_segments=adaptive_num_segments(e, v),
                fused=fused, interpret=True, forest=False)
        return (fn, (jax.ShapeDtypeStruct((e, 2), jnp.int32),
                     jax.ShapeDtypeStruct((), jnp.int32),
                     jax.ShapeDtypeStruct((v,), jnp.int32)),
                [VarInfo(range=(0, v - 1), padded=True),
                 VarInfo(range=(0, e), mask=True),
                 VarInfo(range=(0, v - 1))])

    return [TraceEntry(name="backend.sampled.sample_phase",
                       build=build_sample, backend="sampled"),
            TraceEntry(name="backend.sampled.residue",
                       build=lambda v, e: residue_build(v, e, False),
                       backend="sampled"),
            TraceEntry(name="backend.sampled_fused.residue",
                       build=lambda v, e: residue_build(v, e, True),
                       backend="sampled_fused")]


@register_trace_spec("pallas")
def _pallas_specs():
    def build(v, e):
        fn, args, info = _graph_fn_build(
            v, e, lambda g: cc_mod.solve_pallas(g))
        return fn, args, info
    return [TraceEntry(name="backend.pallas", build=build,
                       backend="pallas")]


@register_trace_spec("hostloop")
def _hostloop_specs():
    # the hostloop backend is CONTRACTED to sync (device_loop=False);
    # its per-step device programs still must stage cleanly, so each
    # step is its own entry without the transfer_free contract
    import jax

    def build_hook(v, e):
        def fn(pi, edges):
            return cc_mod._host_hook(pi, edges)
        return (fn, (jax.ShapeDtypeStruct((v,), jnp.int32),
                     jax.ShapeDtypeStruct((e, 2), jnp.int32)),
                [VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1), padded=True)])

    def build_jump(v, e):
        def fn(pi):
            return cc_mod._host_jump(pi)
        return (fn, (jax.ShapeDtypeStruct((v,), jnp.int32),),
                [VarInfo(range=(0, v - 1))])

    def build_compress(v, e):
        def fn(pi):
            return cc_mod._host_compress(pi)
        return (fn, (jax.ShapeDtypeStruct((v,), jnp.int32),),
                [VarInfo(range=(0, v - 1))])

    bucketed = frozenset({"bucketed"})
    return [TraceEntry("backend.hostloop.hook", build_hook, bucketed,
                       backend="hostloop"),
            TraceEntry("backend.hostloop.jump", build_jump, bucketed,
                       backend="hostloop"),
            TraceEntry("backend.hostloop.compress", build_compress,
                       bucketed, backend="hostloop")]


@register_trace_spec("batched")
def _batched_specs():
    def build(v, e, batch=4):
        import jax
        per = max(e // batch, 8)

        def fn(edges, true_edges, true_nodes):
            return batch_mod._cc_batched_jit(
                edges, true_edges, true_nodes, num_nodes=v,
                num_segments=None)
        return (fn,
                (jax.ShapeDtypeStruct((batch, per, 2), jnp.int32),
                 jax.ShapeDtypeStruct((batch,), jnp.int32),
                 jax.ShapeDtypeStruct((batch,), jnp.int32)),
                [VarInfo(range=(0, v - 1), padded=True),
                 VarInfo(range=(0, per), mask=True),
                 VarInfo(range=(0, v), mask=True)])
    return [TraceEntry(name="backend.batched", build=build,
                       backend="batched")]


@register_trace_spec("incremental")
def _incremental_specs():
    from repro.core import incremental as inc_mod

    def build(v, e):
        import jax

        def fn(pi, new_edges, true_count, version):
            return inc_mod._absorb_jit(pi, new_edges, true_count,
                                       version)
        return (fn,
                (jax.ShapeDtypeStruct((v,), jnp.int32),
                 jax.ShapeDtypeStruct((e, 2), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32)),
                [VarInfo(range=(0, v - 1)),
                 VarInfo(range=(0, v - 1), padded=True),
                 VarInfo(range=(0, e), mask=True),
                 VarInfo()])
    return [TraceEntry(name="backend.incremental.absorb", build=build,
                       backend="incremental")]


def _delete_build(v: int, e: int, scan_method: str):
    import jax

    from repro.core import incremental as inc_mod
    from repro.core.segmentation import adaptive_num_segments
    d = max(e // 4, 8)

    def fn(edges, alive, pi, dels, d_true, version, deleted):
        return inc_mod._delete_jit(
            edges, alive, pi, dels, d_true, version, deleted,
            num_segments=adaptive_num_segments(e, v),
            scan_method=scan_method, interpret=True)
    return (fn,
            (jax.ShapeDtypeStruct((e, 2), jnp.int32),
             jax.ShapeDtypeStruct((e,), jnp.bool_),
             jax.ShapeDtypeStruct((v,), jnp.int32),
             jax.ShapeDtypeStruct((d, 2), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32)),
            [VarInfo(range=(0, v - 1), padded=True),
             VarInfo(mask=True),
             VarInfo(range=(0, v - 1)),
             VarInfo(range=(0, v - 1), padded=True),
             VarInfo(range=(0, d), mask=True),
             VarInfo(),
             VarInfo()])


def _absorb_forest_build(v: int, e: int):
    import jax

    from repro.core import incremental as inc_mod

    def fn(pi, parents, parent_eidx, new_edges, eid_base, true_count,
           version):
        return inc_mod._absorb_forest_jit(
            pi, parents, parent_eidx, new_edges, eid_base, true_count,
            version)
    return (fn,
            (jax.ShapeDtypeStruct((v,), jnp.int32),
             jax.ShapeDtypeStruct((v, 2), jnp.int32),
             jax.ShapeDtypeStruct((v,), jnp.int32),
             jax.ShapeDtypeStruct((e, 2), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32)),
            [VarInfo(range=(0, v - 1)),
             VarInfo(range=(-1, v - 1)),
             VarInfo(range=(-1, e - 1)),
             VarInfo(range=(0, v - 1), padded=True),
             VarInfo(range=(0, e)),
             VarInfo(range=(0, e), mask=True),
             VarInfo()])


def _delete_forest_build(v: int, e: int):
    import jax

    from repro.core import incremental as inc_mod
    d = max(e // 4, 8)

    def fn(edges, alive, pi, parents, parent_eidx, dels, d_true,
           version, deleted, routes):
        return inc_mod._delete_forest_jit(
            edges, alive, pi, parents, parent_eidx, dels, d_true,
            version, deleted, routes)
    return (fn,
            (jax.ShapeDtypeStruct((e, 2), jnp.int32),
             jax.ShapeDtypeStruct((e,), jnp.bool_),
             jax.ShapeDtypeStruct((v,), jnp.int32),
             jax.ShapeDtypeStruct((v, 2), jnp.int32),
             jax.ShapeDtypeStruct((v,), jnp.int32),
             jax.ShapeDtypeStruct((d, 2), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32),
             jax.ShapeDtypeStruct((2,), jnp.int32)),
            [VarInfo(range=(0, v - 1), padded=True),
             VarInfo(mask=True),
             VarInfo(range=(0, v - 1)),
             VarInfo(range=(-1, v - 1)),
             VarInfo(range=(-1, e - 1)),
             VarInfo(range=(0, v - 1), padded=True),
             VarInfo(range=(0, d), mask=True),
             VarInfo(),
             VarInfo(),
             VarInfo()])


@register_trace_spec("dynamic")
def _dynamic_specs():
    def build_absorb(v, e):
        return _incremental_specs()[0].build(v, e)

    return [TraceEntry(name="backend.dynamic.absorb",
                       build=build_absorb, backend="dynamic"),
            TraceEntry(name="backend.dynamic.absorb_forest",
                       build=_absorb_forest_build, backend="dynamic"),
            TraceEntry(name="backend.dynamic.delete",
                       build=lambda v, e: _delete_build(v, e, "jnp"),
                       backend="dynamic"),
            TraceEntry(name="backend.dynamic.delete_fused",
                       build=lambda v, e: _delete_build(
                           v, e, "pallas_fused"),
                       backend="dynamic"),
            TraceEntry(name="backend.dynamic.delete_forest",
                       build=_delete_forest_build, backend="dynamic")]


@register_trace_spec("distributed")
def _distributed_specs():
    def build(v, e):
        import jax
        from jax.sharding import Mesh

        from repro.graphs.device import DeviceGraph
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        dg = DeviceGraph.from_edges(jnp.zeros((e, 2), jnp.int32), v)
        call = dist_mod.build_distributed_cc(dg, mesh, ("data",))
        return (call.on_edges,
                (jax.ShapeDtypeStruct((e, 2), jnp.int32),),
                [VarInfo(range=(0, v - 1), padded=True)])
    return [TraceEntry(name="backend.distributed", build=build,
                       backend="distributed")]
