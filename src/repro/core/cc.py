"""Adaptive work-efficient Connected Components — the paper's core, in JAX.

Implements the four variants compared in the paper's Fig. 5, adapted for
TPU (see DESIGN.md §2 for the GPU→TPU mapping):

  * ``soman``       — Soman et al. baseline (Fig. 1/2): single-level hook
                      rounds + single-level Jump sweeps, a convergence
                      check after *every* sweep (each check is a
                      host-round-trip on the GPU baseline; we count them).
  * ``multijump``   — + the paper's Multi-Jump: the whole Compress phase is
                      fused into one on-device ``lax.while_loop``.
  * ``atomic_hook`` — + the paper's Atomic-Hook: a hook pass that writes
                      roots only (deterministic scatter-min, the TPU
                      analogue of the CAS chase) over the whole edge
                      list, fused with compress into a single device loop.
                      The compress leaves every label at its root, so the
                      hook reads roots with one gather per endpoint and
                      needs no chase.
  * ``adaptive``    — + the paper's adaptive segmentation: the edge list is
                      split into s = 2|E|/|V| segments; each segment hook is
                      followed by a full compress (Fig. 4), all inside one
                      jitted program (zero host round-trips).

All variants produce canonical labels: ``labels[v] == min vertex id of
v's component`` (a strictly stronger guarantee than the paper's "some
representative" — see DESIGN.md §2; it is also what makes batched and
incremental execution bit-compatible with the single-graph path).

The round primitives (hook, compress, segment scan, cleanup loop) live
in ``repro.core.rounds`` and are shared with the batched
(``repro.core.batch``), incremental (``repro.core.incremental``), and
distributed (``repro.core.distributed``) engines; this module keeps the
single-graph variants and their engine entries (``solve_static`` /
``solve_pallas`` / ``solve_hostloop``) — the PUBLIC door is the
``repro.api`` facade, which the deprecated ``connected_components*``
shims forward into. Work accounting (the paper's
currency) bills *true* edge counts — padding is free; see
``rounds.WorkCounters`` for the counter glossary.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rounds
from repro.core.rounds import (        # re-exported; shared machinery
    WorkCounters,
    compress,
    edges_consistent,
    hook_edges,
    jump_once,
)
from repro.core.segmentation import SegmentationPlan, plan_segmentation

_MAX_ROUNDS = rounds.MAX_ROUNDS   # outer hook-round fuel

METHODS = ("soman", "multijump", "atomic_hook", "adaptive", "labelprop")
# + the fused Pallas backend (one kernel launch per segment scan);
# labels are bit-identical to the jnp backend, validated in tests
FUSED_METHOD = "pallas_fused"
# + the k-out sampling engines (repro.core.sampled): sampling phase
# collapses the giant component, adaptive scan covers the residue only
SAMPLED_METHODS = ("sampled", "sampled_fused")
ALL_METHODS = METHODS + (FUSED_METHOD,) + SAMPLED_METHODS
HOSTLOOP_METHODS = ("soman", "multijump")
# the static methods whose jnp hook rounds record the spanning forest
# (labelprop propagates labels without hooking; the fused kernel hooks
# in-kernel without recording; sampled_fused records the sampling
# phase only, so it does not claim the capability)
FOREST_METHODS = ("soman", "multijump", "atomic_hook", "adaptive",
                  "sampled")


class CCResult(NamedTuple):
    labels: jnp.ndarray       # int32 [V]; labels[v] = min id of v's component
    work: WorkCounters


class ForestResult(NamedTuple):
    """Labels + the spanning forest recorded during hook rounds.

    ``parents`` is int32 [V, 2]: row r holds the original graph edge
    whose hook retired root r (rows left (-1, -1) are the component
    roots — exactly one per component, the component minimum). The
    recorded rows are exactly |V| - C edges forming a spanning forest
    whose partition equals ``labels`` (property-tested)."""

    labels: jnp.ndarray
    parents: jnp.ndarray
    work: WorkCounters


# ---------------------------------------------------------------------------
# Variant: Soman et al. baseline (Fig. 1) — single-level hooks and jumps
# ---------------------------------------------------------------------------

def _cc_soman(edges: jnp.ndarray, num_nodes: int,
              true_edges=None) -> CCResult:
    e = edges.shape[0] if true_edges is None else true_edges

    def outer_cond(state):
        _, changed, rounds_, _ = state
        return jnp.logical_and(changed, rounds_ < _MAX_ROUNDS)

    def outer_body(state):
        pi, _, rounds_, w = state
        new_pi = hook_edges(pi, edges, lift_steps=0)
        hook_changed = jnp.any(new_pi != pi)
        # bill the hook kernel + its host-side convergence check
        w = w.add(hook_ops=e, hook_rounds=1, sync_rounds=1)
        # Fig. 1 lines 6-10: single-level Jump until no change, a host
        # convergence check after every sweep.
        new_pi, w = compress(new_pi, w, count_syncs=True)
        return new_pi, hook_changed, rounds_ + 1, w

    pi0 = jnp.arange(num_nodes, dtype=jnp.int32)
    pi, _, _, work = jax.lax.while_loop(
        outer_cond, outer_body,
        (pi0, jnp.asarray(True), jnp.zeros((), jnp.int32),
         WorkCounters.zeros()))
    return CCResult(pi, work)


# ---------------------------------------------------------------------------
# Variant: + Multi-Jump (fused compress, device-resident)
# ---------------------------------------------------------------------------

def _cc_multijump(edges: jnp.ndarray, num_nodes: int,
                  true_edges=None) -> CCResult:
    e = edges.shape[0] if true_edges is None else true_edges

    def outer_cond(state):
        _, changed, rounds_, _ = state
        return jnp.logical_and(changed, rounds_ < _MAX_ROUNDS)

    def outer_body(state):
        pi, _, rounds_, w = state
        new_pi = hook_edges(pi, edges, lift_steps=0)
        hook_changed = jnp.any(new_pi != pi)
        # one hook kernel + ONE fused Multi-Jump kernel => 2 syncs/round
        w = w.add(hook_ops=e, hook_rounds=1, sync_rounds=2)
        new_pi, w = compress(new_pi, w, count_syncs=False)
        return new_pi, hook_changed, rounds_ + 1, w

    pi0 = jnp.arange(num_nodes, dtype=jnp.int32)
    pi, _, _, work = jax.lax.while_loop(
        outer_cond, outer_body,
        (pi0, jnp.asarray(True), jnp.zeros((), jnp.int32),
         WorkCounters.zeros()))
    return CCResult(pi, work)


# ---------------------------------------------------------------------------
# Variant: + Atomic-Hook (root-chasing hook, zero host round-trips)
# ---------------------------------------------------------------------------

def _cc_atomic_hook(edges: jnp.ndarray, num_nodes: int,
                    true_edges=None) -> CCResult:
    # Atomic-Hook is the adaptive cleanup loop run from scratch over the
    # whole (single-segment) edge list.
    if true_edges is None:
        true_edges = edges.shape[0]
    ops = rounds.jnp_round_ops()
    pi0 = jnp.arange(num_nodes, dtype=jnp.int32)
    pi, work = rounds.cleanup_rounds(pi0, edges, ops, WorkCounters.zeros(),
                                     true_edges=true_edges)
    # the whole program is one fused device loop: a single host sync
    work = work.add(sync_rounds=1)
    return CCResult(pi, work)


# ---------------------------------------------------------------------------
# Variant: adaptive segmentation (Fig. 4) — the paper's contribution
# ---------------------------------------------------------------------------

def _cc_adaptive(edges: jnp.ndarray, num_nodes: int,
                 plan: SegmentationPlan, true_edges=None) -> CCResult:
    """Fig. 4: for each of the s = 2|E|/|V| segments, Atomic-Hook the
    segment then fully compress, then a trailing consistency loop —
    all via the shared ``rounds.adaptive_rounds`` core, which bills
    hook_ops on true (unpadded) edges only.
    """
    pi, work = rounds.adaptive_rounds(
        edges, num_nodes, plan,
        true_edges=edges.shape[0] if true_edges is None else true_edges)
    work = work.add(sync_rounds=1)   # one jit call end-to-end
    return CCResult(pi, work)


# ---------------------------------------------------------------------------
# Public API — consumes DeviceGraph (raw arrays via the from_edges shim)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("num_nodes", "method", "num_segments"))
def _cc_jit(edges, true_edges, *, num_nodes, method, num_segments):
    if method == "soman":
        return _cc_soman(edges, num_nodes, true_edges)
    if method == "multijump":
        return _cc_multijump(edges, num_nodes, true_edges)
    if method == "atomic_hook":
        return _cc_atomic_hook(edges, num_nodes, true_edges)
    if method == "adaptive":
        plan = plan_segmentation(edges.shape[0], num_nodes, num_segments)
        return _cc_adaptive(edges, num_nodes, plan, true_edges)
    if method == "labelprop":
        from repro.core.labelprop import _cc_labelprop
        return _cc_labelprop(edges, num_nodes, true_edges)
    raise ValueError(f"unknown method {method!r}; choose from "
                     f"{ALL_METHODS}")


@functools.partial(
    jax.jit, static_argnames=("num_nodes", "num_segments", "interpret"))
def _cc_fused_jit(edges, true_edges, *, num_nodes, num_segments,
                  interpret):
    """method="pallas_fused": the shared adaptive composition over the
    fused segment-scan kernel — ONE pallas_call per segment scan (and
    one per cleanup round) instead of ``num_segments + jump_sweeps``
    launches. Labels and work counters are bit-compatible with the jnp
    backend (asserted in tests)."""
    plan = plan_segmentation(edges.shape[0], num_nodes, num_segments)
    ops = rounds.fused_round_ops(interpret=interpret)
    pi, work = rounds.adaptive_rounds(edges, num_nodes, plan, ops=ops,
                                      true_edges=true_edges)
    return CCResult(pi, work.add(sync_rounds=1))


def solve_static(
    graph,
    num_nodes: int | None = None,
    method: str = "adaptive",
    *,
    num_segments: int | None = None,
) -> CCResult:
    """Compute connected components (the engine entry the ``repro.api``
    backends dispatch to; callers should go through the facade —
    ``repro.api.solve`` / ``Solver`` — which adds policy routing and
    inspectable plans).

    Args:
      graph: a ``repro.graphs.device.DeviceGraph`` (the native input),
        a host ``repro.graphs.format.Graph``, or a raw [E, 2] int edge
        array (one direction per undirected edge suffices; self loops
        and duplicates are harmless) — raw arrays go through the
        ``DeviceGraph.from_edges`` shim and need ``num_nodes``.
      num_nodes: |V| (static; only for raw edge arrays).
      method: one of ``soman | multijump | atomic_hook | adaptive |
        labelprop | pallas_fused``, or ``auto`` — the adaptive-selection
        policy (``repro.connectivity.policy``) picks from the graph's
        features (density 2|E|/|V| heuristic, overridden by a measured
        autotune cache when one is warm). ``pallas_fused`` runs the
        fused segment-scan kernel (one launch per scan; interpret mode
        off-TPU).
      num_segments: override the adaptive 2|E|/|V| heuristic.

    Returns:
      ``CCResult(labels, work)`` with canonical min-id labels. Work is
      billed on TRUE (unpadded) edges — a padded DeviceGraph costs what
      its real edges cost.
    """
    from repro.graphs.device import as_device_graph
    g = as_device_graph(graph, num_nodes, num_segments=num_segments)
    if g.num_nodes <= 0:
        return CCResult(jnp.zeros((0,), jnp.int32), WorkCounters.zeros())
    if g.edges.shape[0] == 0 or g.true_edges_static == 0:
        return CCResult(jnp.arange(g.num_nodes, dtype=jnp.int32),
                        WorkCounters.zeros())
    if method == "auto":
        from repro.connectivity.policy import select_method
        method = select_method(g.num_nodes, g.num_edges,
                               degree_skew=g.degree_skew)
    if method in SAMPLED_METHODS:
        from repro.core.sampled import solve_sampled
        res = solve_sampled(g, num_segments=num_segments,
                            fused=(method == "sampled_fused"))
        return CCResult(res.labels, res.work)
    # the common exact-sized case keeps true_edges out of the traced
    # operands entirely (None): billing stays a compile-time constant
    # and no per-call scalar device_put is paid; only padded graphs
    # thread a traced scalar
    t = g.true_edges_static
    true = None if (t is not None and t == int(g.edges.shape[0])) \
        else g.true_edges_device()
    if method == FUSED_METHOD:
        from repro.kernels import default_interpret
        return _cc_fused_jit(g.edges, true, num_nodes=g.num_nodes,
                             num_segments=g.plan.num_segments,
                             interpret=default_interpret())
    return _cc_jit(g.edges, true, num_nodes=g.num_nodes, method=method,
                   num_segments=g.plan.num_segments)


# ---------------------------------------------------------------------------
# Spanning-forest solves (forest recorded during hook rounds)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("num_nodes", "method", "num_segments"))
def _cc_forest_jit(edges, true_edges, *, num_nodes, method, num_segments):
    """Forest-recording twin of ``_cc_jit``: identical pi updates and
    billing, with the parent-edge table threaded through every hook.
    Kept a SEPARATE program so the plain solves stay bit-identical."""
    e = edges.shape[0] if true_edges is None else true_edges
    pi0 = jnp.arange(num_nodes, dtype=jnp.int32)
    parents0 = rounds.empty_forest(num_nodes)

    if method in ("soman", "multijump"):
        count_syncs = method == "soman"
        per_round = 1 if count_syncs else 2

        def outer_cond(state):
            _, _, changed, rounds_, _ = state
            return jnp.logical_and(changed, rounds_ < _MAX_ROUNDS)

        def outer_body(state):
            pi, f, _, rounds_, w = state
            new_pi, f = rounds.hook_edges_forest(pi, f, edges)
            hook_changed = jnp.any(new_pi != pi)
            w = w.add(hook_ops=e, hook_rounds=1, sync_rounds=per_round)
            new_pi, w = compress(new_pi, w, count_syncs=count_syncs)
            return new_pi, f, hook_changed, rounds_ + 1, w

        pi, parents, _, _, work = jax.lax.while_loop(
            outer_cond, outer_body,
            (pi0, parents0, jnp.asarray(True), jnp.zeros((), jnp.int32),
             WorkCounters.zeros()))
        return ForestResult(pi, parents, work)

    if method == "atomic_hook":
        pi, parents, work = rounds.forest_cleanup_rounds(
            pi0, parents0, edges, WorkCounters.zeros(), true_edges=e)
        return ForestResult(pi, parents, work.add(sync_rounds=1))

    if method == "adaptive":
        plan = plan_segmentation(edges.shape[0], num_nodes, num_segments)
        pi, parents, work = rounds.forest_adaptive_rounds(
            edges, num_nodes, plan, true_edges=e)
        return ForestResult(pi, parents, work.add(sync_rounds=1))

    raise ValueError(f"unknown forest method {method!r}; choose from "
                     f"{FOREST_METHODS}")


def solve_forest(
    graph,
    num_nodes: int | None = None,
    method: str = "adaptive",
    *,
    num_segments: int | None = None,
) -> ForestResult:
    """Connected components WITH the spanning forest: the parent edges
    each hook round records, as a first-class product (DESIGN.md §13).

    ``method`` must be one of ``FOREST_METHODS`` — the static engines
    whose jnp hook rounds run through ``rounds.hook_edges_forest``
    (``sampled`` records during both the sampling phase and the
    residue scan). Labels are the same canonical min-id fixed point as
    the plain solves. The engine entry behind
    ``Solver.spanning_forest()``; prefer the facade.
    """
    from repro.graphs.device import as_device_graph
    if method not in FOREST_METHODS:
        raise ValueError(f"method {method!r} does not record a spanning "
                         f"forest; choose from {FOREST_METHODS}")
    g = as_device_graph(graph, num_nodes, num_segments=num_segments)
    if g.num_nodes <= 0:
        return ForestResult(jnp.zeros((0,), jnp.int32),
                            rounds.empty_forest(0), WorkCounters.zeros())
    if g.edges.shape[0] == 0 or g.true_edges_static == 0:
        return ForestResult(jnp.arange(g.num_nodes, dtype=jnp.int32),
                            rounds.empty_forest(g.num_nodes),
                            WorkCounters.zeros())
    if method == "sampled":
        from repro.core.sampled import solve_sampled
        res = solve_sampled(g, num_segments=num_segments, forest=True)
        return ForestResult(res.labels, res.parents, res.work)
    t = g.true_edges_static
    true = None if (t is not None and t == int(g.edges.shape[0])) \
        else g.true_edges_device()
    return _cc_forest_jit(g.edges, true, num_nodes=g.num_nodes,
                          method=method,
                          num_segments=g.plan.num_segments)


def connected_components(
    graph,
    num_nodes: int | None = None,
    method: str = "adaptive",
    *,
    num_segments: int | None = None,
    lift_steps: int = 2,
) -> CCResult:
    """DEPRECATED legacy entrypoint — forwards through the
    ``repro.api`` facade (``Solver``/``BACKENDS``), bit-identical to
    calling it directly. Use ``repro.api.solve`` (one-shot) or
    ``repro.api.Solver`` (sessions) instead."""
    from repro._deprecation import warn_once
    from repro.api import solve
    warn_once("repro.core.cc.connected_components", "repro.api.solve")
    return solve(graph, num_nodes, method,
                 num_segments=num_segments, lift_steps=lift_steps)


# ---------------------------------------------------------------------------
# Pallas-kernel backend (TPU target; interpret-mode on CPU)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("num_nodes", "num_segments", "interpret"))
def _cc_adaptive_pallas(edges, *, num_nodes, num_segments, interpret):
    plan = plan_segmentation(edges.shape[0], num_nodes, num_segments)
    ops = rounds.pallas_round_ops(
        edge_tile=min(1024, plan.segment_size),
        node_tile=min(512, max(8, num_nodes)),
        interpret=interpret)
    pi, _ = rounds.adaptive_rounds(edges, num_nodes, plan, ops=ops,
                                   true_edges=edges.shape[0])
    return pi


def solve_pallas(graph, num_nodes: int | None = None, *,
                 num_segments: int | None = None,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Adaptive CC on the per-round Pallas kernel backend (hook +
    multi_jump kernels; DESIGN.md §2) — one launch per segment hook and
    per compress sweep. Prefer ``backend="pallas_fused"`` for the
    single-launch fused pipeline. Returns canonical min-id labels.
    (Engine entry for the ``pallas`` backend; go through the facade.)"""
    from repro.graphs.device import as_device_graph
    from repro.kernels import default_interpret
    interpret = default_interpret() if interpret is None else interpret
    g = as_device_graph(graph, num_nodes, num_segments=num_segments)
    if g.num_nodes <= 0:
        return jnp.zeros((0,), jnp.int32)
    if g.edges.shape[0] == 0:
        return jnp.arange(g.num_nodes, dtype=jnp.int32)
    return _cc_adaptive_pallas(g.edges, num_nodes=g.num_nodes,
                               num_segments=g.plan.num_segments,
                               interpret=interpret)


def connected_components_pallas(graph, num_nodes: int | None = None, *,
                                num_segments: int | None = None,
                                lift_steps: int = 2,
                                interpret: bool | None = None
                                ) -> jnp.ndarray:
    """DEPRECATED legacy entrypoint — forwards through the facade's
    ``pallas`` backend; returns labels only, as before."""
    from repro._deprecation import warn_once
    from repro.api import Solver
    warn_once("repro.core.cc.connected_components_pallas",
              'repro.api.solve(..., backend="pallas")')
    res = Solver.open(graph, num_nodes, num_segments=num_segments,
                      lift_steps=lift_steps).solve(
        backend="pallas", interpret=interpret)
    return res.labels


# ---------------------------------------------------------------------------
# Host-driven execution (GPU-baseline control flow, for benchmarking)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def _host_hook(pi, edges):
    new = hook_edges(pi, edges, lift_steps=0)
    return new, jnp.any(new != pi)


@functools.partial(jax.jit, donate_argnums=(0,))
def _host_jump(pi):
    new = pi[pi]
    return new, jnp.any(new != pi)


@jax.jit
def _host_compress(pi):
    pi, w = compress(pi, WorkCounters.zeros())
    return pi, w.jump_sweeps


def solve_hostloop(
    edges, num_nodes: int, method: str = "soman",
) -> tuple[np.ndarray, dict]:
    """Run the Soman baseline (or +multijump) with *host-side* control
    flow — one ``device_get`` per convergence check, faithful to the GPU
    baseline's CPU-GPU round trips. Used by the benchmarks (through the
    facade's ``hostloop`` backend) to expose the cost the paper's
    device-centric design removes.
    """
    if method not in HOSTLOOP_METHODS:
        raise ValueError(f"unknown method {method!r}; choose from "
                         f"{HOSTLOOP_METHODS}")
    edges = jnp.asarray(edges, jnp.int32).reshape(-1, 2)
    pi = jnp.arange(num_nodes, dtype=jnp.int32)
    syncs = 0
    stats = {"hook_rounds": 0, "jump_sweeps": 0}
    while True:
        pi, hook_changed = _host_hook(pi, edges)
        stats["hook_rounds"] += 1
        syncs += 1
        if method == "soman":
            while True:
                pi, jchanged = _host_jump(pi)
                stats["jump_sweeps"] += 1
                syncs += 1
                if not bool(jchanged):          # device->host round trip
                    break
        else:  # multijump: one fused compress kernel, one sync
            pi, sweeps = _host_compress(pi)
            stats["jump_sweeps"] += int(sweeps)
            syncs += 1
        if not bool(hook_changed):              # device->host round trip
            break
    stats["sync_rounds"] = syncs
    return np.asarray(pi), stats


def connected_components_hostloop(
    edges, num_nodes: int, method: str = "soman",
) -> tuple[np.ndarray, dict]:
    """DEPRECATED legacy entrypoint — forwards through the facade's
    ``hostloop`` backend; returns ``(labels, stats)`` as before."""
    from repro._deprecation import warn_once
    from repro.api import Solver
    warn_once("repro.core.cc.connected_components_hostloop",
              'Solver.plan(backend="hostloop")')
    plan = Solver.open(edges, num_nodes).plan(backend="hostloop",
                                              hostloop_method=method)
    res = plan.run()
    return np.asarray(res.labels), plan.artifacts["hostloop_stats"]


def num_components(labels) -> int:
    """Distinct-label count — thin wrapper over the on-device
    sort/segment kernel (``connectivity.queries.count_components``);
    the old host-side ``np.unique`` round trip is gone."""
    from repro.connectivity.queries import count_components
    return int(count_components(jnp.asarray(labels)))
