"""Shared adaptive hook+compress round machinery (DESIGN.md §3).

This module is the single home of the paper's round primitives —
deterministic Hook (scatter-min over flat labels), fused
Multi-Jump Compress (pointer doubling in one ``lax.while_loop``), the
work counters, and the segment-scan / cleanup-loop composition of Fig. 4
— so that every execution mode consumes ONE implementation:

  * ``repro.core.cc``          — single-graph variants + public API,
  * ``repro.core.cc`` (Pallas) — same composition, kernel-backed ops,
  * ``repro.core.batch``       — ``vmap``ped over shape-bucketed batches,
  * ``repro.core.incremental`` — edge-insertion batches hooked into an
                                 existing label array (Hong et al.),
  * ``repro.core.distributed`` — per-chip segment scan under shard_map.

Everything here is pure jnp + lax control flow: safe under ``vmap``
(batched CC), ``shard_map`` (distributed CC), and jit caching.

Every composition here hooks FLAT labels: callers hand in a flat pi
(identity, canonical labels, or a scoped reset of them) and a full
compress follows every hook, so ``pi[pi[x]] == pi[x]`` whenever a hook
reads pi. A hook therefore gathers each
endpoint's label once and issues no root chase: the chase's re-gathers
could never change a value.

Work accounting (the paper's currency is work-efficiency) bills *true*
edge counts: padded ``(0, 0)`` no-op edges — introduced by segmentation,
shape bucketing, or edge-tile alignment — are never counted. Callers
pass the true edge count (static int or traced scalar; the latter is
what the batched path uses, one count per graph in the bucket).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.segmentation import SegmentationPlan, plan_segmentation

MAX_ROUNDS = 64          # outer hook-round fuel


def _scope(name: str):
    """Trace the decorated phase under ``jax.named_scope(name)``: its
    ops carry ``name`` in their HLO op_name path (the profiler's
    ``tf_op``), so every engine that composes the phase inherits the
    name. Metadata only; the computation is unchanged."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def compress_fuel(num_nodes: int) -> int:
    """Pointer doubling squares path lengths per sweep, so
    ceil(log2(V)) + 2 sweeps provably flatten any forest on V nodes —
    a 2-3x tighter static loop bound than a fixed 64 (the roofline's
    memory term for CC scales with this fuel)."""
    return max(4, math.ceil(math.log2(max(num_nodes, 2))) + 2)


class WorkCounters(NamedTuple):
    """Hardware-independent work counters (DESIGN.md §2).

    * ``hook_ops``    — edge-hook evaluations performed (true edges only;
                        one per edge per hook round: the compositions
                        hook flat labels and issue no root chase),
    * ``jump_ops``    — vertex-jump (gather) evaluations performed,
    * ``jump_sweeps`` — full |V|-wide pointer-jump sweeps,
    * ``hook_rounds`` — edge-set hook rounds,
    * ``sync_rounds`` — host-equivalent synchronization points.
    """

    hook_ops: jnp.ndarray
    jump_ops: jnp.ndarray
    jump_sweeps: jnp.ndarray
    hook_rounds: jnp.ndarray
    sync_rounds: jnp.ndarray

    @staticmethod
    def zeros() -> "WorkCounters":
        z = jnp.zeros((), jnp.int32)
        return WorkCounters(z, z, z, z, z)

    def add(self, **kw) -> "WorkCounters":
        d = self._asdict()
        for k, v in kw.items():
            d[k] = d[k] + jnp.asarray(v, jnp.int32)
        return WorkCounters(**d)


# ---------------------------------------------------------------------------
# Primitive operations
# ---------------------------------------------------------------------------

@_scope("hook")
def hook_edges(pi: jnp.ndarray, edges: jnp.ndarray, lift_steps: int = 0
               ) -> jnp.ndarray:
    """One deterministic hook round over ``edges`` (TPU analogue of Hook /
    Atomic-Hook, DESIGN.md §2).

    For every edge (u, v): H = max(pi(u), pi(v)), L = min(...), then
    ``pi[H] <- min(pi[H], L)`` via scatter-min (race-free winner selection —
    the deterministic stand-in for the CAS consensus; identical fixed point
    under the paper's high-to-low rule). ``lift_steps`` performs the bounded
    vectorized root chase of Atomic-Hook (pu <- pi[pu]) before hooking;
    the compositions in this module hook flat labels and pass 0. The
    chase is for callers whose labels are not flat.
    """
    u, v = edges[..., 0], edges[..., 1]
    pu, pv = pi[u], pi[v]
    for _ in range(lift_steps):
        pu, pv = pi[pu], pi[pv]
    hi = jnp.maximum(pu, pv)
    lo = jnp.minimum(pu, pv)
    return pi.at[hi].min(lo)


def jump_once(pi: jnp.ndarray) -> jnp.ndarray:
    """Single-level Jump (Fig. 2): pi <- pi[pi] for every vertex."""
    return pi[pi]


@_scope("compress")
def compress(pi: jnp.ndarray, work: WorkCounters,
             count_syncs: bool = False,
             bill_nodes: int | jnp.ndarray | None = None,
             ) -> tuple[jnp.ndarray, WorkCounters]:
    """Full Compress via fused pointer doubling (the Multi-Jump kernel).

    Runs pi <- pi[pi] sweeps on-device until every tree is a star. Each
    sweep *squares* path lengths (pointer doubling), the same
    work-efficiency lever as the paper's in-kernel chase + continuous
    write-back. With ``count_syncs`` every sweep also bills one host
    synchronization (used by the Soman baseline whose Jump loop re-checks
    convergence from the host after every single-level kernel).
    ``bill_nodes`` overrides the per-sweep jump_ops billing (the batched
    path passes the true |V| so padded self-root vertices are free).
    """
    v = pi.shape[0] if bill_nodes is None else bill_nodes
    fuel = compress_fuel(pi.shape[0])

    def cond(state):
        _, changed, sweeps, _ = state
        return jnp.logical_and(changed, sweeps < fuel)

    def body(state):
        p, _, sweeps, w = state
        nxt = p[p]
        changed = jnp.any(nxt != p)
        w = w.add(jump_ops=v, jump_sweeps=1,
                  sync_rounds=1 if count_syncs else 0)
        return nxt, changed, sweeps + 1, w

    pi, _, _, work = jax.lax.while_loop(
        cond, body, (pi, jnp.asarray(True), jnp.zeros((), jnp.int32), work))
    return pi, work


def edges_consistent(pi: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """True iff every edge has both endpoints under the same label."""
    return jnp.all(pi[edges[..., 0]] == pi[edges[..., 1]])


# ---------------------------------------------------------------------------
# Pluggable round operations
# ---------------------------------------------------------------------------

class RoundOps(NamedTuple):
    """The pluggable kernels of a hook+compress round.

    * ``hook(pi, edges) -> pi``        — one hook pass over an edge set
                                         (billed 1 per true edge),
    * ``compress(pi, work) -> (pi, work)`` — full compress, threading work,
    * ``scan``                         — optional FUSED segment scan:
      ``scan(pi, segments, true_counts, work) -> (pi, work)`` runs the
      whole Fig. 4 inner pipeline (every hook round + every compress
      sweep) in ONE kernel launch, billing internally. When set,
      ``segment_scan`` delegates to it and ``cleanup_rounds`` issues one
      launch per cleanup round instead of ``1 + jump_sweeps``.
    """

    hook: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    compress: Callable[[jnp.ndarray, WorkCounters],
                       tuple[jnp.ndarray, WorkCounters]]
    scan: Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray, WorkCounters],
                   tuple[jnp.ndarray, WorkCounters]] | None = None


def jnp_round_ops(bill_nodes: int | jnp.ndarray | None = None
                  ) -> RoundOps:
    """Pure-jnp ops (the default backend): flat-label hook + compress."""
    return RoundOps(
        hook=hook_edges,
        compress=lambda pi, w: compress(pi, w, bill_nodes=bill_nodes),
    )


def pallas_round_ops(edge_tile: int, node_tile: int,
                     interpret: bool) -> RoundOps:
    """Pallas-kernel-backed ops (hook + multi_jump kernels, DESIGN.md §2).
    The kernels do not thread work counters; compress passes them through.
    The hook kernel walks its edge tiles in order against one pi, so a
    later tile can read a root an earlier tile just hooked: it keeps its
    bounded root chase (the kernel's default depth), which can still
    move a label there.
    """
    from repro.kernels.hook.ops import hook_edges_pallas
    from repro.kernels.multi_jump.ops import full_compress
    return RoundOps(
        hook=lambda pi, e: hook_edges_pallas(
            pi, e, edge_tile=edge_tile, interpret=interpret),
        compress=lambda pi, w: (full_compress(
            pi, tile=node_tile, interpret=interpret), w),
    )


def fused_round_ops(*, interpret: bool | None = None,
                    bill_nodes: int | jnp.ndarray | None = None
                    ) -> RoundOps:
    """Fused-kernel ops (``kernels.cc_fused``): the whole segment scan —
    flat-label hook rounds plus multi-jump compress — in ONE
    ``pallas_call`` per scan. The kernel compresses every segment, so
    it runs at chase depth 0. Billing is bit-compatible with the jnp
    backend: hook_ops on scalar-prefetched TRUE per-segment counts,
    jump_sweeps from the kernel's exact per-segment sweep counters.
    ``hook``/``compress`` fall back to the jnp primitives (used only by
    callers that bypass the fused scan)."""
    from repro.kernels.cc_fused.ops import fused_segment_scan

    def scan(pi, segments, true_counts, work):
        v = pi.shape[0] if bill_nodes is None else bill_nodes
        pi, sweeps = fused_segment_scan(pi, segments, true_counts,
                                        lift_steps=0,
                                        interpret=interpret)
        total = jnp.sum(sweeps)
        return pi, work.add(
            hook_ops=jnp.sum(true_counts),
            hook_rounds=segments.shape[0],
            jump_ops=total * v, jump_sweeps=total)

    return RoundOps(
        hook=hook_edges,
        compress=lambda pi, w: compress(pi, w, bill_nodes=bill_nodes),
        scan=scan,
    )


# ---------------------------------------------------------------------------
# Forest-recording hook (spanning forest as a by-product of hook rounds)
# ---------------------------------------------------------------------------
# Every hook round runs over a fully-compressed pi (all compositions in
# this module compress to fixpoint between hooks), so ``hi`` is a root
# and a scatter-min write at position ``hi`` is a STRICT decrease of
# its own label: pi[hi] == hi before the write, pi[hi] = lo < hi
# after, and hi never reappears as a label. Each position is therefore
# recorded at most once over the whole run, each recorded edge merges
# two components that were distinct at record time, and the recorded
# set is exactly a spanning forest of the input: V - C edges, one
# unrecorded root (the component minimum) per component. These are
# SEPARATE compositions from the plain ones above so the non-forest
# paths stay bit-identical.
#
# The win test compares against the pre-hook ``pi[hi]``, which equals
# ``hi`` on a flat pi, and keeps that gather on purpose: on a TPU v5e
# it is what leads XLA's memory-space assignment to keep a 2^22-vertex
# pi in on-chip VMEM across the segment scan, where every compress
# sweep gathers it. Testing ``new_pi[hi] < hi`` instead saves the
# gather but leaves pi in HBM, and the sampled residue scan's compress
# sweeps then take 3.3x as long (soc-live-journal stand-in).


def empty_forest(num_nodes: int) -> jnp.ndarray:
    """int32 [V, 2] parent-edge table, all (-1, -1): row r will hold
    the original graph edge whose hook retired root r (see
    ``hook_edges_forest``); rows still (-1, -1) at the end are the
    per-component roots."""
    return jnp.full((num_nodes, 2), -1, jnp.int32)


@_scope("hook")
def hook_edges_forest(pi: jnp.ndarray, parents: jnp.ndarray,
                      edges: jnp.ndarray
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``hook_edges`` + spanning-forest recording (same pi updates).
    ``pi`` must be flat, as in every composition of this module.

    An edge wins position ``hi`` iff its scatter-min write actually
    landed (``new_pi[hi] == lo``) AND strictly lowered the root's label
    (``new_pi[hi] < pi[hi]`` — rules out self loops, duplicates, and
    already-merged endpoints). Ties between same-(hi, lo) edges are
    broken by a second scatter-min over edge indices, so exactly one
    original edge is recorded per retired root.
    """
    n = pi.shape[0]
    u, v = edges[..., 0], edges[..., 1]
    pu, pv = pi[u], pi[v]
    hi = jnp.maximum(pu, pv)
    lo = jnp.minimum(pu, pv)
    new_pi = pi.at[hi].min(lo)
    landed = new_pi[hi]
    won = jnp.logical_and(landed == lo, landed < pi[hi])
    eidx = jnp.arange(edges.shape[0], dtype=jnp.int32)
    sentinel = jnp.full((n,), jnp.iinfo(jnp.int32).max, jnp.int32)
    winner = sentinel.at[jnp.where(won, hi, n)].min(eidx, mode="drop")
    rec = jnp.logical_and(won, winner[hi] == eidx)
    parents = parents.at[jnp.where(rec, hi, n)].set(
        jnp.stack([u, v], axis=-1), mode="drop")
    return new_pi, parents


@_scope("segment_scan")
def forest_segment_scan(pi: jnp.ndarray, parents: jnp.ndarray,
                        segments: jnp.ndarray, work: WorkCounters,
                        true_counts: jnp.ndarray,
                        ) -> tuple[jnp.ndarray, jnp.ndarray, WorkCounters]:
    """``segment_scan`` with the parent-edge table threaded through the
    ``lax.scan`` carry (jnp ops only; billing matches ``jnp_round_ops``)."""
    def seg_body(carry, xs):
        p, f, w = carry
        seg, cnt = xs
        p, f = hook_edges_forest(p, f, seg)
        w = w.add(hook_ops=cnt, hook_rounds=1)
        p, w = compress(p, w)
        return (p, f, w), None

    (pi, parents, work), _ = jax.lax.scan(
        seg_body, (pi, parents, work), (segments, true_counts))
    return pi, parents, work


@_scope("cleanup")
def forest_cleanup_rounds(pi: jnp.ndarray, parents: jnp.ndarray,
                          edges: jnp.ndarray, work: WorkCounters,
                          true_edges: int | jnp.ndarray | None = None,
                          max_rounds: int = MAX_ROUNDS,
                          ) -> tuple[jnp.ndarray, jnp.ndarray, WorkCounters]:
    """``cleanup_rounds`` with forest recording (same short-circuit on
    already-consistent edge sets, same true-edge billing)."""
    if true_edges is None:
        true_edges = edges.shape[0]
    bill = jnp.asarray(true_edges, jnp.int32)

    def cond(state):
        _, _, done, rounds_, _ = state
        return jnp.logical_and(~done, rounds_ < max_rounds)

    def body(state):
        p, f, _, rounds_, w = state
        p, f = hook_edges_forest(p, f, edges)
        w = w.add(hook_ops=bill, hook_rounds=1)
        p, w = compress(p, w)
        return p, f, edges_consistent(p, edges), rounds_ + 1, w

    done0 = edges_consistent(pi, edges)
    pi, parents, _, _, work = jax.lax.while_loop(
        cond, body,
        (pi, parents, done0, jnp.zeros((), jnp.int32), work))
    return pi, parents, work


def forest_adaptive_rounds(edges: jnp.ndarray, num_nodes: int,
                           plan: SegmentationPlan, *,
                           true_edges: int | jnp.ndarray | None = None,
                           max_rounds: int = MAX_ROUNDS,
                           ) -> tuple[jnp.ndarray, jnp.ndarray,
                                      WorkCounters]:
    """The Fig. 4 pipeline (segment scan + cleanup) with the spanning
    forest recorded along the way. Labels and counters match
    ``adaptive_rounds`` bit for bit (asserted in tests)."""
    if true_edges is None:
        true_edges = plan.num_edges
    segments = pad_and_segment(edges, plan)
    counts = segment_true_counts(true_edges, plan)
    pi0 = jnp.arange(num_nodes, dtype=jnp.int32)
    pi, parents, work = forest_segment_scan(
        pi0, empty_forest(num_nodes), segments, WorkCounters.zeros(),
        counts)
    flat = segments.reshape(-1, 2)
    pi, parents, work = forest_cleanup_rounds(
        pi, parents, flat, work, true_edges=true_edges,
        max_rounds=max_rounds)
    return pi, parents, work


# ---------------------------------------------------------------------------
# Id-recording forest rounds (maintained forest, DESIGN.md §14)
# ---------------------------------------------------------------------------
# Same win rule as above, but each recorded row also remembers WHICH
# edge won — an external id (the EdgeLog row) scattered alongside the
# endpoints. ``parent_eidx[r]`` is the log row of the edge recorded at
# ``parents[r]`` (-1 for roots), which is what lets a delete batch
# classify tree vs. non-tree hits with one O(V) gather instead of an
# orientation-blind join over the whole log.


def empty_forest_idx(num_nodes: int) -> jnp.ndarray:
    """int32 [V] log-row table matching ``empty_forest``: all -1."""
    return jnp.full((num_nodes,), -1, jnp.int32)


@_scope("hook")
def hook_edges_forest_ids(pi: jnp.ndarray, parents: jnp.ndarray,
                          parent_eidx: jnp.ndarray, edges: jnp.ndarray,
                          edge_ids: jnp.ndarray,
                          ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``hook_edges_forest`` + external-id recording (same pi updates,
    same tie-break: the lowest batch SLOT wins, and that slot's
    ``edge_ids`` entry is what lands in ``parent_eidx``). Padded slots
    carry id -1 but can never win (their (0, 0) self-loop fails the
    strict-decrease test)."""
    n = pi.shape[0]
    u, v = edges[..., 0], edges[..., 1]
    pu, pv = pi[u], pi[v]
    hi = jnp.maximum(pu, pv)
    lo = jnp.minimum(pu, pv)
    new_pi = pi.at[hi].min(lo)
    landed = new_pi[hi]
    won = jnp.logical_and(landed == lo, landed < pi[hi])
    slot = jnp.arange(edges.shape[0], dtype=jnp.int32)
    sentinel = jnp.full((n,), jnp.iinfo(jnp.int32).max, jnp.int32)
    winner = sentinel.at[jnp.where(won, hi, n)].min(slot, mode="drop")
    rec = jnp.logical_and(won, winner[hi] == slot)
    at = jnp.where(rec, hi, n)
    parents = parents.at[at].set(jnp.stack([u, v], axis=-1), mode="drop")
    parent_eidx = parent_eidx.at[at].set(edge_ids, mode="drop")
    return new_pi, parents, parent_eidx


@_scope("cleanup")
def forest_cleanup_rounds_ids(pi: jnp.ndarray, parents: jnp.ndarray,
                              parent_eidx: jnp.ndarray,
                              edges: jnp.ndarray, edge_ids: jnp.ndarray,
                              work: WorkCounters,
                              true_edges: int | jnp.ndarray | None = None,
                              max_rounds: int = MAX_ROUNDS,
                              bill_nodes: int | jnp.ndarray | None = None,
                              ) -> tuple[jnp.ndarray, jnp.ndarray,
                                         jnp.ndarray, WorkCounters]:
    """``forest_cleanup_rounds`` threading the log-row table. The
    scoped delete path passes ``bill_nodes`` (true affected-vertex
    count) so compress sweeps bill the scoped region, not |V|."""
    if true_edges is None:
        true_edges = edges.shape[0]
    bill = jnp.asarray(true_edges, jnp.int32)

    def cond(state):
        _, _, _, done, rounds_, _ = state
        return jnp.logical_and(~done, rounds_ < max_rounds)

    def body(state):
        p, f, fi, _, rounds_, w = state
        p, f, fi = hook_edges_forest_ids(p, f, fi, edges, edge_ids)
        w = w.add(hook_ops=bill, hook_rounds=1)
        p, w = compress(p, w, bill_nodes=bill_nodes)
        return p, f, fi, edges_consistent(p, edges), rounds_ + 1, w

    done0 = edges_consistent(pi, edges)
    pi, parents, parent_eidx, _, _, work = jax.lax.while_loop(
        cond, body,
        (pi, parents, parent_eidx, done0, jnp.zeros((), jnp.int32), work))
    return pi, parents, parent_eidx, work


@_scope("segment_scan")
def forest_segment_scan_ids(pi: jnp.ndarray, parents: jnp.ndarray,
                            parent_eidx: jnp.ndarray,
                            segments: jnp.ndarray, seg_ids: jnp.ndarray,
                            work: WorkCounters, true_counts: jnp.ndarray,
                            bill_nodes: int | jnp.ndarray | None = None,
                            ) -> tuple[jnp.ndarray, jnp.ndarray,
                                       jnp.ndarray, WorkCounters]:
    """``forest_segment_scan`` threading the log-row table (used by the
    from-scratch forest rebuild over the surviving EdgeLog and by the
    scoped delete's scan phases, which pass ``bill_nodes`` so compress
    sweeps bill the affected region, not |V|)."""
    def seg_body(carry, xs):
        p, f, fi, w = carry
        seg, ids, cnt = xs
        p, f, fi = hook_edges_forest_ids(p, f, fi, seg, ids)
        w = w.add(hook_ops=cnt, hook_rounds=1)
        p, w = compress(p, w, bill_nodes=bill_nodes)
        return (p, f, fi, w), None

    (pi, parents, parent_eidx, work), _ = jax.lax.scan(
        seg_body, (pi, parents, parent_eidx, work),
        (segments, seg_ids, true_counts))
    return pi, parents, parent_eidx, work


def forest_scan_rounds_ids(pi: jnp.ndarray, parents: jnp.ndarray,
                           parent_eidx: jnp.ndarray, packed: jnp.ndarray,
                           packed_ids: jnp.ndarray,
                           n_true: jnp.ndarray, work: WorkCounters, *,
                           max_rounds: int = MAX_ROUNDS,
                           bill_nodes: int | jnp.ndarray | None = None,
                           ) -> tuple[jnp.ndarray, jnp.ndarray,
                                      jnp.ndarray, WorkCounters]:
    """Work-efficient drive of the id-recording hook over a packed
    (true-prefix) edge list: one Fig. 4 segment-scan pass — each true
    row billed ONCE, with a full compress between segments so later
    segments hook against already-flattened labels — then the fixpoint
    cleanup loop, which after the scan is usually a 0-round no-op
    (``done0`` short-circuits before any billing). Driving the packed
    rows with the flat round loop instead re-bills every row each
    round, turning the skeleton phase into rounds * V_aff work and
    erasing most of the tree-aware path's advantage over the plain
    scoped recompute."""
    cap = packed.shape[0]
    # the paper's segments of ~|V|/2 rows: every segment pays a full
    # O(V) compress, so a fixed small segment over a V-row skeleton
    # costs V/seg compresses — 4096 per delete tick at |V| = 2^22
    seg = plan_segmentation(cap, pi.shape[0]).segment_size
    pad = (-cap) % seg
    if pad:
        packed = jnp.concatenate(
            [packed, jnp.zeros((pad, 2), packed.dtype)])
        packed_ids = jnp.concatenate(
            [packed_ids, jnp.full((pad,), -1, jnp.int32)])
    segments = packed.reshape(-1, seg, 2)
    seg_ids = packed_ids.reshape(-1, seg)
    starts = jnp.arange(segments.shape[0], dtype=jnp.int32) * seg
    counts = jnp.clip(jnp.asarray(n_true, jnp.int32) - starts, 0, seg)
    pi, parents, parent_eidx, work = forest_segment_scan_ids(
        pi, parents, parent_eidx, segments, seg_ids, work, counts,
        bill_nodes=bill_nodes)
    return forest_cleanup_rounds_ids(
        pi, parents, parent_eidx, packed, packed_ids, work,
        true_edges=n_true, max_rounds=max_rounds, bill_nodes=bill_nodes)


def pack_edge_rows(edges: jnp.ndarray, edge_ids: jnp.ndarray,
                   mask: jnp.ndarray
                   ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pack the rows under ``mask`` to a dense prefix (stable order);
    the tail becomes (0, 0) no-op edges with id -1. Returns
    ``(packed_edges, packed_ids, true_count)``."""
    order = jnp.argsort(~mask, stable=True)
    keep = mask[order]
    packed = jnp.where(keep[:, None], edges[order], 0)
    ids = jnp.where(keep, edge_ids[order], -1)
    return packed, ids, jnp.sum(mask).astype(jnp.int32)


def forest_scoped_rounds(pi: jnp.ndarray, parents: jnp.ndarray,
                         parent_eidx: jnp.ndarray, edges: jnp.ndarray,
                         edge_ids: jnp.ndarray, edge_mask: jnp.ndarray,
                         forest_keep: jnp.ndarray,
                         vertex_mask: jnp.ndarray, work: WorkCounters, *,
                         max_rounds: int = MAX_ROUNDS,
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                    WorkCounters]:
    """Tree-aware scoped reconnection (DESIGN.md §14): relabel only the
    components that lost a spanning-forest edge, in two phases that
    together bill O(V_aff + crossing) instead of O(E_aff):

    1. **skeleton** — re-run hook+compress over the *surviving* forest
       edges of the affected components (``forest_keep``, ~V_aff rows).
       This reassembles the fragments the deletions cut the trees into,
       without touching the (much larger) set of non-tree edges.
    2. **replacement search** — only the alive scoped edges whose
       endpoints still disagree after phase 1 (*crossing* edges) can
       reconnect fragments; pack exactly those and hook to fixpoint,
       recording the replacement edges into the forest.

    Affected vertices restart as self-roots with their forest rows
    cleared; unaffected components keep labels and forest rows
    untouched, so labels stay canonical (component minima) and the
    no-split case reproduces the pre-delete labels bit-identically.

    Both phases hook flat labels, like every composition here: a full
    compress runs between every segment and every cleanup round.
    """
    n_v = pi.shape[0]
    bill_nodes = jnp.sum(vertex_mask).astype(jnp.int32)
    pi0 = jnp.where(vertex_mask, jnp.arange(n_v, dtype=jnp.int32), pi)
    parents0 = jnp.where(vertex_mask[:, None], -1, parents)
    eidx0 = jnp.where(vertex_mask, -1, parent_eidx)

    skel, skel_ids, n_skel = pack_edge_rows(parents, parent_eidx,
                                            forest_keep)
    pi1, parents1, eidx1, work = forest_scan_rounds_ids(
        pi0, parents0, eidx0, skel, skel_ids, n_skel, work,
        max_rounds=max_rounds, bill_nodes=bill_nodes)

    crossing = jnp.logical_and(edge_mask,
                               pi1[edges[:, 0]] != pi1[edges[:, 1]])
    # crossing is the small set (inter-fragment survivors); the flat
    # fixpoint loop converges in O(fragments) rounds. Mask in place
    # instead of packing: (0, 0)/-1 rows are hook no-ops and billing
    # runs on the TRUE crossing count either way, while a pack would
    # argsort the full log capacity on every delete tick
    c_edges = jnp.where(crossing[:, None], edges, 0)
    c_ids = jnp.where(crossing, edge_ids, -1)
    n_cross = jnp.sum(crossing).astype(jnp.int32)
    pi2, parents2, eidx2, work = forest_cleanup_rounds_ids(
        pi1, parents1, eidx1, c_edges, c_ids, work, true_edges=n_cross,
        max_rounds=max_rounds, bill_nodes=bill_nodes)
    return pi2, parents2, eidx2, work


# ---------------------------------------------------------------------------
# Segmentation helpers
# ---------------------------------------------------------------------------

def pad_and_segment(edges: jnp.ndarray, plan: SegmentationPlan
                    ) -> jnp.ndarray:
    """Pad ``edges`` with (0, 0) no-ops to ``plan.padded_edges`` and
    reshape to [num_segments, segment_size, 2]. Trace-safe (static pad)."""
    pad = plan.padded_edges - edges.shape[0]
    if pad > 0:
        edges = jnp.concatenate(
            [edges, jnp.zeros((pad, 2), edges.dtype)], axis=0)
    return edges.reshape(plan.num_segments, plan.segment_size, 2)


def segment_true_counts(true_edges: int | jnp.ndarray,
                        plan: SegmentationPlan) -> jnp.ndarray:
    """Per-segment count of *true* (unpadded) edges, [num_segments] int32.

    Segment i holds edge slots [i*seg, (i+1)*seg); the first
    ``true_edges`` slots are real, the rest are (0, 0) padding. Accepts a
    static int or a traced scalar (the batched path's per-graph counts).
    """
    starts = jnp.arange(plan.num_segments, dtype=jnp.int32) * plan.segment_size
    return jnp.clip(jnp.asarray(true_edges, jnp.int32) - starts,
                    0, plan.segment_size)


# ---------------------------------------------------------------------------
# Round composition (Fig. 4)
# ---------------------------------------------------------------------------

@_scope("segment_scan")
def segment_scan(pi: jnp.ndarray, segments: jnp.ndarray, ops: RoundOps,
                 work: WorkCounters,
                 true_counts: jnp.ndarray | None = None,
                 ) -> tuple[jnp.ndarray, WorkCounters]:
    """Fig. 4 inner structure: for each segment, hook then fully
    compress, all inside one ``lax.scan`` (zero host round-trips).

    ``true_counts`` ([num_segments] int32) bills hook_ops per segment on
    true edges only; None bills the full (padded) segment size.

    With fused ops (``ops.scan`` set) the whole scan is ONE kernel
    launch instead of ``num_segments + jump_sweeps``.
    """
    if true_counts is None:
        true_counts = jnp.full((segments.shape[0],), segments.shape[1],
                               jnp.int32)
    if ops.scan is not None:
        return ops.scan(pi, segments, true_counts, work)

    def seg_body(carry, xs):
        p, w = carry
        seg, cnt = xs
        p = ops.hook(p, seg)
        w = w.add(hook_ops=cnt, hook_rounds=1)
        p, w = ops.compress(p, w)
        return (p, w), None

    (pi, work), _ = jax.lax.scan(seg_body, (pi, work),
                                 (segments, true_counts))
    return pi, work


@_scope("cleanup")
def cleanup_rounds(pi: jnp.ndarray, edges: jnp.ndarray, ops: RoundOps,
                   work: WorkCounters,
                   true_edges: int | jnp.ndarray | None = None,
                   max_rounds: int = MAX_ROUNDS,
                   ) -> tuple[jnp.ndarray, WorkCounters]:
    """Re-hook ``edges`` until every edge is consistent (usually 0-1
    rounds). Covers hook candidates dropped by deterministic
    min-selection — the CAS retry loop of the GPU version resolves those
    in-kernel (DESIGN.md §2). Also the whole of an *incremental* insert:
    hooking a new edge batch into an existing label array is exactly
    this loop (DESIGN.md §6; Hong et al.).

    The initial consistency check short-circuits already-connected edge
    sets to zero hook rounds — the incremental path's common case.
    """
    if true_edges is None:
        true_edges = edges.shape[0]
    bill = jnp.asarray(true_edges, jnp.int32)
    true1 = jnp.asarray(true_edges, jnp.int32).reshape(1)

    def cond(state):
        _, done, rounds, _ = state
        return jnp.logical_and(~done, rounds < max_rounds)

    def body(state):
        p, _, rounds, w = state
        if ops.scan is not None:
            # fused backend: hook + full compress of the (single-segment)
            # edge set in ONE launch per cleanup round
            p, w = ops.scan(p, edges[None], true1, w)
        else:
            p = ops.hook(p, edges)
            w = w.add(hook_ops=bill, hook_rounds=1)
            p, w = ops.compress(p, w)
        return p, edges_consistent(p, edges), rounds + 1, w

    done0 = edges_consistent(pi, edges)
    pi, _, _, work = jax.lax.while_loop(
        cond, body, (pi, done0, jnp.zeros((), jnp.int32), work))
    return pi, work


def scoped_rounds(pi: jnp.ndarray, edges: jnp.ndarray,
                  edge_mask: jnp.ndarray, vertex_mask: jnp.ndarray,
                  plan: SegmentationPlan, ops: RoundOps,
                  work: WorkCounters,
                  max_rounds: int = MAX_ROUNDS,
                  ) -> tuple[jnp.ndarray, WorkCounters]:
    """Scoped recompute (DESIGN.md §9): re-derive labels for ONLY the
    vertices under ``vertex_mask`` from the edges under ``edge_mask``,
    leaving every other label untouched — the deletion fallback of the
    fully-dynamic engine, where ``vertex_mask`` marks the components a
    tombstoned edge may have split and ``edge_mask`` their surviving
    edges.

    The masked edges are compacted to a (0, 0)-padded prefix on device
    (one stable sort — same invariant restoration as
    ``graphs.device.compact_alive``), then run through the ordinary
    Fig. 4 pipeline: segment scan over ``plan`` + trailing cleanup.
    Affected vertices restart as self-roots; unaffected vertices keep
    their (canonical) labels, which hook can neither read nor write
    because every scoped edge joins two affected vertices — k
    simultaneous splits ride ONE stacked scan. Billing is scoped too:
    ``hook_ops`` covers masked edges only (traced count), and callers
    pass ``bill_nodes`` = affected-vertex count into ``ops`` so
    ``jump_ops`` ignores the untouched remainder.
    """
    n_scoped = jnp.sum(edge_mask).astype(jnp.int32)
    order = jnp.argsort(~edge_mask, stable=True)     # scoped rows first
    packed = jnp.where(edge_mask[order][:, None], edges[order], 0)
    pi0 = jnp.where(vertex_mask,
                    jnp.arange(pi.shape[0], dtype=jnp.int32), pi)
    segments = pad_and_segment(packed, plan)
    counts = segment_true_counts(n_scoped, plan)
    pi1, work = segment_scan(pi0, segments, ops, work, true_counts=counts)
    pi1, work = cleanup_rounds(pi1, segments.reshape(-1, 2), ops, work,
                               true_edges=n_scoped, max_rounds=max_rounds)
    return pi1, work


def adaptive_rounds(edges: jnp.ndarray, num_nodes: int,
                    plan: SegmentationPlan, *,
                    ops: RoundOps | None = None,
                    true_edges: int | jnp.ndarray | None = None,
                    max_rounds: int = MAX_ROUNDS,
                    ) -> tuple[jnp.ndarray, WorkCounters]:
    """The full adaptive pipeline (Fig. 4): segment scan, then cleanup.

    ``true_edges`` defaults to ``plan.num_edges`` (the single-graph
    case); the batched path passes a traced per-graph scalar instead.
    Returns (labels, work) — callers add their own sync_rounds billing.
    """
    if ops is None:
        ops = jnp_round_ops()
    if true_edges is None:
        true_edges = plan.num_edges
    segments = pad_and_segment(edges, plan)
    counts = segment_true_counts(true_edges, plan)

    pi0 = jnp.arange(num_nodes, dtype=jnp.int32)
    pi, work = segment_scan(pi0, segments, ops, WorkCounters.zeros(),
                            true_counts=counts)
    flat = segments.reshape(-1, 2)
    pi, work = cleanup_rounds(pi, flat, ops, work, true_edges=true_edges,
                              max_rounds=max_rounds)
    return pi, work
