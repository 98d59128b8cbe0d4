"""Sampling-accelerated CC — the k-out / Afforest-style engine.

Hong et al. (PAPERS.md) observe that on most real graphs — especially
skewed-degree (kron / social) inputs — a cheap neighbor-sampling phase
collapses the giant component before the full edge list is ever
touched, so the expensive scan only has to process the small residue.
This module is that observation composed out of the repo's existing
round machinery, in two jits (each a ``repro.analysis`` trace entry):

* ``_sample_phase_jit`` — build CSR offsets on device (sort +
  searchsorted over the SYMMETRIZED edge list, so one-direction
  undirected storage still samples both endpoints), take the first
  ``k`` slots per vertex (k-out sampling; invalid slots become (0, 0)
  no-ops and are never billed), then run ``sample_rounds`` fixed
  hook+compress rounds over the |V|*k sampled edges. The giant
  component is identified with the existing census kernel (one
  scatter-add + argmax) for telemetry; correctness never depends on
  it.
* ``_residue_scan_jit`` — the residue is every stored edge whose
  endpoints still carry different labels (a strict superset filter of
  "both endpoints outside the giant component": intra-component edges
  of EVERY collapsed component are dropped, not just the giant's).
  Residue edges are compacted to a (0, 0)-padded prefix (one stable
  sort — the ``compact_alive`` idiom) and run through the ordinary
  Fig. 4 pipeline from the sampled labels: segment scan + trailing
  cleanup, billing the traced residue count only. ``fused=True``
  routes the scan through the ``cc_fused`` Pallas kernel
  (``sampled_fused``).

Both jits take a static ``forest``. Only ``cc.solve_forest`` (behind
``Solver.spanning_forest()``) asks for one: then every hook records
the spanning-forest parent edge it merged through
(``rounds.hook_edges_forest`` and the forest compositions), into an
int32 [V, 2] table carried through both phases. A labels-only solve
(``solve()``, the ``sampled`` / ``sampled_fused`` backends) hooks with
the plain compositions and allocates no table: the forest hooks make
the same ``pi`` updates and bill the same, so labels and
``WorkCounters`` are bit-identical either way, and the labels-only
hook issues 2 gathers and one scatter-min per edge instead of 5
gathers, two scatter-mins and the parent-row scatter.

Both phases hook flat labels (each hook follows a full compress, or
starts from identity), so every hook gathers each endpoint's label once
and issues no root chase. Work accounting: the sample phase bills the
valid-slot count in hook evaluations per round; the residue scan bills
true residue edges only, once per hook round. On skewed inputs the
total is a small fraction of what the full-scan backends pay — the
headline of BENCH_sampled.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import rounds
from repro.core.rounds import WorkCounters
from repro.core.segmentation import plan_segmentation

SAMPLE_K = 2          # neighbors sampled per vertex (Afforest's k)
SAMPLE_ROUNDS = 2     # fixed hook+compress rounds over the sample


class SampledResult(NamedTuple):
    """labels + forest (None unless asked for) + work, plus the
    phase-split telemetry."""

    labels: jnp.ndarray           # int32 [V] canonical min-id labels
    parents: jnp.ndarray | None   # int32 [V, 2] forest edges, -1 = root
    work: WorkCounters            # combined (sample + residue) billing
    stats: dict                   # device scalars: phase split + giant


@functools.partial(
    jax.jit, static_argnames=("num_nodes", "k", "sample_rounds", "forest"))
def _sample_phase_jit(edges, true_edges, *, num_nodes, k, sample_rounds,
                      forest):
    """k-out sampling phase: CSR on device, hook each vertex to its
    first ``k`` neighbors for ``sample_rounds`` rounds, the forest
    recorded iff ``forest``. Returns ``(pi, parents, work, n_sampled,
    giant_label, giant_size)`` — all device values, ``parents`` None
    without a forest."""
    e = edges.shape[0]
    with jax.named_scope("sampled.csr"):
        # symmetrize so vertices stored only as targets still get
        # sampled; padded (0, 0) rows stay (0, 0) and are masked out
        # via the true count below
        sym = jnp.concatenate([edges, edges[:, ::-1]], axis=0)
        row_real = jnp.arange(e, dtype=jnp.int32) < true_edges
        real = jnp.concatenate([row_real, row_real])
        src = sym[:, 0]
        order = jnp.argsort(src, stable=True)
        sorted_src = src[order]
        neighbors = sym[order, 1]
        real_sorted = real[order]
        offsets = jnp.searchsorted(
            sorted_src, jnp.arange(num_nodes + 1, dtype=jnp.int32)
        ).astype(jnp.int32)

    with jax.named_scope("sampled.sample"):
        # slot (v, j) = CSR position offsets[v] + j; valid iff inside
        # v's row AND backed by a true (unpadded) edge
        slots = offsets[:-1, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
        in_row = slots < offsets[1:, None]
        slots_c = jnp.minimum(slots, 2 * e - 1)
        valid = jnp.logical_and(in_row, real_sorted[slots_c])
        su = jnp.where(valid, jnp.arange(num_nodes,
                                         dtype=jnp.int32)[:, None], 0)
        sv = jnp.where(valid, neighbors[slots_c], 0)
        sampled = jnp.stack([su.reshape(-1), sv.reshape(-1)], axis=-1)
        n_sampled = jnp.sum(valid).astype(jnp.int32)

        pi = jnp.arange(num_nodes, dtype=jnp.int32)
        parents = rounds.empty_forest(num_nodes) if forest else None
        work = WorkCounters.zeros()
        for _ in range(sample_rounds):
            if forest:
                pi, parents = rounds.hook_edges_forest(pi, parents,
                                                       sampled)
            else:
                pi = rounds.hook_edges(pi, sampled)
            work = work.add(hook_ops=n_sampled, hook_rounds=1)
            pi, work = rounds.compress(pi, work)

    with jax.named_scope("sampled.census"):
        census = jnp.zeros((num_nodes,), jnp.int32).at[pi].add(1)
        giant = jnp.argmax(census).astype(jnp.int32)
        giant_size = census[giant]
    return pi, parents, work, n_sampled, giant, giant_size


@functools.partial(
    jax.jit, static_argnames=("num_nodes", "num_segments", "fused",
                              "interpret", "forest"))
def _residue_scan_jit(edges, true_edges, pi, parents, work, *,
                      num_nodes, num_segments, fused, interpret, forest):
    """Adaptive Fig. 4 scan over the residue only: edges whose
    endpoints the sampling phase left in different components, packed
    to a prefix and billed as a traced count. Starts from the sampled
    labels (NOT identity), and with ``forest`` from the sampled
    ``parents`` (None otherwise; ``fused`` records none). Returns
    ``(pi, parents, work, n_residue)``."""
    e = edges.shape[0]
    with jax.named_scope("sampled.pack"):
        row_real = jnp.arange(e, dtype=jnp.int32) < true_edges
        live = jnp.logical_and(pi[edges[:, 0]] != pi[edges[:, 1]],
                               row_real)
        n_res = jnp.sum(live).astype(jnp.int32)
        order = jnp.argsort(~live, stable=True)   # residue rows first
        packed = jnp.where(live[order][:, None], edges[order], 0)
    plan = plan_segmentation(e, num_nodes, num_segments)
    segments = rounds.pad_and_segment(packed, plan)
    counts = rounds.segment_true_counts(n_res, plan)
    if forest:
        pi, parents, work = rounds.forest_segment_scan(
            pi, parents, segments, work, counts)
        pi, parents, work = rounds.forest_cleanup_rounds(
            pi, parents, segments.reshape(-1, 2), work, true_edges=n_res)
    else:
        ops = rounds.fused_round_ops(interpret=interpret) if fused \
            else rounds.jnp_round_ops()
        pi, work = rounds.segment_scan(pi, segments, ops, work,
                                       true_counts=counts)
        pi, work = rounds.cleanup_rounds(pi, segments.reshape(-1, 2),
                                         ops, work, true_edges=n_res)
    return pi, parents, work, n_res


def solve_sampled(graph, num_nodes: int | None = None, *,
                  k: int = SAMPLE_K,
                  sample_rounds: int = SAMPLE_ROUNDS,
                  num_segments: int | None = None,
                  fused: bool = False,
                  interpret: bool | None = None,
                  forest: bool = False) -> SampledResult:
    """The sampled engine entry (the ``sampled`` / ``sampled_fused``
    backends dispatch here; go through the ``repro.api`` facade).

    Two device programs: the k-out sampling phase, then the adaptive
    scan over the residue. ``fused=True`` runs the residue scan
    through the ``cc_fused`` Pallas kernel. ``forest=True`` (from
    ``cc.solve_forest`` alone) records the spanning forest in
    ``SampledResult.parents``; it is None otherwise, and labels and
    work are the same either way. The fused kernel records no forest,
    so the two do not combine (``sampled_fused`` reports
    ``spanning_forest=False``).
    Each phase runs under its own ``repro.obs`` span, and the
    sampled-vs-residue work split lands in ``SampledResult.stats``
    (device scalars; nothing here syncs).
    """
    from repro.graphs.device import as_device_graph
    from repro.obs import trace as obs
    if fused and forest:
        raise ValueError("the fused residue scan records no forest")
    g = as_device_graph(graph, num_nodes, num_segments=num_segments)
    v = g.num_nodes
    if v <= 0:
        z = jnp.zeros((), jnp.int32)
        return SampledResult(jnp.zeros((0,), jnp.int32),
                             rounds.empty_forest(0) if forest else None,
                             WorkCounters.zeros(),
                             {"sample_hook_ops": z, "residue_hook_ops": z,
                              "n_sampled": z, "n_residue": z,
                              "giant_label": z, "giant_size": z})
    if g.edges.shape[0] == 0 or g.true_edges_static == 0:
        z = jnp.zeros((), jnp.int32)
        return SampledResult(jnp.arange(v, dtype=jnp.int32),
                             rounds.empty_forest(v) if forest else None,
                             WorkCounters.zeros(),
                             {"sample_hook_ops": z, "residue_hook_ops": z,
                              "n_sampled": z, "n_residue": z,
                              "giant_label": z,
                              "giant_size": jnp.ones((), jnp.int32)})
    if fused and interpret is None:
        from repro.kernels import default_interpret
        interpret = default_interpret()
    true = g.true_edges_device()
    with obs.span("sampled.sample_phase", num_nodes=v, k=k):
        pi, parents, s_work, n_sampled, giant, giant_size = \
            _sample_phase_jit(g.edges, true, num_nodes=v, k=k,
                              sample_rounds=sample_rounds, forest=forest)
    with obs.span("sampled.residue_scan", num_nodes=v):
        pi, parents, work, n_res = _residue_scan_jit(
            g.edges, true, pi, parents, s_work, num_nodes=v,
            num_segments=g.plan.num_segments,
            fused=fused, interpret=bool(interpret), forest=forest)
    work = work.add(sync_rounds=2)      # one jit call per phase
    stats = {"sample_hook_ops": s_work.hook_ops,
             "residue_hook_ops": work.hook_ops - s_work.hook_ops,
             "n_sampled": n_sampled, "n_residue": n_res,
             "giant_label": giant, "giant_size": giant_size}
    # the sampled-vs-residue work split, kept as device scalars while
    # tracing is on (no sync here; read through tracer().counters)
    obs.count_device("sampled.solves", 1)
    obs.count_device("sampled.hook_ops.sample", stats["sample_hook_ops"])
    obs.count_device("sampled.hook_ops.residue", stats["residue_hook_ops"])
    if forest:
        # a host counter, always on: how many solves paid for a forest
        obs.count("sampled.forest_solves")
    return SampledResult(pi, parents, work, stats)
