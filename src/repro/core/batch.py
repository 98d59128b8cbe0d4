"""Batched Connected Components: many graphs, one device program.

The serving-shaped workload (DESIGN.md §4): lots of small/medium graphs
— molecule batches, per-user subgraphs, sampled minibatch blocks —
where per-graph dispatch overhead dominates. Graphs are bucketed by
*padded* shape (vertex and edge counts rounded up to powers of two), and
each bucket runs the shared adaptive core (``rounds.adaptive_rounds``)
under ``jax.vmap`` as ONE jitted program:

  * vertices are padded as self-roots — ``pi0 = arange(V_pad)`` makes
    every padded vertex its own (untouched) component;
  * edges are padded with ``(0, 0)`` no-ops (self-loop hooks);
  * the jit cache is keyed on the bucket shape (static ``num_nodes`` /
    segment plan), so a stream of same-regime graphs compiles once.

Because every variant produces *canonical min-id labels* (a fixed point
independent of hook order), the batched labels are bit-identical to the
per-graph ``connected_components`` output — the tests assert exactly
that on mixed-size buckets.

Work accounting stays honest under padding: per-graph true edge counts
ride through the vmap as traced scalars, so ``hook_ops`` bills real
edges only and ``jump_ops`` bills the true |V| (padding is free; see
``rounds.WorkCounters``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rounds
from repro.core.cc import CCResult
from repro.core.rounds import WorkCounters
from repro.core.segmentation import plan_segmentation

_MIN_NODES = 8
_MIN_EDGES = 8


def _next_pow2(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1)."""
    return _next_pow2(max(x, 1))


def pad_rows_pow2(arr: np.ndarray, min_rows: int = _MIN_EDGES
                  ) -> np.ndarray:
    """Pad axis 0 with zero rows to a power-of-two count (floored at
    ``min_rows``). The same bucket rule the batched engine uses for
    edge lists, reused by the connectivity service to route same-shape
    query microbatches through one jit cache entry; zero rows are
    no-ops for every query kernel (vertex 0 compared with itself)."""
    arr = np.asarray(arr)
    target = next_pow2(max(arr.shape[0], min_rows))
    if target == arr.shape[0]:
        return arr
    pad = np.zeros((target - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def bucket_shape(num_nodes: int, num_edges: int) -> tuple[int, int]:
    """The (V_pad, E_pad) bucket a graph lands in: next powers of two,
    floored at small minima so tiny graphs share one compile."""
    return (_next_pow2(max(num_nodes, _MIN_NODES)),
            _next_pow2(max(num_edges, _MIN_EDGES)))


@functools.partial(
    jax.jit, static_argnames=("num_nodes", "num_segments"))
def _cc_batched_jit(edges, true_edge_counts, true_node_counts, *,
                    num_nodes, num_segments):
    """vmapped adaptive CC over one bucket.

    edges: [B, E_pad, 2] int32 ((0,0)-padded);
    true_edge_counts / true_node_counts: [B] int32 billing scalars.
    """
    plan = plan_segmentation(edges.shape[1], num_nodes, num_segments)

    def one(ed, n_edges, n_nodes):
        ops = rounds.jnp_round_ops(bill_nodes=n_nodes)
        pi, work = rounds.adaptive_rounds(ed, num_nodes, plan, ops=ops,
                                          true_edges=n_edges)
        return CCResult(pi, work.add(sync_rounds=1))

    return jax.vmap(one)(edges, true_edge_counts, true_node_counts)


class GraphBatch(NamedTuple):
    """One shape bucket, ready for the device: [B, E_pad, 2] edges plus
    per-graph true sizes (for label truncation and work billing)."""
    edges: np.ndarray        # int32 [B, E_pad, 2]
    num_nodes: int           # V_pad (static bucket height)
    true_nodes: np.ndarray   # int32 [B]
    true_edges: np.ndarray   # int32 [B]
    indices: np.ndarray      # int32 [B] positions in the caller's list


def stack_device_graphs(graphs: Sequence) -> list[GraphBatch]:
    """DeviceGraph bucket stacking: group by the (V_pad, E_pad) pow2
    bucket, pad each member's edges on DEVICE (jitted (0,0) rows) and
    ``jnp.stack`` the bucket — no host round trip. True edge/node
    counts come from static DeviceGraph metadata (explicit device_put,
    so the path stays legal under ``jax.transfer_guard``)."""
    buckets: dict[tuple[int, int], list[int]] = {}
    for i, g in enumerate(graphs):
        if g.true_edges_static is None:
            raise ValueError("batched execution needs static true "
                             "edge counts (graph %d)" % i)
        buckets.setdefault(
            bucket_shape(g.num_nodes, int(g.edges.shape[0])),
            []).append(i)
    out = []
    for (v_pad, e_pad), members in sorted(buckets.items()):
        stack = jnp.stack(
            [graphs[i].pad_rows(e_pad).edges for i in members])
        tn = np.asarray([graphs[i].num_nodes for i in members], np.int32)
        te = np.asarray([graphs[i].true_edges_static for i in members],
                        np.int32)
        out.append(GraphBatch(edges=stack, num_nodes=v_pad,
                              true_nodes=tn, true_edges=te,
                              indices=np.asarray(members, np.int32)))
    return out


def bucketize(graphs: Sequence[tuple[np.ndarray, int]]
              ) -> list[GraphBatch]:
    """Group (edges, num_nodes) pairs into shape buckets."""
    buckets: dict[tuple[int, int], list[int]] = {}
    prepped = []
    for i, (edges, n) in enumerate(graphs):
        edges = np.asarray(edges, np.int32).reshape(-1, 2)
        prepped.append((edges, int(n)))
        buckets.setdefault(bucket_shape(int(n), edges.shape[0]),
                           []).append(i)
    out = []
    for (v_pad, e_pad), members in sorted(buckets.items()):
        stack = np.zeros((len(members), e_pad, 2), np.int32)
        tn = np.zeros(len(members), np.int32)
        te = np.zeros(len(members), np.int32)
        for row, i in enumerate(members):
            edges, n = prepped[i]
            stack[row, : edges.shape[0]] = edges
            tn[row], te[row] = n, edges.shape[0]
        out.append(GraphBatch(edges=stack, num_nodes=v_pad,
                              true_nodes=tn, true_edges=te,
                              indices=np.asarray(members, np.int32)))
    return out


def solve_batched(
    graphs: Sequence, *,
    num_segments: int | None = None,
) -> list[CCResult]:
    """Adaptive CC over a batch of graphs, one device program per shape
    bucket (engine entry for the facade's ``batched`` backend; callers
    should go through ``repro.api.Solver.solve_batch``).

    Args:
      graphs: sequence of ``repro.graphs.format.Graph`` objects or
        ``(edges [E,2], num_nodes)`` pairs; sizes may be mixed freely.
      num_segments: override the bucket's 2|E_pad|/|V_pad| heuristic.

    Returns:
      One ``CCResult`` per input graph, in input order, labels truncated
      to the graph's true |V| — bit-identical to per-graph
      ``connected_components``. DeviceGraph inputs stay device-resident
      end to end (device labels out); host inputs get host labels.
    """
    from repro.graphs.device import DeviceGraph
    graphs = list(graphs)
    device_in = bool(graphs) and all(
        isinstance(g, DeviceGraph) for g in graphs)
    if device_in:
        batches = stack_device_graphs(graphs)
    else:
        pairs = [(g.edges, g.num_nodes) if hasattr(g, "num_nodes") else g
                 for g in graphs]
        batches = bucketize(pairs)
    results: list[CCResult | None] = [None] * len(graphs)
    for batch in batches:
        res = _cc_batched_jit(
            jnp.asarray(batch.edges),
            jax.device_put(np.asarray(batch.true_edges)),
            jax.device_put(np.asarray(batch.true_nodes)),
            num_nodes=batch.num_nodes,
            num_segments=num_segments)
        if device_in:
            # stay on device: per-row static slices, no transfers
            for row, i in enumerate(batch.indices):
                n = int(batch.true_nodes[row])
                results[int(i)] = CCResult(
                    labels=res.labels[row, :n],
                    work=WorkCounters(*(c[row] for c in res.work)))
            continue
        # host views, no per-graph device transfers: [B, V_pad] -> B rows
        labels = np.asarray(res.labels)
        work = jax.tree.map(np.asarray, res.work)
        for row, i in enumerate(batch.indices):
            n = int(batch.true_nodes[row])
            results[int(i)] = CCResult(
                labels=labels[row, :n],
                work=WorkCounters(*(c[row] for c in work)))
    return results  # type: ignore[return-value]


def connected_components_batched(
    graphs: Sequence, *,
    num_segments: int | None = None,
    lift_steps: int = 2,
) -> list[CCResult]:
    """DEPRECATED legacy entrypoint — forwards through the facade's
    ``batched`` backend, bit-identical results."""
    from repro._deprecation import warn_once
    from repro.api import Solver
    warn_once("repro.core.batch.connected_components_batched",
              "repro.api.Solver.solve_batch")
    return Solver.solve_batch(graphs, num_segments=num_segments,
                              lift_steps=lift_steps)
