"""DeviceGraph — the device-resident graph substrate (DESIGN.md §8).

Every execution mode above ``repro.core.rounds`` used to thread graphs
as ad-hoc ``(edges, num_nodes, true_edges)`` tuples with host-side numpy
at the seams (policy feature extraction, service insert coalescing,
distributed partitioning). ``DeviceGraph`` replaces those tuples with
ONE registered pytree that every layer consumes:

  * ``edges``      — on-device int32 [E, 2] COO (possibly padded with
                     (0, 0) no-op self loops);
  * ``num_nodes``  — static |V| (a jit cache key);
  * ``true_edges`` — the unpadded edge count, static int *or* traced
                     int32 scalar (work counters bill true edges only);
  * ``plan``       — the attached ``SegmentationPlan`` (static), keyed
                     on the paper's s = 2|E|/|V| heuristic over the
                     TRUE edge count, covering the stored (padded)
                     edge array;
  * CSR offsets    — built lazily on device via sort + searchsorted
                     (``csr()``), cached on the instance.

Static fields ride in the pytree aux data, so a DeviceGraph crosses
``jax.jit`` boundaries directly and two graphs of one shape bucket hit
one compile. All device-shaping helpers (``pad_pow2``, ``concat``,
``pad_rows``) are jit-backed: under ``jax.transfer_guard("disallow")``
the steady-state service path runs them without a single implicit
host transfer (eager ``jnp.zeros`` would materialize a host constant).

Padding invariant: rows past ``true_edges`` are (0, 0) self loops —
hook no-ops for every engine — and are never billed (see
``rounds.WorkCounters``).

``EdgeLog`` (DESIGN.md §9) extends the substrate to fully-dynamic
workloads: a device-resident append/tombstone log (alive mask, pow2
capacity buckets, sort-based undirected delete matching) whose
``compact_alive`` restores the prefix-padding invariant so the
segmentation machinery and the fused kernel keep working over a log
that has holes.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.batch import next_pow2
from repro.core.segmentation import (SegmentationPlan, adaptive_num_segments,
                                     plan_segmentation)
from repro.obs import trace as obs

_MIN_PAD_ROWS = 8


def validate_edge_bounds(edges: np.ndarray, num_nodes: int) -> None:
    """Raise unless every endpoint lies in [0, num_nodes) — the ONE
    validation rule every host-ingress path shares (registry coerce,
    service admission/rebind). Callers pass a HOST array; device-
    resident paths skip validation by contract (a sync would defeat
    them)."""
    edges = np.asarray(edges)
    if edges.size and (edges.min() < 0 or edges.max() >= num_nodes):
        raise ValueError(f"edge endpoint out of range [0, {num_nodes})")


def measure_degree_skew(edges: np.ndarray, num_nodes: int) -> float:
    """max_degree / mean_degree over the undirected degree sequence —
    the policy's skew feature (~1 on regular/road-like graphs, large on
    power-law/kron-like ones). HOST arrays only: it runs once at
    ``from_edges`` ingest, where the edges are on host anyway; graphs
    that arrive device-resident skip it (skew stays None) rather than
    pay a transfer."""
    edges = np.asarray(edges)
    if edges.size == 0 or num_nodes <= 0:
        return 1.0
    deg = np.bincount(
        np.concatenate([edges[:, 0], edges[:, 1]]), minlength=num_nodes)
    mean = 2.0 * edges.shape[0] / num_nodes
    return float(deg.max() / max(mean, 1e-9))


@functools.partial(jax.jit, static_argnames=("rows",))
def _pad_rows_jit(edges: jnp.ndarray, *, rows: int) -> jnp.ndarray:
    """Append ``rows`` (0, 0) no-op rows on device (jitted so it stays
    transfer-free under ``jax.transfer_guard``)."""
    return jnp.concatenate(
        [edges, jnp.zeros((rows, 2), edges.dtype)], axis=0)


@functools.partial(jax.jit, static_argnames=("num_nodes",))
def _build_csr_jit(edges: jnp.ndarray, *, num_nodes: int):
    """On-device CSR offsets: sort edges by source, then binary-search
    the row starts (no host bincount/cumsum round trip)."""
    src = edges[:, 0]
    order = jnp.argsort(src, stable=True)
    sorted_src = src[order]
    neighbors = edges[order, 1]
    offsets = jnp.searchsorted(
        sorted_src, jnp.arange(num_nodes + 1, dtype=jnp.int32))
    return offsets.astype(jnp.int32), neighbors


@jax.tree_util.register_pytree_node_class
class DeviceGraph:
    """Device-resident COO graph + segmentation plan (one pytree)."""

    def __init__(self, edges, num_nodes: int, true_edges,
                 plan: SegmentationPlan, name: str = "graph",
                 degree_skew: float | None = None):
        self.edges = edges                     # int32 [E, 2], device
        self.num_nodes = int(num_nodes)        # static
        self.true_edges = true_edges           # static int | traced scalar
        self.plan = plan                       # static
        self.name = name
        # static metadata: max_degree / mean_degree, measured once at
        # host ingest (None when the edges arrived device-resident — a
        # host pass would violate transfer discipline). Policy feature
        # for the sampled routing rule; rides in the pytree aux.
        self.degree_skew = degree_skew
        self._csr = None                       # lazy (offsets, neighbors)

    # -- pytree protocol ---------------------------------------------------

    def tree_flatten(self):
        if self.true_edges_static is not None:
            return ((self.edges,),
                    (self.num_nodes, self.true_edges_static, self.plan,
                     self.name, self.degree_skew))
        return ((self.edges, self.true_edges),
                (self.num_nodes, None, self.plan, self.name,
                 self.degree_skew))

    @classmethod
    def tree_unflatten(cls, aux, children):
        num_nodes, true_static, plan, name, degree_skew = aux
        if true_static is not None:
            (edges,) = children
            return cls(edges, num_nodes, true_static, plan, name=name,
                       degree_skew=degree_skew)
        edges, true_edges = children
        return cls(edges, num_nodes, true_edges, plan, name=name,
                   degree_skew=degree_skew)

    # -- constructors ------------------------------------------------------

    @classmethod
    def from_edges(cls, edges, num_nodes: int, *, true_edges=None,
                   num_segments: int | None = None,
                   name: str = "graph", device=None) -> "DeviceGraph":
        """The raw-array shim: accepts host numpy / lists (explicitly
        device_put) or already-device jnp arrays (left in place). Host
        ingest also measures ``degree_skew`` (free while the array is
        on host; device-resident arrays keep it None). ``device=``
        COMMITS the edges to one device (the fleet's per-device
        pinning; None keeps today's default placement)."""
        degree_skew = None
        if isinstance(edges, jnp.ndarray):
            edges = edges.astype(jnp.int32).reshape(-1, 2)
            if device is not None:
                edges = jax.device_put(edges, device)
        else:
            # host ingest spans; ``ingest.put`` times the call, which
            # may return before the copy to the device ends
            with obs.span("ingest.convert"):
                host = np.asarray(edges, np.int32).reshape(-1, 2)
            t = true_edges if isinstance(true_edges, (int, np.integer)) \
                else host.shape[0]
            with obs.span("ingest.skew"):
                degree_skew = measure_degree_skew(host[:int(t)],
                                                  int(num_nodes))
            with obs.span("ingest.put"):
                edges = jax.device_put(host, device)
        e_stored = int(edges.shape[0])
        if true_edges is None:
            true_edges = e_stored
        plan = _plan_for(e_stored, int(num_nodes), true_edges, num_segments)
        return cls(edges, int(num_nodes), true_edges, plan, name=name,
                   degree_skew=degree_skew)

    @classmethod
    def from_host(cls, graph, *, num_segments: int | None = None
                  ) -> "DeviceGraph":
        """From a host ``repro.graphs.format.Graph`` (one device_put)."""
        return cls.from_edges(graph.edges, graph.num_nodes,
                              num_segments=num_segments,
                              name=getattr(graph, "name", "graph"))

    # -- static metadata (policy features — zero host round-trips) ---------

    @property
    def true_edges_static(self) -> int | None:
        """The true edge count when known statically, else None."""
        if isinstance(self.true_edges, (int, np.integer)):
            return int(self.true_edges)
        return None

    @property
    def num_edges(self) -> int:
        """Best static edge count: true if static, else the stored
        (padded) row count."""
        t = self.true_edges_static
        return t if t is not None else int(self.edges.shape[0])

    @property
    def density(self) -> float:
        """The paper's segmentation key 2|E|/|V| from static metadata."""
        return 2.0 * self.num_edges / max(self.num_nodes, 1)

    def true_edges_device(self) -> jnp.ndarray:
        """The true edge count as a device scalar (explicit transfer —
        legal under ``transfer_guard('disallow')``)."""
        if isinstance(self.true_edges, jnp.ndarray):
            return self.true_edges
        return jax.device_put(np.int32(self.true_edges))

    # -- device-side shaping -----------------------------------------------

    def pad_rows(self, target: int) -> "DeviceGraph":
        """Pad the stored edge array with (0, 0) no-ops to ``target``
        rows (device-side, jitted). ``true_edges`` is preserved."""
        e = int(self.edges.shape[0])
        if target <= e:
            return self
        edges = _pad_rows_jit(self.edges, rows=target - e)
        plan = _plan_for(target, self.num_nodes, self.true_edges, None)
        return DeviceGraph(edges, self.num_nodes, self.true_edges, plan,
                           name=self.name, degree_skew=self.degree_skew)

    def pad_pow2(self, min_rows: int = _MIN_PAD_ROWS) -> "DeviceGraph":
        """Pad to the next power-of-two row count (floored at
        ``min_rows``) — the shape-bucket rule of ``repro.core.batch``
        (same ``next_pow2``, so both layers share jit-cache buckets),
        letting a stream of ragged batches hit a handful of entries."""
        e = int(self.edges.shape[0])
        return self.pad_rows(next_pow2(max(e, min_rows)))

    @classmethod
    def concat(cls, graphs: Sequence["DeviceGraph"],
               name: str | None = None) -> "DeviceGraph":
        """Device-side concatenation of same-|V| graphs (the service's
        insert-coalescing primitive — replaces host ``np.concatenate``).
        Every part needs a STATIC true count; counts sum statically.

        Parts with static padding are trimmed first so the result keeps
        the prefix invariant (first ``true_edges`` rows are real) that
        per-segment billing and the fused kernel's edge masking rely on.
        """
        graphs = list(graphs)
        if not graphs:
            raise ValueError("concat needs at least one DeviceGraph")
        if len({g.num_nodes for g in graphs}) != 1:
            raise ValueError("concat requires identical num_nodes, got "
                             f"{[g.num_nodes for g in graphs]}")
        if len(graphs) == 1:
            return graphs[0]
        parts, trues = [], []
        for g in graphs:
            s = g.true_edges_static
            if s is None:
                # a traced-count part MAY be padded, and its pads would
                # land in the interior where the kernel's mask reads
                # them as real — refuse rather than silently corrupt
                raise ValueError(
                    "concat needs static true_edges on every part "
                    "(prefix-padding invariant)")
            if s < int(g.edges.shape[0]):
                parts.append(g.edges[:s])      # static slice, device op
            else:
                parts.append(g.edges)
            trues.append(s)
        edges = jnp.concatenate(parts, axis=0)
        true = int(sum(trues))
        plan = _plan_for(int(edges.shape[0]), graphs[0].num_nodes, true,
                         None)
        # degree-skew None-join rule: a part without a measured skew
        # (device-side ingest skips the host measurement) must not
        # erase another part's known value — routing on a silently
        # dropped skew flips method="auto" mid-session. Unknown parts
        # are ignored; known parts join by max (skew is a max-over-mean
        # statistic, and the union's skew is at least each part's
        # numerator over a no-smaller edge count scaled by parts —
        # max-of-known is the conservative router-facing bound);
        # all-unknown stays None.
        skews = [g.degree_skew for g in graphs if g.degree_skew is not None]
        skew = max(skews) if skews else None
        return cls(edges, graphs[0].num_nodes, true, plan,
                   name=name or graphs[0].name, degree_skew=skew)

    def shard(self, mesh: Mesh, axis_names: tuple[str, ...] = ("data",)
              ) -> "DeviceGraph":
        """Shard the edge list over the mesh's ``axis_names`` (padding
        with (0, 0) no-ops so non-divisible edge counts split evenly).
        The result is what ``core.distributed.make_distributed_cc``
        consumes."""
        n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
        e = int(self.edges.shape[0])
        per = max(1, (e + n_shards - 1) // n_shards)
        padded = self.pad_rows(per * n_shards)
        spec = P(axis_names if len(axis_names) > 1 else axis_names[0],
                 None)
        edges = jax.device_put(padded.edges, NamedSharding(mesh, spec))
        return DeviceGraph(edges, self.num_nodes, padded.true_edges,
                           padded.plan, name=self.name,
                           degree_skew=self.degree_skew)

    # -- lazy on-device CSR ------------------------------------------------

    def csr(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(offsets int32 [V+1], neighbors int32 [E]) built on device
        via sort + searchsorted, cached. Built over the stored edge
        array; padded (0, 0) rows surface as extra 0->0 entries, so
        callers that need exact degrees should slice to
        ``true_edges_static`` first (``trim()``)."""
        if self._csr is None:
            self._csr = _build_csr_jit(self.edges,
                                       num_nodes=self.num_nodes)
        return self._csr

    def trim(self) -> "DeviceGraph":
        """Drop padded rows (requires a static true count). Metadata —
        ``degree_skew`` in particular — is PRESERVED: the trimmed graph
        is the same edge set, so rebuilding through ``from_edges`` (a
        device-array ingest, which cannot re-measure) would silently
        erase a measured skew and flip ``method="auto"`` routing after
        a shard/trim round trip."""
        t = self.true_edges_static
        if t is None:
            raise ValueError("trim() needs a static true_edges")
        if t == int(self.edges.shape[0]):
            return self
        plan = _plan_for(t, self.num_nodes, t, None)
        return DeviceGraph(self.edges[:t], self.num_nodes, t, plan,
                           name=self.name, degree_skew=self.degree_skew)

    def __repr__(self) -> str:
        t = self.true_edges_static
        return (f"DeviceGraph(|V|={self.num_nodes}, "
                f"|E|={self.edges.shape[0]}"
                + (f", true={t}" if t is not None
                   and t != self.edges.shape[0] else "")
                + f", s={self.plan.num_segments}, name={self.name!r})")


# ---------------------------------------------------------------------------
# EdgeLog — the fully-dynamic edge substrate (DESIGN.md §9)
# ---------------------------------------------------------------------------

def undirected_group_ids(pairs: jnp.ndarray) -> jnp.ndarray:
    """int32 [N] group id per row of an int [N, 2] pair array; two rows
    get the same id iff they denote the same UNDIRECTED edge ((u, v)
    and (v, u) collapse). Pure int32 — a min*|V|+max key encoding would
    overflow int32 at |V| > ~46k and this container has no x64 —
    via a lexicographic two-pass stable sort + boundary cumsum."""
    lo = jnp.minimum(pairs[:, 0], pairs[:, 1]).astype(jnp.int32)
    hi = jnp.maximum(pairs[:, 0], pairs[:, 1]).astype(jnp.int32)
    o1 = jnp.argsort(hi, stable=True)               # secondary key
    o2 = jnp.argsort(lo[o1], stable=True)           # primary key (stable)
    order = o1[o2]
    slo, shi = lo[order], hi[order]
    new_group = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         ((slo[1:] != slo[:-1]) | (shi[1:] != shi[:-1])).astype(jnp.int32)])
    gid_sorted = jnp.cumsum(new_group).astype(jnp.int32)
    return jnp.zeros(pairs.shape[0], jnp.int32).at[order].set(gid_sorted)


def tombstone_mask(edges: jnp.ndarray, alive: jnp.ndarray,
                   dels: jnp.ndarray, d_true: jnp.ndarray
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply a delete batch to an alive mask (pure jnp — composes into
    the caller's jit). A delete of undirected edge {u, v} is
    orientation-blind and kills EVERY alive copy (set semantics over a
    multiset log; duplicates die together). Rows of ``dels`` at index
    >= ``d_true`` are padding and match nothing. Returns
    ``(new_alive, killed)`` where ``killed`` marks the log rows this
    batch actually retired.

    O((E + D) log(E + D)) sort-based matching, no [E, D] broadcast."""
    e, d = edges.shape[0], dels.shape[0]
    gid = undirected_group_ids(jnp.concatenate([edges, dels], axis=0))
    real_del = jnp.arange(d) < d_true               # padding matches nothing
    del_present = jnp.zeros((e + d,), jnp.bool_).at[gid[e:]].max(real_del)
    killed = del_present[gid[:e]] & alive
    return alive & ~killed, killed


def compact_alive(edges: jnp.ndarray, alive: jnp.ndarray
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather alive rows to a (0, 0)-padded prefix (pure jnp). Restores
    the prefix-padding invariant every engine relies on — per-segment
    true-count billing and the fused kernel's edge masking both read
    "first ``true`` rows are real". Returns ``(edges, true_count)``
    with ``true_count`` a traced int32 scalar."""
    packed, true, _ = compact_alive_perm(edges, alive)
    return packed, true


def compact_alive_perm(edges: jnp.ndarray, alive: jnp.ndarray
                       ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``compact_alive`` + the old→new row permutation. Compaction
    renumbers edge slots, and any holder of log-row indices — the
    maintained spanning forest's ``parent_eidx`` — must remap through
    it or silently point at the wrong (or a dead) edge. Returns
    ``(packed, true_count, perm)`` with ``perm[i]`` the compacted
    position of old row ``i``, or -1 if the row was dead."""
    e = alive.shape[0]
    order = jnp.argsort(~alive, stable=True)        # alive rows first
    packed = jnp.where(alive[order][:, None], edges[order], 0)
    perm = jnp.zeros((e,), jnp.int32).at[order].set(
        jnp.arange(e, dtype=jnp.int32))
    perm = jnp.where(alive, perm, -1)
    return packed, jnp.sum(alive).astype(jnp.int32), perm


@jax.jit
def _log_delete_jit(edges, alive, dels, d_true):
    return tombstone_mask(edges, alive, dels, d_true)


@jax.jit
def _compact_perm_jit(edges, alive):
    packed, true, perm = compact_alive_perm(edges, alive)
    new_alive = jnp.arange(alive.shape[0], dtype=jnp.int32) < true
    return packed, new_alive, perm, true


@jax.jit
def _append_jit(edges, alive, block, true_count, rows):
    """Write a pow2-padded ``block`` at row offset ``rows``, marking
    its first ``true_count`` rows alive and scrubbing the rest to
    (0, 0). BOTH the offset and the true count are TRACED device
    scalars — a static offset would recompile once per append cursor
    value, a static count once per batch size; this way a long-lived
    stream hits one entry per (capacity, block) pow2 shape pair."""
    p = block.shape[0]
    mask = jnp.arange(p, dtype=jnp.int32) < true_count
    block = jnp.where(mask[:, None], block, 0)
    zero = jnp.zeros((), jnp.int32)
    edges = jax.lax.dynamic_update_slice(edges, block, (rows, zero))
    alive = jax.lax.dynamic_update_slice(alive, mask, (rows,))
    return edges, alive


@functools.partial(jax.jit, static_argnames=("target",))
def _grow_jit(edges, alive, *, target):
    pad = target - edges.shape[0]
    edges = jnp.concatenate([edges, jnp.zeros((pad, 2), edges.dtype)])
    alive = jnp.concatenate([alive, jnp.zeros((pad,), jnp.bool_)])
    return edges, alive


class EdgeLog:
    """Device-resident append/tombstone edge log — the substrate of
    fully-dynamic connectivity (DESIGN.md §9).

    * ``edges`` [cap, 2] int32 on device; rows beyond the append cursor
      are (0, 0) and dead;
    * ``alive`` [cap] bool on device — the tombstone mask. Inserts set
      it, deletes clear it; how many rows a delete batch actually
      killed is known only on device (the steady-state tick never
      syncs it);
    * capacity grows by the power-of-two bucket rule of
      ``repro.core.batch`` (``next_pow2``), so a stream of appends hits
      a handful of jit cache entries — the same shape-bucket discipline
      the batched engine and the service's query microbatcher use.

    The log deliberately does NOT compact on delete: tombstoning is
    O(E log D) with zero allocation churn, and every consumer masks by
    ``alive`` anyway. ``compact()`` (sort-to-prefix + (0, 0) scrub via
    ``compact_alive``) restores the prefix invariant on demand — the
    bulk-rebuild path and ``view()`` use it so the segmentation plan
    and the fused kernel see well-formed prefix padding.
    """

    def __init__(self, num_nodes: int, *, capacity: int = 64):
        self.num_nodes = int(num_nodes)
        cap = next_pow2(max(capacity, 8))
        self.edges = jnp.zeros((cap, 2), jnp.int32)
        self.alive = jnp.zeros((cap,), jnp.bool_)
        self.rows = 0                   # host append cursor (static sizes)

    @property
    def capacity(self) -> int:
        return int(self.edges.shape[0])

    def num_alive_device(self) -> jnp.ndarray:
        """Alive edge count as a device scalar (no sync)."""
        return jnp.sum(self.alive).astype(jnp.int32)

    @property
    def num_alive(self) -> int:
        """Alive edge count (syncs; introspection only)."""
        return int(self.num_alive_device())

    def append(self, delta: "DeviceGraph") -> None:
        """Append a delta's TRUE rows (device-side; needs a static true
        count, like ``DeviceGraph.concat``). The write lands as a
        pow2-padded block whose tail is scrubbed dead in-jit, so
        ragged batch sizes share compile entries; capacity grows by
        pow2 buckets and leaves headroom for the padded block (the
        cursor still advances by the TRUE count — the next append
        overwrites the dead tail)."""
        t = delta.true_edges_static
        if t is None:
            raise ValueError("EdgeLog.append needs a static true_edges "
                             "(prefix-padding invariant)")
        if delta.num_nodes != self.num_nodes:
            raise ValueError(f"delta num_nodes {delta.num_nodes} != "
                             f"{self.num_nodes}")
        if t == 0:
            return
        p = next_pow2(max(t, _MIN_PAD_ROWS))
        if self.rows + p > self.capacity:     # headroom for the block
            self.edges, self.alive = _grow_jit(
                self.edges, self.alive, target=next_pow2(self.rows + p))
        stored = int(delta.edges.shape[0])
        block = delta.edges[:p] if stored >= p \
            else _pad_rows_jit(delta.edges, rows=p - stored)
        # explicit device_puts: legal under
        # jax.transfer_guard("disallow"), unlike implicit host-scalar
        # jit arguments
        self.edges, self.alive = _append_jit(
            self.edges, self.alive, block,
            jax.device_put(np.int32(t)),
            jax.device_put(np.int32(self.rows)))
        self.rows += t

    def delete(self, dels: jnp.ndarray, d_true) -> jnp.ndarray:
        """Standalone tombstone application (the registry's bulk-rebuild
        delete route — the scoped-recompute route fuses
        ``tombstone_mask`` into the DynamicCC delete jit instead).
        Returns the killed mask (device; never synced here)."""
        self.alive, killed = _log_delete_jit(
            self.edges, self.alive, jnp.asarray(dels, jnp.int32),
            jnp.asarray(d_true, jnp.int32))
        return killed

    def view(self) -> "DeviceGraph":
        """The alive edge set as a compacted DeviceGraph (traced true
        count; prefix invariant restored on device). This is what the
        bulk-rebuild path feeds to the static engines."""
        packed, true = compact_alive(self.edges, self.alive)
        plan = _plan_for(self.capacity, self.num_nodes, true, None)
        return DeviceGraph(packed, self.num_nodes, true, plan, name="log")

    def compact(self) -> jnp.ndarray:
        """In-place compaction: pack alive rows to the prefix, scrub the
        tail, and pull the append cursor back to the alive count (ONE
        host sync, for the cursor — this is a maintenance operation,
        not a steady-state tick). Returns the old→new row permutation
        (int32 [cap], -1 for retired rows) so holders of log-row
        indices — ``DynamicCC``'s maintained ``parent_eidx`` — can
        remap; dropping it on the floor is the seeded bug
        ``fixture.stale_forest_idx`` demonstrates."""
        self.edges, self.alive, perm, true = _compact_perm_jit(
            self.edges, self.alive)
        self.rows = int(true)
        return perm

    def __repr__(self) -> str:
        return (f"EdgeLog(|V|={self.num_nodes}, cap={self.capacity}, "
                f"rows={self.rows})")


def _plan_for(e_stored: int, num_nodes: int, true_edges,
              num_segments: int | None) -> SegmentationPlan:
    """Plan over the STORED row count, with the paper's s = 2|E|/|V|
    heuristic evaluated on the TRUE count when it is static (padding
    must not inflate the segment count)."""
    if num_segments is None:
        heur = true_edges if isinstance(true_edges, (int, np.integer)) \
            else e_stored
        num_segments = adaptive_num_segments(int(heur), num_nodes)
    return plan_segmentation(e_stored, num_nodes, num_segments)


def as_device_graph(graph, num_nodes: int | None = None, *,
                    num_segments: int | None = None) -> DeviceGraph:
    """Coerce any accepted graph spelling to a DeviceGraph:

      * a ``DeviceGraph`` — returned as-is (``num_segments`` override
        rebuilds the plan only);
      * a host ``Graph`` (anything with ``.edges``/``.num_nodes``);
      * raw ``(edges, num_nodes)`` arrays — the compatibility shim.
    """
    if isinstance(graph, DeviceGraph):
        if num_segments is not None and \
                num_segments != graph.plan.num_segments:
            plan = plan_segmentation(int(graph.edges.shape[0]),
                                     graph.num_nodes, num_segments)
            return DeviceGraph(graph.edges, graph.num_nodes,
                               graph.true_edges, plan, name=graph.name,
                               degree_skew=graph.degree_skew)
        return graph
    if hasattr(graph, "edges") and hasattr(graph, "num_nodes"):
        return DeviceGraph.from_edges(graph.edges, graph.num_nodes,
                                      num_segments=num_segments,
                                      name=getattr(graph, "name", "graph"))
    if num_nodes is None:
        raise ValueError("raw edge arrays need an explicit num_nodes")
    return DeviceGraph.from_edges(graph, num_nodes,
                                  num_segments=num_segments)
