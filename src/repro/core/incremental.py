"""Incremental Connected Components: absorb edge insertions without a
full recompute (DESIGN.md §6; Hong et al., arXiv 2008.11839) — and,
via ``DynamicCC`` (DESIGN.md §9), edge DELETIONS through a
device-resident tombstone log with scoped recompute and split-aware
version ticks.

``IncrementalCC`` keeps the canonical label array as persistent state.
An insertion batch is absorbed by running the shared cleanup loop
(``rounds.cleanup_rounds``) over ONLY the new edges: hooking a new edge
(u, v) merges the two existing stars by their min roots, the fused
Multi-Jump compress re-flattens, and the loop repeats until every new
edge is consistent. Because the state is always at the canonical min-id
fixed point, the result after any insertion sequence is bit-identical to
a from-scratch run over the accumulated edge set — the tests assert
this against the union-find oracle after every batch.

Cost model (the paper's currency): a from-scratch recompute hooks all
|E_total| edges every time, the incremental absorb hooks only the
|ΔE| new edges — and a batch that lands entirely inside existing
components short-circuits at the initial consistency check, costing
ZERO hook rounds. The work counters accumulate across batches so the
saving is measurable (``benchmarks/run.py --only incremental``).

State residency (DESIGN.md §8): labels AND the label version live on
device and are threaded through the absorb jit — the steady-state
insert path performs ZERO host synchronizations (no ``bool(changed)``,
no per-field ``int(...)``). The version ticks inside the same device
program that detects a merge. Per-batch work counters come back as
int32 device scalars and queue unsynced; they fold into host
arbitrary-precision ints lazily (at ``work`` access, or every
``_DRAIN_EVERY`` batches as an amortized sync point), so accumulated
totals never wrap int32 over a long-lived instance.

Insert batches arrive as host arrays (validated + padded on host) or as
``DeviceGraph``s (``insert_graph`` — the service's coalesced path:
device-side concat + jitted pow2 padding, transfer-free under
``jax.transfer_guard("disallow")``). Padding is (0, 0) no-op edges and
is never billed (true counts thread through the shared core).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rounds
from repro.core.rounds import WorkCounters

_MIN_BATCH_PAD = 64
_DRAIN_EVERY = 256   # fold pending per-batch work into host ints


@jax.jit
def _absorb_jit(pi, new_edges, true_count, version):
    """One absorb: cleanup loop over the new edges + merge detection +
    version tick, all in ONE device program. ``pi`` is the state's
    canonical labels, which are flat, so the loop hooks without a root
    chase. Returns the PER-BATCH work counters (int32 — safe for a
    single batch; the caller accumulates across batches in host
    arbitrary-precision ints, lazily, so no int32 wraparound over a
    long-lived instance)."""
    ops = rounds.jnp_round_ops()
    new_pi, work = rounds.cleanup_rounds(pi, new_edges, ops,
                                         WorkCounters.zeros(),
                                         true_edges=true_count)
    work = work.add(sync_rounds=1)      # one jit call per absorb
    # the label-version counter (query-cache invalidation) must tick
    # ONLY when labels changed — detected on device, no host round trip
    version = version + jnp.any(new_pi != pi).astype(version.dtype)
    return new_pi, version, work


@jax.jit
def _adopt_jit(pi, labels, version):
    changed = jnp.any(labels != pi)
    return labels, version + changed.astype(version.dtype)


class IncrementalCC:
    """Connectivity state under streaming edge insertions.

    >>> inc = IncrementalCC(num_nodes=6)
    >>> inc.insert([[0, 1], [2, 3]])
    >>> inc.connected(0, 1)
    True
    >>> inc.insert([[1, 2]])          # merges {0,1} and {2,3}
    >>> int(inc.labels[3])
    0
    """

    def __init__(self, num_nodes: int):
        if num_nodes < 0:
            raise ValueError(f"num_nodes must be >= 0, got {num_nodes}")
        self.num_nodes = num_nodes
        self._pi = jnp.arange(num_nodes, dtype=jnp.int32)
        self.num_edges_inserted = 0
        self.batches_absorbed = 0
        # device-resident: the version ticks inside the absorb jit
        self._version = jnp.zeros((), jnp.int32)
        # work accounting: each absorb emits per-batch int32 device
        # counters (billed on true edges only); they queue here unsynced
        # and fold into host arbitrary-precision ints lazily — at
        # inspection (``work``) or every _DRAIN_EVERY batches — so the
        # steady-state insert path stays transfer-free AND the
        # accumulated totals never wrap int32
        self._work_host = {k: 0 for k in WorkCounters._fields}
        self._work_pending: list[WorkCounters] = []
        # optional on-device telemetry (repro.obs Metrics pytree):
        # None (default) costs one attribute check per mutation;
        # attached, it is updated by a device program per batch —
        # still transfer-free — and flushed only on explicit request
        self.metrics = None

    def enable_metrics(self) -> None:
        """Attach zeroed ``repro.obs`` Metrics accumulators (no-op if
        already attached)."""
        if self.metrics is None:
            from repro.obs.metrics import Metrics
            self.metrics = Metrics.zeros()

    def _record_metrics(self, kind: str, batch_work, true_count,
                        version_before) -> None:
        """Fold one mutation batch into the attached accumulators —
        every operand is already a device scalar, so the update is one
        more staged program on the tick (no transfer)."""
        if self.metrics is None:
            return
        from repro.obs import metrics as obs_metrics
        self.metrics = obs_metrics.record_mutation(
            self.metrics, batch_work, true_count, version_before,
            self._version, kind=kind)

    @property
    def labels(self) -> jnp.ndarray:
        """Canonical min-id labels, [num_nodes] int32."""
        return self._pi

    @property
    def version(self) -> int:
        """Label version as a host int (syncs; see ``version_device``)."""
        return int(self._version)

    @property
    def version_device(self) -> jnp.ndarray:
        """Label version as a device int32 scalar (no sync)."""
        return self._version

    def _drain_work(self) -> None:
        # explicit device_get, not int(): the amortized drain can fire
        # inside a jax.transfer_guard("disallow") region (every
        # _DRAIN_EVERY-th absorb), where implicit conversions raise but
        # explicit transfers are allowed
        for w in jax.device_get(self._work_pending):
            for k, v in w._asdict().items():
                self._work_host[k] += int(v)
        self._work_pending.clear()

    def _queue_work(self, work: WorkCounters | dict | None) -> None:
        if work is None:
            return
        if isinstance(work, WorkCounters):
            self._work_pending.append(work)
        else:
            for k, v in work.items():
                self._work_host[k] += int(v)
        if len(self._work_pending) >= _DRAIN_EVERY:
            self._drain_work()           # rare amortized sync point

    @property
    def work(self) -> dict:
        """Accumulated work counters as host ints (syncs on access)."""
        self._drain_work()
        return dict(self._work_host)

    def insert(self, new_edges) -> jnp.ndarray:
        """Absorb a host-array batch of edge insertions; returns the new
        labels. Self loops, duplicates, and already-connected edges are
        harmless (the latter cost zero hook rounds)."""
        new_edges = np.asarray(new_edges, np.int32).reshape(-1, 2)
        if (new_edges.size and
                (new_edges.min() < 0 or new_edges.max() >= self.num_nodes)):
            raise ValueError("edge endpoint out of range "
                             f"[0, {self.num_nodes})")
        e = new_edges.shape[0]
        self.num_edges_inserted += e
        self.batches_absorbed += 1
        if e == 0 or self.num_nodes == 0:
            return self._pi
        # pad to a power-of-two bucket: few jit entries for a stream of
        # ragged batches ((0,0) self-loop no-ops, never billed)
        target = max(_MIN_BATCH_PAD, 1 << int(e - 1).bit_length())
        padded = np.zeros((target, 2), np.int32)
        padded[:e] = new_edges
        v0, true_count = self._version, jax.device_put(np.int32(e))
        self._pi, self._version, batch_work = _absorb_jit(
            self._pi, jax.device_put(padded), true_count, self._version)
        self._queue_work(batch_work)
        self._record_metrics("insert", batch_work, true_count, v0)
        return self._pi

    def insert_graph(self, delta) -> jnp.ndarray:
        """Absorb a device-resident ``DeviceGraph`` insert batch — the
        registry/service steady-state path. Coalescing (``concat``) and
        pow2 padding happen on device; the absorb jit threads labels,
        version, and work counters without a single host transfer
        (validated under ``jax.transfer_guard("disallow")``). Bounds are
        NOT re-checked on this path (device values; the API boundary
        validates host inputs)."""
        if delta.num_nodes != self.num_nodes:
            raise ValueError(f"delta num_nodes {delta.num_nodes} != "
                             f"{self.num_nodes}")
        self.num_edges_inserted += delta.num_edges
        self.batches_absorbed += 1
        if self.num_nodes == 0 or delta.edges.shape[0] == 0:
            return self._pi
        padded = delta.pad_pow2(min_rows=_MIN_BATCH_PAD)
        v0, true_count = self._version, padded.true_edges_device()
        self._pi, self._version, batch_work = _absorb_jit(
            self._pi, padded.edges, true_count, self._version)
        self._queue_work(batch_work)
        self._record_metrics("insert", batch_work, true_count, v0)
        return self._pi

    def adopt(self, labels, work=None, num_edges: int = 0) -> jnp.ndarray:
        """Adopt externally computed canonical labels as the new state
        (the registry's bulk-load path: the policy routed a large batch
        through a static engine instead of the absorb). Bills ``work``
        (a ``WorkCounters`` or field dict) into the accumulated
        counters and ticks the version iff the labels changed — the
        merge detection runs on device.
        """
        labels = jnp.asarray(labels, jnp.int32)
        if labels.shape != (self.num_nodes,):
            raise ValueError(f"labels shape {labels.shape} != "
                             f"({self.num_nodes},)")
        self.num_edges_inserted += int(num_edges)
        self.batches_absorbed += 1
        self._queue_work(work)
        if self.num_nodes == 0:
            return self._pi
        self._pi, self._version = _adopt_jit(self._pi, labels,
                                             self._version)
        if self.metrics is not None:
            # rebuild work is billed through the engine's own
            # WorkCounters; the accumulator counts the route
            from repro.obs import metrics as obs_metrics
            self.metrics = obs_metrics.record_rebuild(self.metrics)
        return self._pi

    def connected(self, u: int, v: int) -> bool:
        for x in (u, v):
            if not 0 <= x < self.num_nodes:
                raise ValueError(f"vertex {x} out of range "
                                 f"[0, {self.num_nodes})")
        return int(self._pi[u]) == int(self._pi[v])

    def num_components(self) -> int:
        """Component count — on-device sort/segment kernel, no host
        ``np.unique`` round trip (``connectivity.queries``)."""
        from repro.connectivity.queries import count_components
        return int(count_components(self._pi))


# ---------------------------------------------------------------------------
# Fully-dynamic connectivity: + edge deletions (DESIGN.md §9)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_segments", "scan_method",
                                             "interpret"))
def _delete_jit(edges, alive, pi, dels, d_true, version, deleted, *,
                num_segments, scan_method, interpret):
    """One delete tick, ONE device program: tombstone the delete batch
    against the log, detect the affected components, and — only if the
    batch actually retired an edge — run the scoped recompute over
    their surviving edges (``rounds.scoped_rounds``). The version ticks
    iff labels changed, which under a pure-delete batch means an
    ACTUAL SPLIT: a non-bridge deletion reproduces the same canonical
    partition bit-for-bit, so cached query answers stay warm. This is
    the deletion-side mirror of the absorb jit's merge tick — no host
    round trip anywhere on the path."""
    from repro.graphs.device import tombstone_mask
    from repro.core.segmentation import plan_segmentation

    num_nodes = pi.shape[0]
    alive2, killed = tombstone_mask(edges, alive, dels, d_true)
    deleted = deleted + jnp.sum(killed).astype(deleted.dtype)
    plan = plan_segmentation(edges.shape[0], num_nodes, num_segments)

    def recompute(_):
        # components touched by a retired edge: both endpoints of an
        # alive edge share a label, so marking pi[u] covers pi[v]
        aff = jnp.zeros((num_nodes,), jnp.bool_) \
            .at[pi[edges[:, 0]]].max(killed)
        in_aff = aff[pi]                       # vertex in affected comp?
        edge_aff = alive2 & in_aff[edges[:, 0]]
        n_aff_nodes = jnp.sum(in_aff).astype(jnp.int32)
        if scan_method == "pallas_fused":
            ops = rounds.fused_round_ops(interpret=interpret,
                                         bill_nodes=n_aff_nodes)
        else:
            ops = rounds.jnp_round_ops(bill_nodes=n_aff_nodes)
        return rounds.scoped_rounds(pi, edges, edge_aff, in_aff, plan,
                                    ops, WorkCounters.zeros())

    def no_op(_):
        # nothing retired (unknown edges / double deletes): zero hook
        # rounds, zero sweeps — the delete-side analogue of the
        # absorb's already-connected short circuit
        return pi, WorkCounters.zeros()

    pi1, work = jax.lax.cond(jnp.any(killed), recompute, no_op, None)
    work = work.add(sync_rounds=1)             # one jit call per tick
    version = version + jnp.any(pi1 != pi).astype(version.dtype)
    return pi1, alive2, version, deleted, work


# ---------------------------------------------------------------------------
# Maintained spanning forest (DESIGN.md §14): forest-threading jits
# ---------------------------------------------------------------------------

@jax.jit
def _absorb_forest_jit(pi, parents, parent_eidx, new_edges, eid_base,
                       true_count, version):
    """``_absorb_jit`` + forest extension: the batch's true rows were
    just appended to the EdgeLog at offset ``eid_base`` (a TRACED
    device scalar — a static offset would recompile once per append
    cursor value), so batch slot i is log row ``eid_base + i``. A
    winning hook records its edge AND that log row (same scatter-min
    win rule as the forest round variants). Labels and version are
    bit-identical to ``_absorb_jit`` — recording never changes a pi
    update."""
    p = new_edges.shape[0]
    slot = jnp.arange(p, dtype=jnp.int32)
    eids = jnp.where(slot < true_count, eid_base + slot, -1)
    new_pi, parents, parent_eidx, work = rounds.forest_cleanup_rounds_ids(
        pi, parents, parent_eidx, new_edges, eids, WorkCounters.zeros(),
        true_edges=true_count)
    work = work.add(sync_rounds=1)
    version = version + jnp.any(new_pi != pi).astype(version.dtype)
    return new_pi, parents, parent_eidx, version, work


@jax.jit
def _delete_forest_jit(edges, alive, pi, parents, parent_eidx, dels,
                       d_true, version, deleted, routes):
    """The tree-aware delete tick, ONE device program (DESIGN.md §14):

    1. tombstone the batch (orientation-blind multiset matching via
       ``undirected_group_ids`` inside ``tombstone_mask``);
    2. classify tree vs. non-tree hits with one O(V) gather — vertex r
       lost its tree edge iff its recorded log row just died
       (``killed[parent_eidx[r]]``; deleting {u, v} kills EVERY alive
       copy, including the recorded one, so the gather is exact);
    3. ``lax.cond``: zero tree hits → labels, forest, and version are
       UNTOUCHED (the common case bills zero hook rounds and zero
       sweeps); otherwise ``rounds.forest_scoped_rounds`` reconnects
       only the components that lost a tree edge via the surviving
       forest skeleton + crossing replacement edges.

    ``routes`` is an int32 [2] device counter
    (nontree_shortcircuit, tree_scoped) — how a batch classified is
    only known on device and the steady-state tick must not sync to
    find out; hosts drain it lazily into the obs counters."""
    from repro.graphs.device import tombstone_mask

    num_nodes = pi.shape[0]
    alive2, killed = tombstone_mask(edges, alive, dels, d_true)
    deleted = deleted + jnp.sum(killed).astype(deleted.dtype)
    has_parent = parent_eidx >= 0
    safe = jnp.maximum(parent_eidx, 0)
    tree_hit = has_parent & killed[safe]
    any_hit = jnp.any(tree_hit)

    def tree_scoped(_):
        aff = jnp.zeros((num_nodes,), jnp.bool_).at[pi].max(tree_hit)
        in_aff = aff[pi]                   # vertex in an affected comp?
        edge_aff = alive2 & in_aff[edges[:, 0]]
        forest_keep = in_aff & has_parent & ~killed[safe]
        eids = jnp.arange(edges.shape[0], dtype=jnp.int32)
        return rounds.forest_scoped_rounds(
            pi, parents, parent_eidx, edges, eids, edge_aff,
            forest_keep, in_aff, WorkCounters.zeros())

    def no_op(_):
        return pi, parents, parent_eidx, WorkCounters.zeros()

    pi1, parents1, eidx1, work = jax.lax.cond(any_hit, tree_scoped,
                                              no_op, None)
    work = work.add(sync_rounds=1)
    version = version + jnp.any(pi1 != pi).astype(version.dtype)
    routes = routes + jnp.stack([(~any_hit).astype(jnp.int32),
                                 any_hit.astype(jnp.int32)])
    return pi1, alive2, parents1, eidx1, version, deleted, routes, work


@functools.partial(jax.jit, static_argnames=("num_nodes", "num_segments"))
def _rebuild_forest_jit(edges, alive, *, num_nodes, num_segments):
    """From-scratch forest (re)derivation over the surviving log — the
    lazy fallback when a bulk route (static rebuild, tombstone-only
    delete) left the maintained forest stale. Runs the Fig. 4 pipeline
    with id-recording hooks; the resulting labels are canonical and
    therefore bit-identical to the live state's, so assigning them is
    safe and the version must NOT tick."""
    from repro.core.segmentation import plan_segmentation

    e = edges.shape[0]
    ids = jnp.arange(e, dtype=jnp.int32)
    packed, pids, true = rounds.pack_edge_rows(edges, ids, alive)
    plan = plan_segmentation(e, num_nodes, num_segments)
    segments = rounds.pad_and_segment(packed, plan)
    pad = plan.padded_edges - e
    seg_ids = pids if pad <= 0 else jnp.concatenate(
        [pids, jnp.full((pad,), -1, jnp.int32)])
    seg_ids = seg_ids.reshape(plan.num_segments, plan.segment_size)
    counts = rounds.segment_true_counts(true, plan)
    pi0 = jnp.arange(num_nodes, dtype=jnp.int32)
    pi, parents, eidx, work = rounds.forest_segment_scan_ids(
        pi0, rounds.empty_forest(num_nodes),
        rounds.empty_forest_idx(num_nodes), segments, seg_ids,
        WorkCounters.zeros(), counts)
    pi, parents, eidx, work = rounds.forest_cleanup_rounds_ids(
        pi, parents, eidx, packed, pids, work, true_edges=true)
    work = work.add(sync_rounds=1)
    return pi, parents, eidx, work


@jax.jit
def _remap_eidx_jit(parent_eidx, perm):
    """Remap forest log-row pointers through a compaction permutation
    (roots and retired rows stay -1)."""
    safe = jnp.maximum(parent_eidx, 0)
    return jnp.where(parent_eidx >= 0, perm[safe], -1)


class DynamicCC(IncrementalCC):
    """Fully-dynamic connectivity: streaming edge insertions AND
    deletions over one device-resident state (DESIGN.md §9; Hong,
    Dhulipala & Shun, arXiv 2008.11839 motivate why insert-only
    structures break under churn).

    On top of ``IncrementalCC`` this keeps the accumulated edge set in
    a ``graphs.device.EdgeLog`` (tombstone mask + pow2 capacity
    buckets). Inserts append to the log and absorb as before; a delete
    batch tombstones matching log rows and falls back to a *scoped
    recompute* — re-running the Fig. 4 scan over only the components a
    retired edge touched — instead of a full recompute. A deletion
    that is not a bridge reproduces the identical canonical partition,
    so the label version (query-cache invalidation) ticks only on
    ACTUAL splits, exactly mirroring the insert path's merge tick.

    Deletion semantics: a delete of undirected edge {u, v} is
    orientation-blind and retires EVERY alive copy in the (multiset)
    log; deleting an absent edge is a zero-cost no-op. After any
    interleaved insert/delete script the labels are bit-identical to a
    from-scratch run over the surviving edge set (oracle-tested).

    ``scan_method`` picks the scoped-recompute backend: ``"jnp"``
    (default) or ``"pallas_fused"`` (one kernel launch per scoped
    scan) — the policy layer routes this via the delete-rate feature
    (``connectivity.policy.select_for``).

    >>> dyn = DynamicCC(num_nodes=4)
    >>> dyn.insert([[0, 1], [1, 2]])
    >>> dyn.delete([[1, 2]])
    >>> dyn.connected(0, 1), dyn.connected(1, 2)
    (True, False)
    """

    def __init__(self, num_nodes: int, *, scan_method: str = "jnp"):
        super().__init__(num_nodes)
        from repro.graphs.device import EdgeLog
        if scan_method not in ("jnp", "pallas_fused"):
            raise ValueError(f"unknown scan_method {scan_method!r}; "
                             "choose from ('jnp', 'pallas_fused')")
        self.scan_method = scan_method
        self.log = EdgeLog(num_nodes)
        self.delete_batches = 0
        # device-resident retired-edge count: how many log rows a
        # delete batch matched is only known on device, and the
        # steady-state delete tick must not sync to find out
        self._deleted = jnp.zeros((), jnp.int32)
        # maintained spanning forest (DESIGN.md §14): parent edges +
        # the EdgeLog row each was recorded from, extended in-jit by
        # the forest absorb and consumed by the tree-aware delete.
        # ``_forest_valid`` is a HOST flag: bulk routes (adopt,
        # tombstone-only deletes, the plain scoped delete) mutate
        # labels or the log without maintaining the forest, and the
        # next forest-routed delete rebuilds it lazily.
        self._parents = rounds.empty_forest(num_nodes)
        self._parent_eidx = rounds.empty_forest_idx(num_nodes)
        self._forest_valid = True
        # delete-route telemetry: device [nontree_shortcircuit,
        # tree_scoped] counter (ticked inside the delete jit) + host
        # rebuild count; drained into obs by delete_route_counts()
        self._delete_routes = jnp.zeros((2,), jnp.int32)
        self.forest_rebuilds = 0
        self._routes_flushed = {"nontree_shortcircuit": 0,
                                "tree_scoped": 0, "rebuild": 0}

    # -- inserts (log-keeping overrides) -----------------------------------

    def insert(self, new_edges) -> jnp.ndarray:
        """Absorb a host-array insert batch (validated, device_put,
        logged)."""
        from repro.graphs.device import DeviceGraph, validate_edge_bounds
        arr = np.asarray(new_edges, np.int32).reshape(-1, 2)
        validate_edge_bounds(arr, self.num_nodes)
        return self.insert_graph(
            DeviceGraph.from_edges(arr, self.num_nodes))

    def insert_graph(self, delta) -> jnp.ndarray:
        """Absorb a DeviceGraph insert batch; the delta's true rows are
        appended to the device edge log first (static true count
        required — same contract as ``DeviceGraph.concat``). While the
        maintained forest is valid the absorb runs the forest-extending
        jit (labels/version bit-identical to the plain absorb; a
        winning hook also records its log row), so inserts never stale
        the forest."""
        rows_before = self.log.rows
        self.log.append(delta)          # validates |V| + static count
        if not self._forest_valid:
            return super().insert_graph(delta)
        self.num_edges_inserted += delta.num_edges
        self.batches_absorbed += 1
        if self.num_nodes == 0 or delta.edges.shape[0] == 0:
            return self._pi
        padded = delta.pad_pow2(min_rows=_MIN_BATCH_PAD)
        v0, true_count = self._version, padded.true_edges_device()
        (self._pi, self._parents, self._parent_eidx, self._version,
         batch_work) = _absorb_forest_jit(
            self._pi, self._parents, self._parent_eidx, padded.edges,
            jax.device_put(np.int32(rows_before)), true_count,
            self._version)
        self._queue_work(batch_work)
        self._record_metrics("insert", batch_work, true_count, v0)
        return self._pi

    def stage(self, delta) -> None:
        """Append a delta to the log WITHOUT absorbing — the registry's
        bulk-rebuild route, where a static engine recomputes over the
        whole log view and ``adopt``s the result (which does the
        version/work accounting)."""
        self.log.append(delta)

    def adopt(self, labels, work=None, num_edges: int = 0) -> jnp.ndarray:
        """``IncrementalCC.adopt`` + forest invalidation: a static
        engine recomputed labels without recording parent edges, so the
        maintained forest is stale until the next forest-routed delete
        rebuilds it."""
        self._forest_valid = False
        return super().adopt(labels, work=work, num_edges=num_edges)

    # -- deletes ------------------------------------------------------------

    def delete(self, edges) -> jnp.ndarray:
        """Delete a host-array edge batch; returns the new labels."""
        from repro.graphs.device import DeviceGraph, validate_edge_bounds
        arr = np.asarray(edges, np.int32).reshape(-1, 2)
        validate_edge_bounds(arr, self.num_nodes)
        return self.delete_graph(
            DeviceGraph.from_edges(arr, self.num_nodes))

    def delete_graph(self, dels) -> jnp.ndarray:
        """Delete a device-resident ``DeviceGraph`` batch — the
        registry/service steady-state path. Tombstoning, bridge
        detection (did the partition change?), the scoped recompute,
        and the split-version tick all run in ONE device program with
        zero host transfers (validated under
        ``jax.transfer_guard("disallow")``)."""
        if dels.num_nodes != self.num_nodes:
            raise ValueError(f"dels num_nodes {dels.num_nodes} != "
                             f"{self.num_nodes}")
        self.delete_batches += 1
        if self.num_nodes == 0 or dels.edges.shape[0] == 0 \
                or self.log.rows == 0:
            return self._pi
        from repro.core.segmentation import adaptive_num_segments
        from repro.kernels import default_interpret
        padded = dels.pad_pow2(min_rows=_MIN_BATCH_PAD)
        v0, true_count = self._version, padded.true_edges_device()
        (self._pi, self.log.alive, self._version, self._deleted,
         batch_work) = _delete_jit(
            self.log.edges, self.log.alive, self._pi, padded.edges,
            true_count, self._version, self._deleted,
            num_segments=adaptive_num_segments(self.log.capacity,
                                               self.num_nodes),
            scan_method=self.scan_method,
            interpret=default_interpret())
        # the plain scoped recompute does not maintain parent edges —
        # whether it even ran (anything killed?) is device knowledge,
        # so conservatively stale the forest
        self._forest_valid = False
        self._queue_work(batch_work)
        self._record_metrics("delete", batch_work, true_count, v0)
        return self._pi

    def ensure_forest(self) -> None:
        """(Re)derive the maintained forest from the surviving log if a
        bulk route staled it. The rebuild's labels are canonical and
        bit-identical to the live state's, so assigning them is safe;
        the version does not tick. Counts into
        ``dynamic.deletes.rebuild``."""
        if self._forest_valid:
            return
        from repro.core.segmentation import adaptive_num_segments
        from repro.obs import trace as obs
        (self._pi, self._parents, self._parent_eidx,
         work) = _rebuild_forest_jit(
            self.log.edges, self.log.alive, num_nodes=self.num_nodes,
            num_segments=adaptive_num_segments(self.log.capacity,
                                               self.num_nodes))
        self._queue_work(work)
        self._forest_valid = True
        self.forest_rebuilds += 1
        obs.count("dynamic.deletes.rebuild")

    def delete_graph_forest(self, dels) -> jnp.ndarray:
        """Tree-aware delete (DESIGN.md §14): one device program
        tombstones the batch, classifies tree vs. non-tree hits against
        the maintained forest, short-circuits the all-non-tree case
        (labels, forest, and version untouched — ~zero hook_ops), and
        otherwise reconnects only the components that lost a tree edge
        via the surviving forest skeleton + crossing replacement
        edges. Transfer-free on the steady-state path (the lazy
        ``ensure_forest`` fallback is the only exception, and only
        after a bulk route)."""
        if dels.num_nodes != self.num_nodes:
            raise ValueError(f"dels num_nodes {dels.num_nodes} != "
                             f"{self.num_nodes}")
        self.delete_batches += 1
        if self.num_nodes == 0 or dels.edges.shape[0] == 0 \
                or self.log.rows == 0:
            return self._pi
        self.ensure_forest()
        padded = dels.pad_pow2(min_rows=_MIN_BATCH_PAD)
        v0, true_count = self._version, padded.true_edges_device()
        (self._pi, self.log.alive, self._parents, self._parent_eidx,
         self._version, self._deleted, self._delete_routes,
         batch_work) = _delete_forest_jit(
            self.log.edges, self.log.alive, self._pi, self._parents,
            self._parent_eidx, padded.edges, true_count, self._version,
            self._deleted, self._delete_routes)
        self._queue_work(batch_work)
        self._record_metrics("delete", batch_work, true_count, v0)
        return self._pi

    def tombstone_graph(self, dels) -> None:
        """Tombstone a delete batch WITHOUT the scoped recompute — the
        bulk-delete route, where the policy decided a full static
        rebuild over the remaining log beats scoping (the caller
        rebuilds and ``adopt``s; adopt's device-side diff supplies the
        split tick)."""
        if dels.num_nodes != self.num_nodes:
            raise ValueError(f"dels num_nodes {dels.num_nodes} != "
                             f"{self.num_nodes}")
        self.delete_batches += 1
        if self.num_nodes == 0 or dels.edges.shape[0] == 0 \
                or self.log.rows == 0:
            return
        padded = dels.pad_pow2(min_rows=_MIN_BATCH_PAD)
        killed = self.log.delete(padded.edges,
                                 padded.true_edges_device())
        self._deleted = self._deleted + \
            jnp.sum(killed).astype(self._deleted.dtype)
        # rows died without forest maintenance (the caller rebuilds
        # labels via a static engine + adopt, which also invalidates)
        self._forest_valid = False

    def compact(self) -> None:
        """Compact the EdgeLog in place and remap the maintained
        forest's ``parent_eidx`` through the compaction permutation —
        the two must move together or every forest pointer silently
        refers to the wrong post-compaction row (the seeded bug in
        ``analysis/fixtures.py``). One host sync (the log cursor);
        maintenance operation, not a tick."""
        perm = self.log.compact()
        if self._forest_valid:
            self._parent_eidx = _remap_eidx_jit(self._parent_eidx, perm)

    # -- views / introspection ----------------------------------------------

    @property
    def forest(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(parents [V, 2], parent_eidx [V]) — the maintained spanning
        forest (device arrays; -1 rows are component roots). Check
        ``forest_valid`` (or call ``ensure_forest()``) first if a bulk
        route may have staled it."""
        return self._parents, self._parent_eidx

    @property
    def forest_valid(self) -> bool:
        return self._forest_valid

    def delete_route_counts(self, flush_obs: bool = True) -> dict:
        """Drain the delete-route telemetry: syncs the device
        [nontree_shortcircuit, tree_scoped] counter (introspection
        point — never on the steady-state tick) and, unless told
        otherwise, folds the deltas into the host obs counters
        ``dynamic.deletes.{nontree_shortcircuit,tree_scoped,rebuild}``."""
        vals = np.asarray(jax.device_get(self._delete_routes))
        counts = {"nontree_shortcircuit": int(vals[0]),
                  "tree_scoped": int(vals[1]),
                  "rebuild": self.forest_rebuilds}
        if flush_obs:
            from repro.obs import trace as obs
            for k in ("nontree_shortcircuit", "tree_scoped"):
                delta = counts[k] - self._routes_flushed[k]
                if delta:
                    obs.count(f"dynamic.deletes.{k}", delta)
                self._routes_flushed[k] = counts[k]
        return counts

    def graph(self):
        """The surviving edge set as a compacted DeviceGraph (traced
        true count) — what the bulk-rebuild path feeds to the static
        engines."""
        return self.log.view()

    @property
    def num_edges_deleted(self) -> int:
        """Retired-edge count as a host int (syncs; introspection)."""
        return int(self._deleted)

    @property
    def num_edges_alive(self) -> int:
        """Surviving-edge count (syncs; introspection)."""
        return self.log.num_alive
