"""Adaptive method selection — the paper's heuristic as a serving policy.

The paper's contribution is *adaptivity*: pick the work-efficient
schedule from the input's shape instead of hardcoding it. This module
is the brain behind ``connected_components(..., method="auto")`` and
the registry's insert path. Two layers:

1. **Heuristic** (`heuristic_method`) — the paper's segmentation rule
   on cheap, O(1) features (|V|, |E|, density 2|E|/|V|, update rate):

   * a pending insert batch that is small relative to the absorbed
     edge set (update rate <= ``UPDATE_RATE_ABSORB``) is an
     ``incremental-absorb`` (hook only the delta; Hong et al.) — a
     bulk load falls through to a static method on the accumulated set;
   * a pending DELETE batch that is small relative to the alive edge
     set (delete rate <= ``DELETE_RATE_SCOPED``) is a
     ``tombstone-delete`` (scoped recompute over the affected
     components only, DESIGN.md §9) — a bulk drop falls through to a
     static rebuild over the survivors;
   * density < ``MIN_SEGMENT_DENSITY``: s = 2|E|/|V| rounds to <= 1
     segment, so segmentation degenerates — run ``atomic_hook``
     (one segment, no scan overhead);
   * density >= ``LABELPROP_DENSITY_FRAC`` * |V| (near-clique regime,
     O(1) diameter): ``labelprop`` converges in a sweep or two and
     skips the hook/compress machinery;
   * otherwise: ``adaptive`` (the paper's default, Fig. 4).

2. **Autotune cache** (`AutotuneCache`) — measured truth beats
   modeling. Wall-clock winners are cached per *bucketed* shape (the
   power-of-two (V_pad, E_pad) bucket of ``repro.core.batch``, so one
   measurement covers a whole size regime), persisted as JSON
   (``{"version": 1, "entries": {"v1024_e4096": {"method": ...,
   "ms": ...}, ...}}``), and warm-started by the benchmark sweep
   (``benchmarks/run.py --only service`` calls `warm_start`).

Selection order in `select_method`: update-rate rule first (absorb vs
static is structural, not tunable), then autotune-cache hit, then the
heuristic. Set ``REPRO_AUTOTUNE_CACHE=/path.json`` to persist the
default process-wide cache across runs.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time

import numpy as np

from repro.obs import trace as obs

# the static engines the heuristic chooses between; the k-out sampling
# engine joins the measured (autotune) candidate set below. No Pallas
# backend is a candidate: none of the CC kernels lowers for a TPU, so
# routing never reaches them (a forced method still fails loudly there)
STATIC_METHODS = ("adaptive", "atomic_hook", "labelprop")
AUTOTUNE_METHODS = STATIC_METHODS + ("sampled",)
INCREMENTAL_ABSORB = "incremental-absorb"
# delete-path routes (DESIGN.md §9): tombstone + scoped recompute over
# the affected components only — the fused variant (forced only, via
# ``delete_route``) runs the scoped scan through the cc_fused kernel; a
# bulk delete falls through to a static rebuild over the surviving log
DYNAMIC_DELETE = "tombstone-delete"
DYNAMIC_DELETE_FUSED = "tombstone-delete-fused"
# the tree-aware route (DESIGN.md §14): classify the batch against the
# maintained spanning forest, short-circuit all-non-tree batches, and
# reconnect via the forest skeleton + replacement edges otherwise
DYNAMIC_DELETE_FOREST = "tombstone-delete-forest"
DELETE_METHODS = (DYNAMIC_DELETE, DYNAMIC_DELETE_FUSED,
                  DYNAMIC_DELETE_FOREST)

# heuristic thresholds (see module docstring)
UPDATE_RATE_ABSORB = 0.5       # delta/total above this is a bulk load
DELETE_RATE_SCOPED = 0.5       # deletes/alive above this is a bulk drop
# tree-hit-rate routing: min(|V|-1, |E|)/|E| bounds the fraction of
# alive edges that can be spanning-tree edges — i.e. the expected
# tree-hit rate of a uniform delete batch. Below the threshold most
# deletes are non-tree and the forest route's short-circuit/skeleton
# reconnection wins; near 1 (road-like |E| ~ |V|) nearly every delete
# IS a tree edge and the plain scoped recompute is already right-sized
FOREST_TREE_RATIO = 0.75
MIN_SEGMENT_DENSITY = 1.5      # below: s = round(2E/V) <= 1 segment
LABELPROP_DENSITY_FRAC = 0.25  # density >= frac*V: near-clique regime
# k-out sampling routing (Hong et al.): max_degree/mean_degree above
# SAMPLED_SKEW marks a power-law/kron-like graph where the sampling
# phase collapses the giant component cheaply; road-like graphs sit
# near 1 and skip it. The edge floor keeps tiny graphs (the whole test
# corpus) on the exact engines — sampling's two extra jit launches
# only pay for themselves at scale.
SAMPLED_SKEW = 8.0
SAMPLED_MIN_EDGES = 4096

CACHE_FORMAT_VERSION = 1


@dataclasses.dataclass(frozen=True)
class GraphFeatures:
    """Cheap selection features — all O(1) from array shapes."""

    num_nodes: int
    num_edges: int              # edges already absorbed (static: total)
    delta_edges: int | None = None    # pending insert batch (None: static)
    delta_deletes: int | None = None  # pending delete batch (None: static)
    degree_skew: float | None = None  # max_deg/mean_deg (None: unmeasured)

    @property
    def total_edges(self) -> int:
        return self.num_edges + (self.delta_edges or 0)

    @property
    def remaining_edges(self) -> int:
        """Post-delete edge-count upper bound (a delete row retires at
        most every copy of one edge; absent rows retire nothing)."""
        return max(self.num_edges - (self.delta_deletes or 0), 0)

    @property
    def density(self) -> float:
        """The paper's segmentation key: 2|E|/|V| (average degree)."""
        return 2.0 * self.total_edges / max(self.num_nodes, 1)

    @property
    def update_rate(self) -> float:
        """|delta E| / |E total| — 0 for a static (no-delta) call."""
        if self.delta_edges is None:
            return 0.0
        return self.delta_edges / max(self.total_edges, 1)

    @property
    def delete_rate(self) -> float:
        """|delete batch| / |E alive| — the delete-side twin of
        ``update_rate``: a batch small relative to the surviving set is
        worth scoping, a bulk drop is worth a static rebuild."""
        if self.delta_deletes is None:
            return 0.0
        return self.delta_deletes / max(self.num_edges, 1)

    @property
    def tree_edge_ratio(self) -> float:
        """Upper bound on the fraction of alive edges that are
        spanning-tree edges: min(|V|-1, |E|)/|E| — the expected
        tree-hit rate of a uniform delete batch (the delete-route
        feature behind ``FOREST_TREE_RATIO``)."""
        if self.num_edges <= 0:
            return 1.0
        return min(self.num_nodes - 1, self.num_edges) / self.num_edges


def extract_features(num_nodes: int, num_edges: int,
                     delta_edges: int | None = None,
                     delta_deletes: int | None = None,
                     degree_skew: float | None = None) -> GraphFeatures:
    return GraphFeatures(num_nodes=int(num_nodes),
                         num_edges=int(num_edges),
                         delta_edges=None if delta_edges is None
                         else int(delta_edges),
                         delta_deletes=None if delta_deletes is None
                         else int(delta_deletes),
                         degree_skew=None if degree_skew is None
                         else float(degree_skew))




def heuristic_method(f: GraphFeatures) -> str:
    """The paper's segmentation heuristic as a method choice."""
    if f.delta_deletes is not None:
        if f.num_edges > 0 and f.delete_rate <= DELETE_RATE_SCOPED:
            if f.tree_edge_ratio <= FOREST_TREE_RATIO:
                # mostly-non-tree regime: the maintained-forest route
                # short-circuits the common all-non-tree batch
                return DYNAMIC_DELETE_FOREST
            return DYNAMIC_DELETE
        # bulk drop: a static engine over the surviving edge set beats
        # scoping (most components are affected anyway)
        return heuristic_method(GraphFeatures(f.num_nodes,
                                              f.remaining_edges))
    if (f.delta_edges is not None and f.num_edges > 0
            and f.update_rate <= UPDATE_RATE_ABSORB):
        return INCREMENTAL_ABSORB
    if f.num_nodes <= 1 or f.total_edges == 0:
        return "adaptive"              # trivial either way
    if (f.degree_skew is not None and f.degree_skew >= SAMPLED_SKEW
            and f.total_edges >= SAMPLED_MIN_EDGES
            and f.density >= MIN_SEGMENT_DENSITY):
        return "sampled"               # skewed at scale: sampling wins
    if f.density < MIN_SEGMENT_DENSITY:
        return "atomic_hook"
    if f.density >= LABELPROP_DENSITY_FRAC * f.num_nodes:
        return "labelprop"
    return "adaptive"


# ---------------------------------------------------------------------------
# Measured autotune cache
# ---------------------------------------------------------------------------

class AutotuneCache:
    """Measured best-method table keyed on the power-of-two shape bucket.

    JSON format (``CACHE_FORMAT_VERSION``)::

        {"version": 1,
         "entries": {"v1024_e4096": {"method": "adaptive", "ms": 1.93,
                                     "num_nodes": 1000, "num_edges": 3900},
                     ...}}

    A lookup for any graph landing in a recorded bucket returns the
    measured winner; ``measure`` times the static candidates and
    records one. ``path=None`` keeps the table in memory only.
    """

    def __init__(self, path: str | None = None):
        self.path = path
        self.entries: dict[str, dict] = {}
        if path and os.path.exists(path):
            self.load()

    @staticmethod
    def key(num_nodes: int, num_edges: int) -> str:
        from repro.core.batch import bucket_shape
        v_pad, e_pad = bucket_shape(num_nodes, num_edges)
        return f"v{v_pad}_e{e_pad}"

    def lookup(self, num_nodes: int, num_edges: int) -> str | None:
        ent = self.entries.get(self.key(num_nodes, num_edges))
        # always-on obs counters: cold-cache serving (miss-heavy
        # steady state) must be visible in the tick summary
        obs.count("autotune.hit" if ent else "autotune.miss")
        return ent["method"] if ent else None

    def record(self, num_nodes: int, num_edges: int, method: str,
               ms: float) -> None:
        self.entries[self.key(num_nodes, num_edges)] = {
            "method": method, "ms": round(float(ms), 4),
            "num_nodes": int(num_nodes), "num_edges": int(num_edges)}
        if self.path:
            self.save()

    def save(self) -> None:
        """Atomic write: a process-unique temp file in the target dir +
        an atomic rename (``os.replace`` — rename semantics with
        cross-platform overwrite) — two concurrent
        ``ConnectivityService`` processes can interleave saves without
        ever corrupting the JSON (a fixed ``.tmp`` name would let their
        writes interleave in the SAME temp file; last rename still
        wins, but both renames are atomic)."""
        payload = {"version": CACHE_FORMAT_VERSION, "entries": self.entries}
        target = os.path.abspath(self.path)
        os.makedirs(os.path.dirname(target), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(target),
                                   prefix=os.path.basename(target) + ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as fh:
                json.dump(payload, fh, indent=1, sort_keys=True)
            os.replace(tmp, target)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def load(self) -> None:
        with open(self.path) as fh:
            payload = json.load(fh)
        if payload.get("version") != CACHE_FORMAT_VERSION:
            return                      # stale format: start fresh
        # a winner ``measure`` can no longer record (a backend since
        # taken off the candidate set) is stale: drop it, never route it
        self.entries = {k: v for k, v in payload.get("entries", {}).items()
                        if v.get("method") in AUTOTUNE_METHODS}

    def measure(self, edges, num_nodes: int,
                methods: tuple[str, ...] | None = None,
                reps: int = 2) -> str:
        """Time each candidate (``AUTOTUNE_METHODS`` — the same set on
        every platform) on this graph, record and return the
        wall-clock winner for its shape bucket. Every rep
        drains in-flight async work with ``block_until_ready`` BEFORE
        starting its timer — otherwise asynchronously dispatched work
        from the previous candidate flatters whichever method is
        measured next. Candidates run THROUGH the facade
        (``repro.api.solve``) so the measurement prices exactly what a
        production ``method="auto"`` call will pay — backend dispatch
        included."""
        from repro.api import solve
        if methods is None:
            methods = AUTOTUNE_METHODS
        edges = np.asarray(edges, np.int32).reshape(-1, 2)
        best_method, best_ms = None, float("inf")
        for method in methods:
            warm = solve(edges, num_nodes, method=method)
            warm.labels.block_until_ready()
            ts = []
            for _ in range(reps):
                warm.labels.block_until_ready()   # quiesce before t0
                t0 = time.perf_counter()
                warm = solve(edges, num_nodes, method=method)
                warm.labels.block_until_ready()
                ts.append(time.perf_counter() - t0)
            ms = float(np.median(ts)) * 1e3
            if ms < best_ms:
                best_method, best_ms = method, ms
        self.record(num_nodes, edges.shape[0], best_method, best_ms)
        return best_method


def warm_start(graphs, cache: AutotuneCache, reps: int = 2
               ) -> AutotuneCache:
    """Benchmark-sweep warm start: measure every graph's bucket once."""
    for g in graphs:
        if cache.lookup(g.num_nodes, g.num_edges) is None:
            cache.measure(g.edges, g.num_nodes, reps=reps)
    return cache


_default_cache: AutotuneCache | None = None


def default_cache() -> AutotuneCache:
    """Process-wide cache; persisted iff ``REPRO_AUTOTUNE_CACHE`` names
    a JSON path."""
    global _default_cache
    if _default_cache is None:
        _default_cache = AutotuneCache(
            os.environ.get("REPRO_AUTOTUNE_CACHE"))
    return _default_cache


# ---------------------------------------------------------------------------
# The selection entry point
# ---------------------------------------------------------------------------

def select_static_explained(num_nodes: int, num_edges: int, *,
                            degree_skew: float | None = None,
                            cache: AutotuneCache | None = None
                            ) -> tuple[str, str]:
    """Static-solve selection WITH its provenance: ``(method, reason)``
    where reason is ``"autotune"`` (measured cache hit for the shape
    bucket) or ``"heuristic"`` (the paper's density rule, including the
    degree-skew sampling rule when the caller measured skew at ingest).
    This is what ``repro.api`` plans report via
    ``ExecutionPlan.explain()`` — ``select_method`` routes through it
    so the facade's account of the decision can never drift from the
    decision itself."""
    f = extract_features(num_nodes, num_edges, degree_skew=degree_skew)
    cache = default_cache() if cache is None else cache
    with obs.span("policy.select", num_nodes=f.num_nodes,
                  num_edges=f.total_edges) as sp:
        hit = cache.lookup(f.num_nodes, f.total_edges)
        if hit is not None:
            sp.tag(method=hit, reason="autotune")
            return hit, "autotune"
        choice = heuristic_method(f)
        sp.tag(method=choice, reason="heuristic")
        return choice, "heuristic"


def select_method(num_nodes: int, num_edges: int, *,
                  delta_edges: int | None = None,
                  delta_deletes: int | None = None,
                  degree_skew: float | None = None,
                  cache: AutotuneCache | None = None) -> str:
    """Pick the execution method from graph features.

    Static callers (``connected_components(method="auto")``) pass sizes
    only and get a method from ``STATIC_METHODS``; the registry's
    insert path also passes ``delta_edges`` and may get
    ``"incremental-absorb"`` back; its delete path passes
    ``delta_deletes`` and may get ``tombstone-delete`` or
    ``tombstone-delete-forest`` back. Autotuned winners override the
    heuristic for the static choice.
    """
    if delta_edges is None and delta_deletes is None:
        # static call: one shared path with the facade's plan(), so
        # ExecutionPlan.explain() can never drift from the selection
        return select_static_explained(num_nodes, num_edges,
                                       degree_skew=degree_skew,
                                       cache=cache)[0]
    f = extract_features(num_nodes, num_edges, delta_edges, delta_deletes)
    choice = heuristic_method(f)
    if choice in (INCREMENTAL_ABSORB, DYNAMIC_DELETE,
                  DYNAMIC_DELETE_FOREST):
        # structural routes: the autotune cache ranks static engines
        return choice
    cache = default_cache() if cache is None else cache
    lookup_edges = f.total_edges if f.delta_deletes is None \
        else max(f.remaining_edges, 1)
    hit = cache.lookup(f.num_nodes, lookup_edges)
    return hit if hit is not None else choice


def select_for(num_nodes: int, num_edges: int, delta=None, *,
               delete: bool = False,
               cache: AutotuneCache | None = None) -> str:
    """The registry's mutation-path selection over a pending
    ``DeviceGraph`` delta: the update/delete-rate feature comes from
    the delta's static pytree metadata (true edge count) — no device
    sync, no host round trip of edge data. ``delete=True`` routes the
    batch through the delete-side heuristic (scoped tombstone delete
    vs full static rebuild over the survivors)."""
    size = None if delta is None else delta.num_edges
    with obs.span("policy.select_for", num_edges=num_edges, delta=size,
                  delete=delete) as sp:
        method = select_method(
            num_nodes, num_edges,
            delta_edges=None if delete else size,
            delta_deletes=size if delete else None,
            cache=cache)
        sp.tag(method=method)
        return method
