"""Slot-based connectivity query engine: microbatched interleaved
insert/query traffic over the multi-tenant registry.

Every tenant behind the registry is a ``repro.api.Solver`` session
(DESIGN.md §10), so the service inherits the facade's policy routing
and transfer-free steady-state mutation contract by construction.

Mirrors the admit/step/retire idiom of ``repro.serving.engine``: a
bounded number of request slots per tick; each tick admits queued
requests, executes them in two phases, and retires them with results.

Per tick:

  * **inserts coalesce per tenant** — all admitted insert batches for
    one tenant concatenate into ONE absorb/rebuild call (one device
    dispatch instead of one per request). Payloads are device_put at
    submit time and coalesced with ``DeviceGraph.concat`` ON DEVICE
    (DESIGN.md §8): the steady-state tick performs zero host transfers
    — no ``np.concatenate``, no host-side merge check — which the
    transfer-guard test pins down;
  * **deletes coalesce per tenant** the same way (DESIGN.md §9) — one
    tombstone + scoped-recompute program per tenant per tick, so k
    simultaneous splits ride one stacked scan; the steady-state
    tombstone tick is transfer-free under the same guard;
  * **queries microbatch per (tenant, kind)** — all admitted
    ``same_component`` pairs (resp. ``component_size`` vertices) for a
    tenant concatenate into one batch, padded to the power-of-two
    buckets of ``repro.core.batch``, so every same-shape batch across
    all tenants of one |V| routes through one jit cache entry.

Consistency model: within a tick, inserts apply first, then deletes,
then queries — a query observes every mutation admitted in its tick
(and all earlier ticks), and a delete admitted alongside an insert of
the same edge wins (read-fresh, delete-after-insert semantics).

Every query is served from the live label array — zero label
recomputes. ``stats["recomputes_avoided"]`` counts the full CC runs a
recompute-per-query design would have paid; the ``service`` benchmark
(``benchmarks/run.py --only service``) prices that counterfactual in
hook_ops.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.connectivity.registry import GraphRegistry
from repro.graphs.device import DeviceGraph, validate_edge_bounds
from repro.obs import trace as obs
from repro.obs.slo import SLORecorder

QUERY_KINDS = ("same_component", "component_size", "count_components",
               "component_histogram")
MUTATION_KINDS = ("insert", "delete")
KINDS = MUTATION_KINDS + QUERY_KINDS


@dataclasses.dataclass
class Request:
    uid: int
    tenant: str
    kind: str                       # one of KINDS
    # np array for query kinds; a DeviceGraph for inserts/deletes
    # (device-put at admission so the tick stays transfer-free)
    payload: Optional[Any] = None
    result: Any = None
    done: bool = False
    error: Optional[str] = None
    # wall-clock admission stamp — the fleet's pipelined collect phase
    # records END-TO-END latency (collect minus submit), which needs
    # the submit time to ride on the request
    t_submit: float = 0.0


class ConnectivityService:
    """Continuous-microbatching engine over a ``GraphRegistry``.

    ``device=`` pins the shard: admission device_puts every payload to
    that device and the registry's sessions allocate their dynamic
    state there — this is what lets ``repro.fleet`` run one service
    per mesh device as a thin per-device shell (DESIGN.md §15)."""

    def __init__(self, registry: GraphRegistry | None = None, *,
                 slots: int = 32, device=None):
        if registry is None:
            registry = GraphRegistry(device=device)
        self.registry = registry
        self.device = device
        self.slots = slots
        self.queue: list[Request] = []
        self._uid = 0
        # per-(tenant, kind) latency SLO histograms — a fixed-size
        # bucket table (never grows with traffic); recorded only while
        # repro.obs tracing is enabled. Query latencies are end-to-end
        # (the query path syncs to return answers); mutation latencies
        # are dispatch-side (blocking the async tick to time it would
        # serialize the pipeline the service exists to keep full).
        self.slo = SLORecorder()
        self.stats = {
            "ticks": 0,
            "inserts_absorbed": 0,        # insert requests completed
            "insert_calls": 0,            # coalesced device-side inserts
            "deletes_absorbed": 0,        # delete requests completed
            "delete_calls": 0,            # coalesced device-side deletes
            "queries_served": 0,          # query requests completed
            "query_calls": 0,             # microbatched kernel dispatches
            "pairs_answered": 0,
            "recomputes_avoided": 0,      # vs a recompute-per-query design
            "errors": 0,
        }

    # -- submission --------------------------------------------------------

    def submit(self, tenant: str, kind: str, payload=None) -> int:
        if kind not in KINDS:
            raise ValueError(f"unknown kind {kind!r}; choose from {KINDS}")
        if kind in MUTATION_KINDS:
            payload = self._ingest_edges(tenant, kind, payload)
        elif kind in ("same_component", "component_size"):
            if payload is None:
                raise ValueError(f"kind {kind!r} requires a payload")
            # admission is the front door's per-request hot path (a
            # fleet tick admits thousands): skip the asarray copy
            # machinery when the caller already hands well-typed rows
            if not (isinstance(payload, np.ndarray)
                    and payload.dtype == np.int32):
                payload = np.asarray(payload, np.int32)
            payload = payload.reshape(-1) if kind == "component_size" \
                else payload.reshape(-1, 2)
        else:
            payload = None
        self._uid += 1
        if obs.enabled():
            with obs.span("service.admit", tenant=tenant, kind=kind):
                self.queue.append(Request(self._uid, tenant, kind,
                                          payload,
                                          t_submit=time.perf_counter()))
        else:
            self.queue.append(Request(self._uid, tenant, kind, payload,
                                      t_submit=time.perf_counter()))
        return self._uid

    def _ingest_edges(self, tenant: str, kind: str, payload
                      ) -> DeviceGraph:
        """Admission-time ingress (inserts AND deletes): validate on
        host (while the data IS host data), then explicit device_put —
        the tick itself then touches device arrays only. DeviceGraph
        payloads pass through."""
        if payload is None:
            raise ValueError(f"kind {kind!r} requires a payload")
        if isinstance(payload, DeviceGraph):
            return payload
        num_nodes = self.registry.get(tenant).num_nodes \
            if tenant in self.registry else None
        if isinstance(payload, jax.Array):
            edges = payload.astype("int32").reshape(-1, 2)
            # admission-time ingress may sync: bounds-check the host
            # view so an out-of-range endpoint errors here instead of
            # silently clamping inside the absorb (DeviceGraph payloads
            # are the no-sync fast lane — the caller owns bounds there)
            if num_nodes is not None:
                validate_edge_bounds(np.asarray(edges), num_nodes)
            if self.device is not None:
                edges = jax.device_put(edges, self.device)
        else:
            arr = np.asarray(payload, np.int32).reshape(-1, 2)
            if num_nodes is not None:
                validate_edge_bounds(arr, num_nodes)
            edges = jax.device_put(arr, self.device)
        if num_nodes is None:
            # unknown tenant: the tick's failure path will reject the
            # group; a zero-|V| DeviceGraph just carries the payload
            num_nodes = 0
        return DeviceGraph.from_edges(edges, num_nodes)

    def submit_insert(self, tenant: str, edges) -> int:
        return self.submit(tenant, "insert", edges)

    def submit_delete(self, tenant: str, edges) -> int:
        return self.submit(tenant, "delete", edges)

    def submit_query(self, tenant: str, kind: str, payload=None) -> int:
        if kind not in QUERY_KINDS:
            raise ValueError(f"unknown query kind {kind!r}; "
                             f"choose from {QUERY_KINDS}")
        return self.submit(tenant, kind, payload)

    # -- the engine tick ---------------------------------------------------

    def _fail(self, req: Request, err: Exception) -> None:
        req.error = f"{type(err).__name__}: {err}"
        req.done = True
        self.stats["errors"] += 1

    @staticmethod
    def _rebind(payload: DeviceGraph, num_nodes: int) -> DeviceGraph:
        """Bind a pre-create payload (|V|=0 marker) to the tenant's
        |V|, running the bounds validation it skipped at admission.
        This is the rare tenant-created-after-submit path, so the
        device->host sync for the check is acceptable."""
        validate_edge_bounds(np.asarray(payload.edges), num_nodes)
        return DeviceGraph.from_edges(payload.edges, num_nodes)

    def _run_mutations(self, kind: str, reqs_in: list[Request]) -> None:
        """Coalesced mutation phase for one kind ('insert'/'delete')."""
        by_tenant: dict[str, list[Request]] = {}
        for r in reqs_in:
            by_tenant.setdefault(r.tenant, []).append(r)
        registry_call = getattr(self.registry, kind)
        record = obs.enabled()
        for tenant, reqs in by_tenant.items():
            with obs.span(f"service.{kind}", tenant=tenant,
                          requests=len(reqs)) as sp:
                t0 = time.perf_counter()
                try:
                    # device-side coalescing: one concat + ONE
                    # absorb/tombstone per tenant per tick, zero host
                    # transfers. Only payloads submitted before the
                    # tenant existed (|V|=0 marker) re-bind to its |V| —
                    # with the bounds check they skipped at admission; a
                    # real |V| mismatch must fall through to the
                    # registry's error, not be papered over.
                    n = self.registry.get(tenant).num_nodes
                    batch = DeviceGraph.concat(
                        [self._rebind(r.payload, n) if
                         r.payload.num_nodes == 0 and n != 0 else r.payload
                         for r in reqs])
                    version = registry_call(tenant, batch)
                except Exception as err:  # fail the group, not the tick
                    for r in reqs:
                        self._fail(r, err)
                    sp.tag(failed=len(reqs))
                    continue
                sp.tag(route=self.registry.get(tenant).last_method)
                dt = time.perf_counter() - t0
            if record:
                # dispatch latency, shared by the coalesced group (one
                # device call served all of them)
                for _ in reqs:
                    self.slo.record(tenant, kind, dt)
            self.stats[f"{kind}_calls"] += 1
            for r in reqs:
                # the version rides as a device scalar; int(...) it to
                # observe (the tick itself must not sync)
                r.result = version
                r.done = True
                self.stats[f"{kind}s_absorbed"] += 1

    def _run_query_group(self, tenant: str, kind: str,
                         reqs: list[Request]) -> None:
        with obs.span(f"service.query.{kind}", tenant=tenant,
                      requests=len(reqs)) as sp:
            t0 = time.perf_counter()
            try:
                if kind in ("same_component", "component_size"):
                    parts = [r.payload for r in reqs]
                    flat = np.concatenate(parts, axis=0)
                    answers = getattr(self.registry, kind)(tenant, flat)
                    self.stats["query_calls"] += 1
                    self.stats["pairs_answered"] += int(flat.shape[0])
                    sp.tag(rows=int(flat.shape[0]))
                    off = 0
                    for r, part in zip(reqs, parts):
                        r.result = answers[off:off + part.shape[0]]
                        off += part.shape[0]
                else:               # scalar/histogram: one call serves all
                    answer = getattr(self.registry, kind)(tenant)
                    self.stats["query_calls"] += 1
                    for r in reqs:
                        r.result = answer
            except Exception as err:     # fail the group, not the tick
                for r in reqs:
                    self._fail(r, err)
                sp.tag(failed=len(reqs))
                return
            dt = time.perf_counter() - t0
        if obs.enabled():
            # end-to-end: the query path syncs to return host answers,
            # so the wall time IS the request latency
            for _ in reqs:
                self.slo.record(tenant, kind, dt)
        for r in reqs:
            r.done = True
            self.stats["queries_served"] += 1
            self.stats["recomputes_avoided"] += 1

    def _pop_admitted(self) -> list[Request]:
        """Atomically snapshot and remove this tick's admitted slice.

        The snapshot is taken ONCE and exactly that many entries are
        deleted from the head — a ``submit()`` landing between the read
        and the delete (a query callback enqueueing follow-up work
        mid-tick) appends past the snapshot and survives to the next
        tick. The old ``self.queue = self.queue[self.slots:]`` reslice
        re-read the list: with fewer queued requests than slots, a
        mid-tick append landed below ``slots`` and the reslice silently
        dropped it — admitted by nobody, never retired."""
        admitted = self.queue[: self.slots]
        del self.queue[: len(admitted)]
        return admitted

    def step(self) -> list[Request]:
        """One tick: admit up to ``slots`` requests, coalesce inserts
        then deletes, microbatch queries, retire. Returns the retired
        requests."""
        admitted = self._pop_admitted()
        if not admitted:
            return []
        self.stats["ticks"] += 1

        # step= maps to jax.profiler.StepTraceAnnotation under the
        # opt-in profiler bridge, so device profiles step-align
        with obs.span("service.tick", step=self.stats["ticks"],
                      admitted=len(admitted)):
            for kind in MUTATION_KINDS:   # inserts apply before deletes
                self._run_mutations(
                    kind, [r for r in admitted if r.kind == kind])
            groups: dict[tuple[str, str], list[Request]] = {}
            for r in admitted:
                if r.kind not in MUTATION_KINDS:
                    groups.setdefault((r.tenant, r.kind), []).append(r)
            for (tenant, kind), reqs in groups.items():
                self._run_query_group(tenant, kind, reqs)
        return admitted

    def run(self) -> list[Request]:
        """Drain the queue; returns every retired request in admit order."""
        finished: list[Request] = []
        while self.queue:
            finished.extend(self.step())
        return finished

    # -- telemetry ---------------------------------------------------------

    def obs_summary(self) -> dict:
        """The tick summary: per-tenant/global latency SLOs, host
        counters (autotune hit/miss, deprecation-shim traffic, and the
        device counters kept while tracing is on, synced here),
        and the fleet's on-device metrics — merged across tenants with
        ``Metrics.merge`` (associative, so fold order is irrelevant)
        and flushed ONCE through the audited ``queries.to_host`` sink.
        This is the one explicit sync point of the instrumented
        service; everything upstream of it stays on device."""
        from repro.obs import metrics as obs_metrics
        merged = None
        for name in self.registry.names():
            m = self.registry.get(name).solver.metrics
            if m is not None:
                merged = m if merged is None else merged.merge(m)
        return {
            "ticks": self.stats["ticks"],
            "latency": self.slo.summary(),
            "counters": dict(obs.tracer().counters),
            "device_metrics": (None if merged is None
                               else obs_metrics.flush(merged)),
        }
