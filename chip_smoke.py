#!/usr/bin/env python3
"""Chip smoke: the connected-components engine end to end on a TPU.

Drives the main path through the public entry points (``repro.Solver``,
``ConnectivityService``, ``FleetService``) at the full size of two
Table I stand-ins, and checks every answer against scipy's connected
components on the same edges:

  static  ``Solver.open(graph).solve()`` with ``method="auto"``, then
          ``method="adaptive"``, on soc-live-journal (R-MAT scale 22,
          edge factor 7: 4,194,304 vertices, 29,360,128 edges) and
          usa-osm (a 4898^2 grid: 23,990,404 vertices, ~31.7M edges);
  stream  4 insert batches of 65,536 random edges, then 4 delete
          batches of 16,384 live edges, through one soc session;
  serve   soc as a ``ConnectivityService`` tenant: one insert batch and
          1,024 ``same_component`` pairs plus 1,024 ``component_size``
          rows over 4 ticks, with no request on the fail path.

``--chips 4`` runs only the four-chip path: a ``distributed`` solve of
soc over a 4-device mesh against a one-device ``adaptive`` solve, and a
``FleetService`` over 4 devices with packed tenants and one sharded
whale. One process drives all four chips.

Each phase prints one line (sizes, routes, whether labels matched,
first-call time with compilation, steady time, peak device bytes).
These are bring-up observations, not benchmark numbers. The last line
is ``{"ok": true, "device": {...}}``. Without a TPU, or on any wrong
answer, the script exits nonzero and prints no result.

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.api import Solver  # noqa: E402
from repro.connectivity.policy import AutotuneCache  # noqa: E402
from repro.connectivity.registry import GraphRegistry  # noqa: E402
from repro.connectivity.service import ConnectivityService  # noqa: E402
from repro.core.unionfind import connected_components_scipy  # noqa: E402
from repro.fleet import FleetService  # noqa: E402
from repro.fleet.placement import DEFAULT_SHARD_THRESHOLD  # noqa: E402
from repro.graphs import generators  # noqa: E402
from repro.graphs.format import Graph  # noqa: E402

STREAM_BATCHES = 4
INSERT_ROWS = 65_536
DELETE_ROWS = 16_384
SERVE_TICKS = 4
QUERY_ROWS = 256                 # per kind per tick: 1,024 over 4 ticks


class SmokeFailure(RuntimeError):
    """A phase produced an answer that differs from the reference."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _timed(fn):
    """(seconds, result) for one call, ending in block_until_ready."""
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return time.perf_counter() - t0, out


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _undirected_keys(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    e = np.asarray(edges, np.int64)
    return np.minimum(e[:, 0], e[:, 1]) * num_nodes \
        + np.maximum(e[:, 0], e[:, 1])


# ---------------------------------------------------------------------------
# Phases (each prints its record, then checks it)
# ---------------------------------------------------------------------------

def phase_static(graph: Graph, device) -> dict:
    """``method="auto"`` then ``method="adaptive"`` on one session."""
    ref = connected_components_scipy(graph.edges, graph.num_nodes)
    session = Solver.open(graph, policy_cache=AutotuneCache(None))
    record = {"phase": "static", "graph": graph.name,
              "num_nodes": graph.num_nodes, "num_edges": graph.num_edges}
    for method in ("auto", "adaptive"):
        first, res = _timed(lambda: session.solve(method).labels)
        steady, res = _timed(lambda: session.solve(method).labels)
        record[method] = {"route": session.last_method,
                          "labels_match": bool(np.array_equal(
                              np.asarray(res), ref)),
                          "first_s": first, "steady_s": steady}
    record["peak_bytes_in_use"] = peak_bytes(device)
    _emit(record)
    for method in ("auto", "adaptive"):
        _check(record[method]["labels_match"], f"static {graph.name} "
               f"method={method}: labels differ from the reference")
    return record


def phase_stream(graph: Graph, rng: np.random.Generator, device, *,
                 batches: int = STREAM_BATCHES,
                 insert_rows: int = INSERT_ROWS,
                 delete_rows: int = DELETE_ROWS) -> dict:
    """Insert then delete batches through one session; the final labels
    must equal the reference on the surviving edge set."""
    n = graph.num_nodes
    session = Solver.open(graph, policy_cache=AutotuneCache(None))
    # the opened snapshot becomes the dynamic state's first (bulk) load
    setup_s, _ = _timed(lambda: session.state.labels)
    live = np.asarray(graph.edges, np.int64).reshape(-1, 2)
    inserts, deletes = [], []
    for _ in range(batches):
        batch = rng.integers(0, n, (insert_rows, 2)).astype(np.int32)
        dt, _ = _timed(lambda: (session.insert(batch), session.labels))
        inserts.append({"route": session.last_method, "s": dt})
        live = np.concatenate([live, batch])
    for _ in range(batches):
        dels = live[rng.choice(live.shape[0], delete_rows, replace=False)]
        dt, _ = _timed(lambda: (session.delete(dels), session.labels))
        deletes.append({"route": session.last_method, "s": dt})
        live = live[~np.isin(_undirected_keys(live, n),
                             _undirected_keys(dels, n))]
    match = bool(np.array_equal(np.asarray(session.labels),
                                connected_components_scipy(live, n)))
    record = {"phase": "stream", "graph": graph.name, "num_nodes": n,
              "surviving_edges": int(live.shape[0]), "setup_s": setup_s,
              "inserts": inserts, "deletes": deletes,
              "labels_match": match,
              "peak_bytes_in_use": peak_bytes(device)}
    _emit(record)
    _check(match, f"stream {graph.name}: final labels differ from the "
                  "reference on the surviving edges")
    return record


def phase_serve(graph: Graph, rng: np.random.Generator, device, *,
                insert_rows: int = INSERT_ROWS, ticks: int = SERVE_TICKS,
                query_rows: int = QUERY_ROWS) -> dict:
    """One tenant behind a ConnectivityService: admit, insert, query."""
    n, name = graph.num_nodes, graph.name
    svc = ConnectivityService(GraphRegistry(policy_cache=AutotuneCache(None)))
    svc.registry.create(name, n)
    svc.submit_insert(name, graph.edges)
    admit_s, (admitted, _) = _timed(
        lambda: (svc.run(), svc.registry.get(name).labels))
    batch = rng.integers(0, n, (insert_rows, 2)).astype(np.int32)
    ref = connected_components_scipy(np.concatenate([graph.edges, batch]),
                                     n)
    ref_sizes = np.bincount(ref, minlength=n)[ref]
    svc.submit_insert(name, batch)
    retired, expected, tick_s = list(admitted), {}, []
    for _ in range(ticks):
        pairs = rng.integers(0, n, (query_rows, 2)).astype(np.int32)
        verts = rng.integers(0, n, query_rows).astype(np.int32)
        expected[svc.submit_query(name, "same_component", pairs)] = \
            ref[pairs[:, 0]] == ref[pairs[:, 1]]
        expected[svc.submit_query(name, "component_size", verts)] = \
            ref_sizes[verts]
        t0 = time.perf_counter()
        retired.extend(svc.step())
        tick_s.append(time.perf_counter() - t0)
    failed = sum(r.error is not None for r in retired)
    wrong = sum(not np.array_equal(np.asarray(r.result), expected[r.uid])
                for r in retired if r.uid in expected)
    answered = sum(r.uid in expected for r in retired)
    record = {"phase": "serve", "graph": name, "num_nodes": n,
              "admit_s": admit_s, "first_tick_s": tick_s[0],
              "steady_tick_s": tick_s[1:],
              "query_rows": 2 * ticks * query_rows,
              "requests": len(retired), "answered": answered,
              "failed": failed, "service_errors": svc.stats["errors"],
              "wrong": wrong, "route": svc.registry.get(name).last_method,
              "peak_bytes_in_use": peak_bytes(device)}
    _emit(record)
    _check(failed == 0 and svc.stats["errors"] == 0,
           f"serve {name}: {failed} request(s) ended on the fail path")
    _check(answered == len(expected), f"serve {name}: {answered} of "
                                      f"{len(expected)} queries answered")
    _check(wrong == 0, f"serve {name}: {wrong} answer(s) differ from "
                       "the reference")
    return record


def phase_mesh(graph: Graph, devices) -> dict:
    """``distributed`` over a mesh of ``devices`` vs one-device adaptive."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(devices), ("data",))
    session = Solver.open(graph, mesh=mesh)
    first, _ = _timed(lambda: session.solve().labels)
    steady, dist = _timed(lambda: session.solve().labels)
    route = session.last_method
    one = Solver.open(graph, policy_cache=AutotuneCache(None)) \
        .solve("adaptive").labels
    dist, one = np.asarray(dist), np.asarray(one)
    same = bool(np.array_equal(dist, one))
    match = bool(np.array_equal(
        dist, connected_components_scipy(graph.edges, graph.num_nodes)))
    record = {"phase": "mesh", "graph": graph.name,
              "num_nodes": graph.num_nodes, "num_edges": graph.num_edges,
              "devices": len(devices), "route": route, "first_s": first,
              "steady_s": steady, "equals_single_device": same,
              "labels_match": match,
              "peak_bytes_in_use": [peak_bytes(d) for d in devices]}
    _emit(record)
    _check(same, "mesh: distributed labels differ from the one-device "
                 "adaptive labels")
    _check(match, "mesh: distributed labels differ from the reference")
    return record


def phase_fleet(whale: Graph, tenants: list[Graph], devices,
                rng: np.random.Generator, *,
                shard_threshold: int = DEFAULT_SHARD_THRESHOLD,
                query_rows: int = QUERY_ROWS) -> dict:
    """Packed tenants plus one sharded whale over ``devices``; every
    query answer is checked against the reference."""
    fleet = FleetService(devices=devices, rebalance_every=0,
                         shard_threshold=shard_threshold,
                         policy_cache=AutotuneCache(None))
    for g in [whale] + tenants:
        fleet.admit(g.name, g.num_nodes, expected_edges=g.num_edges)
        fleet.submit_insert(g.name, g.edges)
    _check(fleet.placement_of(whale.name) == "mesh",
           f"fleet: whale {whale.name} was not sharded")
    load_s, loaded = _timed(fleet.run)
    expected = {}
    for g in [whale] + tenants:
        ref = connected_components_scipy(g.edges, g.num_nodes)
        pairs = rng.integers(0, g.num_nodes, (query_rows, 2)) \
            .astype(np.int32)
        verts = rng.integers(0, g.num_nodes, query_rows).astype(np.int32)
        # request ids are per shard, so answers are keyed by tenant
        fleet.submit_query(g.name, "same_component", pairs)
        expected[g.name, "same_component"] = \
            ref[pairs[:, 0]] == ref[pairs[:, 1]]
        fleet.submit_query(g.name, "component_size", verts)
        expected[g.name, "component_size"] = \
            np.bincount(ref, minlength=g.num_nodes)[ref][verts]
    query_s, retired = _timed(fleet.run)
    retired = loaded + retired
    failed = sum(r.error is not None for r in retired)
    answered = [r for r in retired if (r.tenant, r.kind) in expected]
    wrong = sum(not np.array_equal(np.asarray(r.result),
                                   expected[r.tenant, r.kind])
                for r in answered)
    record = {"phase": "fleet", "devices": len(devices),
              "whale": {"graph": whale.name, "num_nodes": whale.num_nodes,
                        "num_edges": whale.num_edges},
              "placement": {g.name: fleet.placement_of(g.name)
                            for g in tenants},
              "load_s": load_s, "query_s": query_s,
              "requests": len(retired), "answered": len(answered),
              "failed": failed, "wrong": wrong,
              "peak_bytes_in_use": [peak_bytes(d) for d in devices]}
    _emit(record)
    _check(failed == 0, f"fleet: {failed} request(s) failed")
    _check(len(answered) == len(expected), f"fleet: {len(answered)} of "
                                           f"{len(expected)} answered")
    _check(wrong == 0, f"fleet: {wrong} answer(s) differ from the "
                       "reference")
    return record


def fleet_tenants(seed: int) -> list[Graph]:
    """Packed tenants: small social and road graphs, each well under
    the shard threshold."""
    out = []
    for i in range(3):
        out.append(generators.rmat(16, 7, a=0.45, b=0.22, c=0.22,
                                   seed=seed + i, name=f"social{i}"))
        out.append(generators.grid_road(256, extra_prob=0.02,
                                        seed=seed + i, name=f"road{i}"))
    return out


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip mesh and fleet path")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform} "
              f"({kind}); no result", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(json.dumps({"device_kind": kind, "device_count": len(devices),
                      "jax": jax.__version__, "compile_cache": cache_dir,
                      "seed": args.seed}), flush=True)

    rng = np.random.default_rng(args.seed)
    soc = generators.table1_scaled("soc-live-journal", scale=1.0,
                                   seed=args.seed)
    if args.chips == 4:
        used = devices[:4]
        phase_mesh(soc, used)
        fleet = phase_fleet(soc, fleet_tenants(args.seed), used, rng)
        _check(all(fleet["peak_bytes_in_use"]),
               "fleet: a device reports no memory in use")
    else:
        phase_static(soc, devices[0])
        road = generators.table1_scaled("usa-osm", scale=1.0,
                                        seed=args.seed)
        phase_static(road, devices[0])
        del road
        phase_stream(soc, rng, devices[0])
        phase_serve(soc, rng, devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
