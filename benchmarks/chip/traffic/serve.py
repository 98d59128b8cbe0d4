"""Open-loop read-only queries against one service tenant.

Requests arrive at the mix's fixed ``rate_per_s``, each carrying
``rows_per_request`` rows of one kind (``kinds`` gives the shares), over
Zipfian vertices (``zipf_constant``). Each request is timed from when it
was due until its answer is back; every request due in the window is
checked against the reference.
"""
from __future__ import annotations

import time

import jax
import numpy as np

import reference
from drivers import Driver, now

DRAIN_LIMIT_S = 60      # a request unanswered this long after the window fails


class Traffic(Driver):

    def setup(self) -> None:
        from repro.connectivity.policy import AutotuneCache
        from repro.connectivity.registry import GraphRegistry
        from repro.connectivity.service import ConnectivityService
        self.load_graph()
        g, m = self.graph, self.mix
        self.tenant = g.name
        self.svc = ConnectivityService(
            GraphRegistry(policy_cache=AutotuneCache(None)))
        self.svc.registry.create(self.tenant, g.num_nodes)
        self.svc.submit_insert(self.tenant, g.edges)
        self.svc.run()
        jax.block_until_ready(self.svc.registry.get(self.tenant).labels)
        self.plan()
        # warm every padded batch shape a tick can form: k requests of
        # one kind make k * rows rows, padded to a power of two
        rng = np.random.default_rng([self.seed % 2**63, 4])
        rows, k = int(m["rows_per_request"]), 1
        while k <= self.svc.slots:
            for kind in self.kinds:
                for _ in range(k):
                    self.svc.submit_query(self.tenant, kind,
                                          self._payload(kind, rng, rows))
            self.svc.run()
            k *= 2

    @property
    def kinds(self) -> list[str]:
        return sorted(self.mix["kinds"])

    def _payload(self, kind, rng, rows, vertices=None):
        n = self.graph.num_nodes
        if vertices is None:
            vertices = rng.integers(0, n, 2 * rows, dtype=np.int32)
        if kind == "same_component":
            return vertices[:2 * rows].reshape(rows, 2)
        return vertices[:rows]

    def plan(self) -> None:
        """The window's requests, from the seed: a fixed count at the
        mix's rate, arrival times uniform over the window (a Poisson
        process given its count), kinds in the mix's shares, vertices
        Zipfian over a seeded permutation of the vertex ids."""
        m, n, seconds = self.mix, self.graph.num_nodes, self.seconds
        rng = np.random.default_rng([self.seed % 2**63, 3])
        count = int(round(float(m["rate_per_s"]) * seconds))
        self.due = np.sort(rng.uniform(0.0, seconds, count))
        shares = np.array([float(m["kinds"][k]) for k in self.kinds])
        per_kind = np.floor(shares / shares.sum() * count).astype(int)
        per_kind[0] += count - per_kind.sum()
        kinds = np.repeat(np.arange(len(self.kinds)), per_kind)
        rng.shuffle(kinds)
        rows = int(m["rows_per_request"])
        theta = float(m["zipf_constant"])
        cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -theta)
        cdf /= cdf[-1]
        perm = rng.permutation(n).astype(np.int32)
        ranks = np.searchsorted(cdf, rng.random(count * 2 * rows))
        verts = perm[np.minimum(ranks, n - 1)].reshape(count, 2 * rows)
        self.reqs = [(self.kinds[k], self._payload(self.kinds[k], None,
                                                   rows, verts[i]))
                     for i, k in enumerate(kinds)]

    def window(self) -> dict:
        svc, due, count = self.svc, self.due, len(self.reqs)
        limit = self.seconds + DRAIN_LIMIT_S
        uid_of, done_at, late = {}, {}, np.zeros(count)
        start, i = now(), 0
        backlog = []
        while True:
            t = now() - start
            while i < count and due[i] <= t:
                kind, payload = self.reqs[i]
                uid_of[svc.submit_query(self.tenant, kind, payload)] = i
                late[i] = now() - start - due[i]
                i += 1
            if svc.queue:
                backlog.append((t, len(svc.queue)))
                t0 = now()
                with self.span("bench.tick"):
                    retired = svc.step()
                self.record("tick", now() - t0)
                t_done = now() - start
                for r in retired:
                    done_at[uid_of[r.uid]] = (t_done, r)
            elif i < count:
                wait = due[i] - (now() - start)
                if wait > 0:
                    with self.span("bench.wait"):
                        time.sleep(wait)
            else:
                break
            if now() - start > limit:
                break
        elapsed = now() - start
        self.done_at = done_at
        lat = np.full(count, np.inf)
        for j, (t_done, r) in done_at.items():
            if r.error is None:
                lat[j] = t_done - due[j]
        self.latency_s = lat
        self.notes["requests"] = count
        self.notes["generator_late_ms"] = {
            "p50": float(np.median(late) * 1e3),
            "p99": float(np.percentile(late, 99) * 1e3),
            "max": float(late.max() * 1e3)} if count else {}
        b = np.array([q for _, q in backlog]) if backlog else np.zeros(1)
        third = max(1, len(b) // 3)
        self.notes["backlog"] = {"first_third_mean": float(b[:third].mean()),
                                 "last_third_mean": float(b[-third:].mean()),
                                 "max": int(b.max())}
        return {"query_p95_ms": float(np.percentile(
                    lat, 95, method="higher") * 1e3) if count else 0.0,
                "query_p50_ms": float(np.percentile(
                    lat, 50, method="higher") * 1e3) if count else 0.0,
                "window_s": elapsed}

    def release(self) -> None:
        self.svc = None

    def check(self):
        want = reference.Answers(reference.cc_labels(self.graph.edges,
                                                     self.graph.num_nodes))
        failed = wrong = 0
        for j, (kind, payload) in enumerate(self.reqs):
            got = self.done_at.get(j)
            if got is None or got[1].error is not None:
                failed += 1
                continue
            if not np.array_equal(np.asarray(got[1].result),
                                  want(kind, payload)):
                wrong += 1
        return len(self.reqs), failed, {"wrong_answers": (wrong, 0),
                                        "failed_requests": (failed, 0)}

    def control(self):
        """The window's requests answered from early-stopped labels."""
        self.load_graph()
        self.plan()
        approx = self.early_stopped_labels()
        got = reference.Answers(approx)
        want = reference.Answers(reference.cc_labels(self.graph.edges,
                                                     self.graph.num_nodes))
        wrong = sum(not np.array_equal(got(k, p), want(k, p))
                    for k, p in self.reqs)
        return {"wrong_answers": (wrong, 0)}
