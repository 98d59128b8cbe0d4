"""Whole-graph solves: each unit opens the graph and solves it to
labels with ``solve("auto")``; every unit's labels are checked."""
from __future__ import annotations

import jax
import numpy as np

import reference
from drivers import Driver, now


class Traffic(Driver):

    def _unit(self):
        from repro.api import Solver
        from repro.connectivity.policy import AutotuneCache
        t0 = now()
        with self.span("bench.open"):
            session = Solver.open(self.graph, policy_cache=AutotuneCache(None))
            jax.block_until_ready(session.graph().edges)
        t1 = now()
        with self.span("bench.solve"):
            labels = session.solve("auto").labels
            jax.block_until_ready(labels)
        t2 = now()
        return labels, session.last_method, t1 - t0, t2 - t1

    def setup(self) -> None:
        self.load_graph()
        _, route, *_ = self._unit()
        self.notes["warm_route"] = route
        self.labels, self.routes = [], []

    def window(self) -> dict:
        start = now()
        while True:
            labels, route, t_open, t_solve = self._unit()
            self.labels.append(labels)
            self.routes.append(route)
            self.record("open", t_open)
            self.record("solve", t_solve)
            elapsed = now() - start
            if elapsed >= self.seconds:
                break
        self.units = len(self.labels)
        self.notes["routes"] = sorted(set(self.routes))
        return {"solve_edges_per_s":
                self.units * self.graph.num_edges / elapsed,
                "window_s": elapsed}

    def release(self) -> None:
        self.labels = [np.asarray(x) for x in self.labels]

    def check(self):
        ref = reference.cc_labels(self.graph.edges, self.graph.num_nodes)
        wrong = sum(int(np.count_nonzero(x != ref)) for x in self.labels)
        bad_units = sum(not np.array_equal(x, ref) for x in self.labels)
        return self.units, bad_units, {"wrong_labels": (wrong, 0)}

    def control(self):
        """Early-stopped labels in the solve's place."""
        self.load_graph()
        approx = self.early_stopped_labels()
        ref = reference.cc_labels(self.graph.edges, self.graph.num_nodes)
        return {"wrong_labels": (int(np.count_nonzero(approx != ref)), 0)}
