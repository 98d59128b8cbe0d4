"""Cycles of insert batches then delete batches through one session.

The graph is bulk-loaded in set-up. A cycle is ``inserts_per_cycle``
batches of ``insert_rows`` edges drawn from the configuration's own
generator (its ``more_edges``), then ``deletes_per_cycle`` batches of
``delete_rows`` rows of the loaded graph chosen uniformly without
replacement. Labels are read to the host clock after every batch; the
window ends on a delete, and the labels then are checked against the
reference on the surviving edges.
"""
from __future__ import annotations

import jax
import numpy as np

import graphs
import reference
from drivers import Driver, now


class Traffic(Driver):

    def setup(self) -> None:
        from repro.api import Solver
        from repro.connectivity.policy import AutotuneCache
        from repro.core.batch import next_pow2
        self.load_graph()
        m, g = self.mix, self.graph
        n_ins, n_del = int(m["inserts_per_cycle"]), int(m["deletes_per_cycle"])
        r_ins, r_del = int(m["insert_rows"]), int(m["delete_rows"])
        # the edge log holds next_pow2(|E|) rows after the bulk load; a
        # window that appended past them would compile the grown shape
        spare = (next_pow2(g.num_edges) - g.num_edges) // next_pow2(r_ins)
        self.max_cycles = max(1, (spare - 2) // n_ins)
        cycles = self.max_cycles
        # inserts follow the configuration's own generator
        ins = graphs.generator(self.cfg, self.home).more_edges(
            graphs.prng_key(self.seed), self.cfg["params"],
            (2 + cycles * n_ins) * r_ins, 1)
        ins = np.asarray(ins).reshape(-1, r_ins, 2)
        rng = np.random.default_rng([self.seed % 2**63, 2])
        pick = rng.choice(g.num_edges, (1 + cycles * n_del) * r_del,
                          replace=False)
        dels = g.edges[pick].reshape(1 + cycles * n_del, r_del, 2)
        self.ins, self.dels = list(ins), list(dels)
        self.ops, self.routes = [("insert", g.edges)], []
        t0 = now()
        self.session = Solver.open(g, policy_cache=AutotuneCache(None))
        jax.block_until_ready(self.session.state.labels)
        self.notes["bulk_load_s"] = now() - t0
        # an insert after a delete retraces the absorb program, so the
        # warm-up runs the window's order: insert, delete, insert
        self._apply("insert")
        self._apply("delete")
        self._apply("insert")
        self.notes["warm_routes"] = list(self.routes)
        self.routes = []

    def _apply(self, kind: str) -> float:
        batch = (self.ins if kind == "insert" else self.dels).pop(0)
        t0 = now()
        with self.span(f"bench.{kind}"):
            getattr(self.session, kind)(batch)
            jax.block_until_ready(self.session.labels)
        dt = now() - t0
        self.ops.append((kind, batch))
        self.routes.append(self.session.last_method)
        return dt

    def window(self) -> dict:
        m = self.mix
        start, cycles, rows = now(), 0, 0
        while True:
            for _ in range(int(m["inserts_per_cycle"])):
                self.record("insert", self._apply("insert"))
                rows += int(m["insert_rows"])
            for _ in range(int(m["deletes_per_cycle"])):
                self.record("delete", self._apply("delete"))
                rows += int(m["delete_rows"])
            cycles += 1
            elapsed = now() - start
            if elapsed >= self.seconds:
                break
            if cycles == self.max_cycles:
                self.notes["cut_by_log_capacity"] = True
                break
        self.units = cycles * (int(m["inserts_per_cycle"])
                               + int(m["deletes_per_cycle"]))
        self.notes["cycles"] = cycles
        self.notes["routes"] = {r: self.routes.count(r)
                                for r in sorted(set(self.routes))}
        return {"mutations_per_s": rows / elapsed, "window_s": elapsed}

    def release(self) -> None:
        self.final = np.asarray(self.session.labels)
        self.session = None

    def check(self):
        g = self.graph
        alive = reference.surviving_edges(self.ops, g.num_nodes)
        ref = reference.cc_labels(alive, g.num_nodes)
        wrong = int(np.count_nonzero(self.final != ref))
        return self.units, 0, {"wrong_labels": (wrong, 0)}

    def control(self):
        """The program's own session, one cycle, with its last delete
        batch left unapplied: labels one batch stale."""
        self.setup()
        self.max_cycles = 1
        self.session.delete = lambda batch: None
        self.window()
        self.release()
        return self.check()[2]
