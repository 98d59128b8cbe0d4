"""The interface every traffic driver keeps, and what they share.

A traffic mix is a data file ``traffic/<mix>.json`` whose ``driver``
key names a driver, ``traffic/<driver>.py``; the rest of the file is
that driver's parameters. A driver module defines ``Traffic``, a
subclass of ``Driver`` with:

* ``setup()``: generate the graph and the window's inputs from the
  seed, hand the graph to the program, and warm every shape the window
  will use (all counted as set-up);
* ``window()``: run whole units until ``seconds`` have passed and
  return the end-to-end readings of the window;
* ``check()``: after the window, compare what the timed path produced
  with the reference; returns ``(attempted, failed, checks)`` where
  ``checks`` maps a short name to ``(value, limit)``;
* ``release()``: drop the program's state before the reference runs;
* ``control()``: the same checks with the configuration's guarantee
  broken in the program's place (``control.py``), at the cell's size.

``timings`` holds the per-unit host-clock readings (milliseconds) that
per-layer metrics read; ``notes`` holds what the run prints on an
earlier line.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

import graphs
import plugins

now = time.perf_counter
CONTROL_ROUNDS = 4      # min-label sweeps of the controls' early stop


@functools.partial(jax.jit, static_argnames=("rounds",))
def propagate(edges, labels, *, rounds: int):
    """``rounds`` sweeps of min-label propagation over both directions
    of every edge."""
    u, v = edges[:, 0], edges[:, 1]

    def sweep(_, lab):
        lab = lab.at[u].min(lab[v])
        return lab.at[v].min(lab[u])

    return jax.lax.fori_loop(0, rounds, sweep, labels)


def load(name: str, home=plugins.HOME) -> type:
    """The ``Traffic`` class of ``traffic/<name>.py``."""
    return plugins.load("traffic", name, home).Traffic


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 *, annotate: bool = False, home=plugins.HOME):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.seconds = float(seconds)
        self.annotate, self.home = annotate, home
        self.timings: dict[str, list[float]] = {}
        self.notes: dict = {}
        self.graph = None

    def span(self, name: str):
        """A profiler annotation in traced runs, nothing otherwise."""
        if not self.annotate:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def record(self, name: str, seconds: float) -> None:
        self.timings.setdefault(name, []).append(seconds * 1e3)

    def load_graph(self) -> None:
        self.graph = graphs.host_graph(self.cfg, self.seed, self.home)
        want = (self.cfg.get("num_vertices"), self.cfg.get("num_edges"))
        got = (self.graph.num_nodes, self.graph.num_edges)
        if None not in want and tuple(want) != got:
            raise ValueError(f"generated |V|, |E| = {got}, the "
                             f"configuration states {tuple(want)}")

    def early_stopped_labels(self) -> np.ndarray:
        """Labels of min-label propagation stopped after
        ``CONTROL_ROUNDS`` sweeps instead of at convergence: the early
        stop that would tempt a faster solve."""
        g = self.graph
        return np.asarray(propagate(
            jnp.asarray(g.edges), jnp.arange(g.num_nodes, dtype=jnp.int32),
            rounds=CONTROL_ROUNDS))

    def release(self) -> None:
        pass
