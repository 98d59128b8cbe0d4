"""The configurations' graphs, generated on the device from the seed.

A configuration file names a generator, ``generators/<name>.py``, and
its parameters. The generator's ``graph(key, params)`` builds the edge
list on the default device in one jitted call; ``host_graph`` copies it
to the host once, so the program is handed a host ``Graph`` as a user
loading a file would hand it over.
"""
from __future__ import annotations

import jax
import numpy as np

import plugins


def prng_key(seed: int, stream: int = 0):
    """A key from a seed of any size (more than 32 bits allowed)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def generator(cfg: dict, home=plugins.HOME):
    return plugins.load("generators", cfg["generator"], home)


def host_graph(cfg: dict, seed: int, home=plugins.HOME):
    """The configuration's graph as a host ``repro`` ``Graph``."""
    from repro.graphs.format import Graph
    edges, n = generator(cfg, home).graph(prng_key(seed), cfg["params"])
    return Graph(edges=np.asarray(edges), num_nodes=n, name=cfg["name"])
