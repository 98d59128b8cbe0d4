"""The plain reference: connected components by scipy, on the host.

It imports nothing of the program under test and takes nothing it made:
it sees only the edges the benchmark generated and the mutations it
sent. Labels are canonical, each vertex labelled with the smallest
vertex id of its component, which is the program's labelling too.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components


def cc_labels(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    """int32 [V] min-id component labels of an undirected edge list."""
    e = np.asarray(edges).reshape(-1, 2)
    ones = np.ones(e.shape[0], np.int8)
    adj = sp.coo_matrix((ones, (e[:, 0], e[:, 1])),
                        shape=(num_nodes, num_nodes)).tocsr()
    _, comp = connected_components(adj, directed=True, connection="weak")
    first = np.full(comp.max(initial=0) + 1, num_nodes, np.int64)
    np.minimum.at(first, comp, np.arange(num_nodes))
    return first[comp].astype(np.int32)


def undirected_keys(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    """int64 key of each row's undirected edge (orientation-blind)."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    return np.minimum(e[:, 0], e[:, 1]) * num_nodes + \
        np.maximum(e[:, 0], e[:, 1])


def surviving_edges(ops: list[tuple[str, np.ndarray]],
                    num_nodes: int) -> np.ndarray:
    """Replay a log of ("insert" | "delete", rows) in order: a delete
    retires every copy of its undirected edges inserted before it, and
    an edge inserted after a delete of the same key lives again."""
    ins = [(t, rows) for t, (kind, rows) in enumerate(ops)
           if kind == "insert"]
    dels = [(t, rows) for t, (kind, rows) in enumerate(ops)
            if kind == "delete"]
    edges = np.concatenate([r.reshape(-1, 2) for _, r in ins])
    born = np.concatenate([np.full(r.shape[0], t, np.int64)
                           for t, r in ins])
    if not dels:
        return edges
    dkeys = np.concatenate([undirected_keys(r, num_nodes) for _, r in dels])
    dtime = np.concatenate([np.full(r.shape[0], t, np.int64)
                            for t, r in dels])
    order = np.lexsort((dtime, dkeys))
    dkeys, dtime = dkeys[order], dtime[order]
    last = np.r_[dkeys[1:] != dkeys[:-1], True]   # latest delete per key
    dkeys, dtime = dkeys[last], dtime[last]
    keys = undirected_keys(edges, num_nodes)
    at = np.clip(np.searchsorted(dkeys, keys), 0, max(dkeys.size - 1, 0))
    hit = dkeys[at] == keys
    alive = ~hit | (dtime[at] < born)
    return edges[alive]


class Answers:
    """What ``same_component`` and ``component_size`` queries must
    return on the partition ``labels``."""

    def __init__(self, labels: np.ndarray):
        self.labels = labels
        self.sizes = np.bincount(labels, minlength=labels.shape[0])

    def __call__(self, kind: str, payload: np.ndarray) -> np.ndarray:
        lab = self.labels
        if kind == "same_component":
            return lab[payload[:, 0]] == lab[payload[:, 1]]
        if kind == "component_size":
            return self.sizes[lab[payload]]
        raise KeyError(f"no reference for query kind {kind!r}")
