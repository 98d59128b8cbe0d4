"""A road-network stand-in on the device: a 2D grid that keeps an exact
share of its lattice edges, plus diagonal shortcuts.

Parameters: ``side``, ``keep_share`` (of the 2·side·(side-1) lattice
edges) and ``diag_share`` (shortcuts per vertex). The kept count is
exact, so every seed gives the same |E|. Ids are in lattice order, row
by row, as a road network's ids follow its geography.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("side", "keep", "extra"))
def grid_edges(key, *, side: int, keep: int, extra: int):
    """int32 [keep + extra, 2]: ``keep`` lattice edges of a side x side
    grid chosen uniformly (in lattice order: right edges row by row,
    then down edges), then ``extra`` distinct diagonal shortcuts in
    random order."""
    k_keep, k_diag = jax.random.split(key)
    n_right = side * (side - 1)
    lattice = 2 * n_right
    pick = jnp.sort(jnp.argsort(
        jax.random.uniform(k_keep, (lattice,)))[:keep]).astype(jnp.int32)
    is_right = pick < n_right
    j = jnp.where(is_right, pick, pick - n_right)
    u = jnp.where(is_right, (j // (side - 1)) * side + j % (side - 1), j)
    v = jnp.where(is_right, u + 1, u + side)
    diag = jnp.argsort(jax.random.uniform(
        k_diag, ((side - 1) * (side - 1),)))[:extra].astype(jnp.int32)
    du = (diag // (side - 1)) * side + diag % (side - 1)
    return jnp.concatenate([jnp.stack([u, v], 1),
                            jnp.stack([du, du + side + 1], 1)])


def sizes(params: dict) -> tuple[int, int, int]:
    """(side, kept lattice edges, diagonal shortcuts)."""
    side = int(params["side"])
    keep = round(float(params["keep_share"]) * 2 * side * (side - 1))
    extra = min(int(float(params["diag_share"]) * side * side),
                (side - 1) ** 2)
    return side, keep, extra


def graph(key, params: dict):
    """(edges on the device, |V|)."""
    side, keep, extra = sizes(params)
    return grid_edges(key, side=side, keep=keep, extra=extra), side * side
