"""Graph500 R-MAT on the device.

Parameters: ``scale`` (2**scale vertices), ``num_edges`` (edge rows)
and the quadrant probabilities ``a``, ``b``, ``c``. As Graph500 does,
the vertex ids are relabelled by a random permutation drawn from the
seed, so no id says anything about a vertex's degree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("scale", "num_edges"))
def rmat_edges(key, perm_key, *, scale: int, num_edges: int, a: float,
               b: float, c: float):
    """int32 [num_edges, 2] R-MAT edges over 2**scale vertices: one
    quadrant choice per bit, probabilities a (top-left), b (top-right),
    c (bottom-left) and 1-a-b-c (bottom-right); then every id mapped
    through the permutation that ``perm_key`` draws."""
    def bit(i, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(key, i), (num_edges,))
        right = (r >= a) & (r < a + b)
        down = (r >= a + b) & (r < a + b + c)
        diag = r >= a + b + c
        src = src | ((down | diag).astype(jnp.int32) << i)
        dst = dst | ((right | diag).astype(jnp.int32) << i)
        return src, dst

    zero = jnp.zeros((num_edges,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    perm = jax.random.permutation(perm_key, 1 << scale).astype(jnp.int32)
    return jnp.stack([perm[src], perm[dst]], axis=1)


def _draw(key, params: dict, rows: int, stream: int):
    k_edges, k_perm = jax.random.split(key)
    return rmat_edges(jax.random.fold_in(k_edges, stream), k_perm,
                      scale=int(params["scale"]), num_edges=rows,
                      a=float(params["a"]), b=float(params["b"]),
                      c=float(params["c"]))


def graph(key, params: dict):
    """(edges on the device, |V|)."""
    return _draw(key, params, int(params["num_edges"]), 0), \
        1 << int(params["scale"])


def more_edges(key, params: dict, rows: int, stream: int):
    """``rows`` further edges of the same graph's distribution and
    permutation (the same ``key``), from their own ``stream``."""
    return _draw(key, params, rows, stream)
