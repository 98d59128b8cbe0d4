"""Pure-jnp oracle for the fused segment-scan kernel: the shared
per-round composition (``rounds.segment_scan`` with jnp ops), which the
fused kernel must match bit-for-bit — labels AND sweep counts."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import rounds


def ref_segment_scan(pi: jnp.ndarray, segments: jnp.ndarray,
                     true_counts: jnp.ndarray, *, lift_steps: int = 2
                     ) -> tuple[jnp.ndarray, rounds.WorkCounters]:
    ops = rounds.jnp_round_ops()._replace(
        hook=lambda p, e: rounds.hook_edges(p, e, lift_steps=lift_steps))
    return rounds.segment_scan(pi, segments, ops,
                               rounds.WorkCounters.zeros(),
                               true_counts=jnp.asarray(true_counts,
                                                       jnp.int32))
