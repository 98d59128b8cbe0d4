"""ExecutionPlan — the adaptive decision as an inspectable object.

The paper's contribution is picking the right CC schedule per input;
before this module that decision vanished inside ``method="auto"``
string plumbing. ``Solver.plan()`` reifies it: which backend runs,
why (forced override / measured autotune winner / the paper's
heuristic), which power-of-two shape bucket the graph lands in (the
jit-cache and autotune key), the segmentation plan (s = 2|E|/|V|),
and the predicted per-round work. ``plan.explain()`` renders it for
humans; ``plan.run()`` executes it through the ``BACKENDS`` registry.

A plan is cheap host metadata and performs no host<->device transfers,
so the steady-state mutation paths can plan under
``jax.transfer_guard("disallow")``. On a static, device-resident
session planning touches the device not at all; on a live streaming
session the plan captures the log's compacted alive view, which lazily
enqueues one on-device compaction program (still transfer-free).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

from repro.core.segmentation import SegmentationPlan
from repro.obs import trace as obs


@dataclasses.dataclass
class ExecutionPlan:
    """One routed execution: backend choice + everything that drove it."""

    backend: str                   # BACKENDS key that will run
    reason: str                    # forced | autotune | heuristic | policy | sharded
    num_nodes: int
    num_edges: int                 # true edges when statically known
    bucket: tuple                  # pow2 (V_pad, E_pad) — jit/autotune key
    segmentation: Optional[SegmentationPlan]
    lift_steps: int = 2            # accepted; no effect (flat-label hooks)
    num_segments: Optional[int] = None      # caller override (None = heuristic)
    graph: Any = dataclasses.field(default=None, repr=False)
    graphs: Any = dataclasses.field(default=None, repr=False)   # batched plans
    opts: dict = dataclasses.field(default_factory=dict, repr=False)
    predicted: dict = dataclasses.field(default_factory=dict)
    artifacts: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def bucket_key(self) -> str:
        """The autotune-cache spelling of the shape bucket."""
        return f"v{self.bucket[0]}_e{self.bucket[1]}"

    def run(self):
        """Execute through the registered backend; returns its
        ``CCResult`` (a list of them for batched plans). Extra outputs
        land in ``self.artifacts``. Traced as a ``plan.run`` span
        tagged with the plan provenance when ``repro.obs`` is
        enabled."""
        from repro.api.registry import get_backend
        if not obs.enabled():
            return get_backend(self.backend).run(self)
        with obs.span("plan.run", **self.trace_tags()):
            return get_backend(self.backend).run(self)

    def as_dict(self) -> dict:
        """The decision as one plain-JSON dict — THE schema shared by
        the ``explain()`` renderer and the tracer's span tags (pinned
        by a snapshot test so traces and ``explain()`` can't drift)."""
        seg = self.segmentation
        return {
            "backend": self.backend,
            "reason": self.reason,
            "num_nodes": self.num_nodes,
            "num_edges": self.num_edges,
            "density": 2.0 * self.num_edges / max(self.num_nodes, 1),
            "bucket": list(self.bucket),
            "bucket_key": self.bucket_key,
            "lift_steps": self.lift_steps,
            "num_segments": self.num_segments,
            "batch_size": (len(self.graphs) if self.graphs is not None
                           else None),
            "segmentation": None if seg is None else {
                "num_segments": seg.num_segments,
                "segment_size": seg.segment_size,
                "padded_edges": seg.padded_edges,
                "source": ("override" if self.num_segments is not None
                           else "s=2|E|/|V| heuristic"),
            },
            "predicted": dict(self.predicted),
        }

    def trace_tags(self) -> dict:
        """The provenance subset of ``as_dict()`` that rides on every
        span touching this plan: backend, why it won, shape bucket."""
        d = self.as_dict()
        return {"backend": d["backend"], "reason": d["reason"],
                "bucket": d["bucket_key"]}

    def explain(self) -> str:
        """Human-readable account of the adaptive decision (rendered
        from ``as_dict()`` — same fields the tracer tags see)."""
        from repro.api.registry import BACKENDS
        d = self.as_dict()
        lines = [f"plan: backend={d['backend']} ({d['reason']})"]
        if d["batch_size"] is not None:
            lines.append(f"  batch: {d['batch_size']} graphs, "
                         f"total |E|={d['num_edges']}")
        lines.append(f"  graph: |V|={d['num_nodes']} |E|={d['num_edges']} "
                     f"density={d['density']:.2f} "
                     f"bucket={d['bucket_key']}")
        s = d["segmentation"]
        if s is not None:
            lines.append(f"  segmentation: {s['num_segments']} segment(s)"
                         f" x {s['segment_size']} edges "
                         f"(padded {s['padded_edges']}; {s['source']})")
        if d["predicted"]:
            lines.append("  predicted: " + " ".join(
                f"{k}={v}" for k, v in sorted(d["predicted"].items())))
        backend = BACKENDS.get(self.backend)
        if backend is not None:
            lines.append(f"  capabilities: "
                         f"{backend.capabilities.describe()}")
        return "\n".join(lines)
