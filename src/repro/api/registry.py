"""Backend protocol + the pluggable ``BACKENDS`` registry (DESIGN.md §10).

Every execution mode of the stack — the five jnp single-graph variants,
the per-round and fused Pallas kernel backends, the host-driven
baseline loop, the shape-bucketed batched engine, the incremental and
fully-dynamic streaming engines, and the sharded distributed engine —
registers here under a uniform contract:

  * a ``Capabilities`` descriptor saying what workloads the backend can
    take (static / batched / streaming / deletions / sharded) and
    whether its ``WorkCounters`` are bit-exact against the jnp adaptive
    composition (the repo's counter ground truth);
  * a ``run(plan) -> CCResult`` entry point consuming an
    ``ExecutionPlan`` (``repro.api.plan``);
  * optionally a ``make_state(num_nodes, ...)`` factory for streaming
    backends — the ``Solver`` session asks the registry for its live
    state instead of hard-coding an engine class.

Adding a backend is a one-file, one-decorator change::

    @register_backend("my-engine", Capabilities(static=True))
    def _run(plan):
        return my_engine(plan.graph, num_segments=plan.num_segments)

The ``Solver`` facade and the adaptive policy then route to it by name;
nothing else in the stack needs to know it exists.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Protocol, runtime_checkable


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend can run, as data (the capability matrix in
    DESIGN.md §10 is generated from these)."""

    static: bool = True            # one-shot solve over a fixed edge set
    batched: bool = False          # many graphs, one device program
    streaming: bool = False        # absorbs edge insertions into live state
    deletions: bool = False        # absorbs edge deletions (tombstone log)
    sharded: bool = False          # runs over a multi-device mesh
    device_loop: bool = True       # control flow on device (no host syncs)
    # exact true-work WorkCounters (padding never billed; trustworthy
    # for cross-mode comparison — pallas_fused's are additionally
    # bit-identical to the jnp adaptive composition, asserted in tests)
    bit_exact_counters: bool = False
    # records the spanning forest during hook rounds (the parent-edge
    # table behind Solver.spanning_forest(); property-tested)
    spanning_forest: bool = False
    # keeps the spanning forest as a MAINTAINED device resident across
    # mutations (extended in-jit on insert, consumed by the tree-aware
    # delete route; DESIGN.md §14) rather than recompute-on-demand
    maintained_forest: bool = False

    def describe(self) -> str:
        flag = lambda b: "y" if b else "n"          # noqa: E731
        return (f"static={flag(self.static)} batched={flag(self.batched)} "
                f"streaming={flag(self.streaming)} "
                f"deletions={flag(self.deletions)} "
                f"sharded={flag(self.sharded)} "
                f"device_loop={flag(self.device_loop)} "
                f"bit_exact_counters={flag(self.bit_exact_counters)} "
                f"spanning_forest={flag(self.spanning_forest)} "
                f"maintained_forest={flag(self.maintained_forest)}")


@runtime_checkable
class Backend(Protocol):
    """The uniform backend contract the Solver dispatches against."""

    name: str
    capabilities: Capabilities

    def run(self, plan: Any) -> Any:                 # -> CCResult (or list)
        ...


class _FunctionBackend:
    """Adapter: a plain ``run(plan)`` function as a Backend."""

    def __init__(self, name: str, capabilities: Capabilities,
                 fn: Callable[[Any], Any],
                 make_state: Optional[Callable[..., Any]] = None):
        self.name = name
        self.capabilities = capabilities
        self._fn = fn
        self._make_state = make_state

    def run(self, plan):
        return self._fn(plan)

    def make_state(self, num_nodes: int, **kw):
        if self._make_state is None:
            raise TypeError(f"backend {self.name!r} is not a streaming "
                            "backend (no make_state)")
        return self._make_state(num_nodes, **kw)

    def __repr__(self) -> str:
        return f"<Backend {self.name!r} {self.capabilities.describe()}>"


BACKENDS: Dict[str, Backend] = {}


# ---------------------------------------------------------------------------
# Traceable entry specs (consumed by repro.analysis — DESIGN.md §11)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class VarInfo:
    """Static facts the analyzer knows about ONE flat traced argument.

    * ``range``  — inclusive (lo, hi) value bounds for integer inputs
      (vertex ids lie in [0, |V|-1], true counts in [0, |E|], ...);
      None = unbounded/unknown (the int32 pass treats it as TOP and
      never reports overflow through it);
    * ``padded`` — the array carries rows past a true count (the §8
      prefix-padding / tombstone-log discipline) — the padding-mask
      pass seeds its taint here;
    * ``mask``   — the argument IS a true-count scalar or alive mask:
      a sanitizer source for the padding-mask pass.
    """

    range: Optional[tuple] = None
    padded: bool = False
    mask: bool = False


@dataclasses.dataclass(frozen=True)
class TraceEntry:
    """One traceable program of the stack, as data.

    ``build(num_nodes, num_edges)`` returns ``(fn, args, arg_info)``:
    a pure function over FLAT array arguments, example arguments
    (``jax.ShapeDtypeStruct``s — nothing is allocated), and a
    ``VarInfo`` per argument. ``repro.analysis`` closes each entry to
    a jaxpr via ``jax.make_jaxpr`` at symbolic shape buckets and runs
    its checker passes over the graph.

    ``contracts`` name the invariants the entry is held to:
      * ``"transfer_free"`` — the program must stage with zero host
        round trips (the steady-state tick contract the
        ``jax.transfer_guard`` tests pin at runtime);
      * ``"bucketed"``      — inputs must land on the pow2 shape-bucket
        rule (``repro.core.batch``), the retrace-storm guard.
    """

    name: str
    build: Callable[[int, int], tuple]
    contracts: frozenset = frozenset({"transfer_free", "bucketed"})
    backend: Optional[str] = None        # owning BACKENDS key, if any


TRACE_SPECS: Dict[str, Callable[[], list]] = {}


def register_trace_spec(name: str):
    """Decorator registering a zero-arg builder returning the
    ``TraceEntry`` list for one backend (or subsystem). The analysis
    toolkit discovers every traceable program through this registry —
    adding a backend without a trace spec is caught by its sweep test."""
    def deco(fn):
        if name in TRACE_SPECS:
            raise ValueError(f"trace spec {name!r} already registered")
        TRACE_SPECS[name] = fn
        return fn
    return deco


def trace_entries() -> list:
    """Every registered ``TraceEntry``, sorted by name. Importing
    ``repro.api.backends`` (and ``repro.analysis.entries``) populates
    the registry; this accessor only reads it."""
    out = []
    for name in sorted(TRACE_SPECS):
        out.extend(TRACE_SPECS[name]())
    return sorted(out, key=lambda e: e.name)


def register_backend(name: str, capabilities: Capabilities,
                     make_state: Optional[Callable[..., Any]] = None):
    """Class/function decorator registering an execution backend.

    Decorate either a class exposing ``run(self, plan)`` (instantiated
    once, ``name``/``capabilities`` attached) or a bare ``run(plan)``
    function (wrapped). ``make_state`` (or a ``make_state`` method on
    the class) marks a streaming backend whose live session state the
    ``Solver`` obtains through the registry.
    """
    def deco(obj):
        if name in BACKENDS:
            raise ValueError(f"backend {name!r} already registered")
        if isinstance(obj, type):
            backend = obj()
            backend.name = name
            backend.capabilities = capabilities
        else:
            backend = _FunctionBackend(name, capabilities, obj,
                                       make_state=make_state)
        BACKENDS[name] = backend
        return obj
    return deco


def get_backend(name: str) -> Backend:
    if name not in BACKENDS:
        raise KeyError(f"unknown backend {name!r}; registered backends: "
                       f"{sorted(BACKENDS)}")
    return BACKENDS[name]


def available_backends() -> list[str]:
    return sorted(BACKENDS)


def capability_matrix() -> dict[str, dict]:
    """``{backend: {capability: bool}}`` — the registry's contents as
    data (snapshot-tested so the public surface cannot drift silently)."""
    return {name: dataclasses.asdict(b.capabilities)
            for name, b in sorted(BACKENDS.items())}
