"""Zero-dependency host-side span tracer (DESIGN.md §12).

The runtime twin of ``ExecutionPlan.explain()``: *what actually ran*,
span by span, with the plan provenance (backend, shape bucket,
forced/autotune/heuristic) and tenant id attached to every event —
so "what did tick 4 of tenant B do, under which plan, and at what
latency?" is answerable from a trace file instead of a debugger.

Design constraints, in priority order:

1. **Disabled mode is (nearly) free.** ``span(...)`` checks ONE
   module-level flag and returns a shared stateless null context
   manager — no allocation, no clock read, no try/except. The ``api``
   benchmark gates this: disabled-mode tracing must cost <= 5% of a
   facade dispatch.
2. **Bounded memory.** Finished spans land in a fixed-capacity ring
   buffer (``EventLog``); a long-lived service overwrites its oldest
   events instead of growing without bound. ``dropped`` says how many
   fell off.
3. **Zero dependencies.** Pure stdlib. The optional
   ``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation`` bridge
   (``enable(jax_annotations=True)``) is imported lazily so device
   profiles line up with host spans when a profiler session is active,
   and costs nothing otherwise.

Every record carries ``ts_ns``, its start on the clock the profiler
stamps a ``TraceAnnotation`` with (``time.time_ns``, the wall clock),
so records line up with a device profile even when read from the log
or an export rather than through the annotation mirror; ``dur_us``
comes from ``perf_counter_ns``. Each record also has an ``id`` and the
``parent`` id of the span that was open around it (None at the top),
from which ``span_summary`` computes self time.

Host **counters** (``count(name)``) are always on — they are plain
dict increments used for process-wide facts that must not depend on
when ``enable()`` was called: autotune cache hits/misses
(``connectivity.policy``) and legacy deprecation-shim traffic
(``repro._deprecation``). They surface in
``ConnectivityService.obs_summary()`` and the JSONL export.

**Device counters** (``count_device(name, scalar)``) count what only a
device value knows (sweeps a solve ran, hook work billed) without a
sync on the hot path: off, they do nothing; on, the tracer keeps the
device scalar and adds it to ``counters`` as a Python int only when
``counters`` or the JSONL export is read.

Exports: ``export_jsonl`` writes one JSON object per span (plus a
trailing ``counters`` record); ``export_chrome_trace`` writes the
Chrome ``trace_event`` format (complete "X" events, µs on the
profiler's clock) — loadable directly in Perfetto / chrome://tracing,
beside a device profile of the same run. ``python -m repro.obs``
renders either.
"""
from __future__ import annotations

import json
import time
from typing import Optional

_ENABLED = False        # THE module-level fast-path flag (see enable())
PENDING_CAP = 4096      # device scalars count_device keeps before folding


class _NullSpan:
    """Shared stateless no-op span — what ``span()`` returns while
    tracing is disabled. One instance serves every call site."""

    __slots__ = ()
    enabled = False

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def tag(self, **tags) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


class EventLog:
    """Fixed-capacity ring buffer of finished-span records.

    ``append`` is O(1) and never allocates past ``capacity``; once
    full, the oldest event is overwritten (``dropped`` counts how many
    fell off). ``events()`` returns the retained records oldest-first.
    """

    __slots__ = ("capacity", "_buf", "_n")

    def __init__(self, capacity: int = 4096):
        if capacity <= 0:
            raise ValueError(f"capacity must be > 0, got {capacity}")
        self.capacity = capacity
        self._buf: list = [None] * capacity
        self._n = 0

    def append(self, event: dict) -> None:
        self._buf[self._n % self.capacity] = event
        self._n += 1

    def __len__(self) -> int:
        return min(self._n, self.capacity)

    @property
    def total(self) -> int:
        """Events ever appended (retained + dropped)."""
        return self._n

    @property
    def dropped(self) -> int:
        """Events overwritten by wraparound."""
        return max(0, self._n - self.capacity)

    def events(self) -> list:
        """Retained events, oldest first (wraparound-corrected)."""
        if self._n <= self.capacity:
            return list(self._buf[: self._n])
        i = self._n % self.capacity
        return self._buf[i:] + self._buf[:i]

    def clear(self) -> None:
        self._buf = [None] * self.capacity
        self._n = 0


class Span:
    """One live span. Use via ``with span("name", tenant=..., **tags)``;
    ``tag(...)`` attaches facts learned mid-span (the policy route, the
    retired-request count) before it closes."""

    __slots__ = ("name", "tenant", "step", "tags", "depth", "id",
                 "parent", "_tracer", "_t0_ns", "_ts_ns", "_annotation")
    enabled = True

    def __init__(self, tracer: "Tracer", name: str,
                 tenant: Optional[str], step: Optional[int],
                 tags: dict):
        self.name = name
        self.tenant = tenant
        self.step = step
        self.tags = tags
        self.depth = 0
        self.id = 0
        self.parent = None
        self._tracer = tracer
        self._t0_ns = 0
        self._ts_ns = 0
        self._annotation = None

    def tag(self, **tags) -> "Span":
        self.tags.update(tags)
        return self

    def __enter__(self) -> "Span":
        t = self._tracer
        self.depth = len(t._stack)
        self.parent = t._stack[-1].id if t._stack else None
        t._next_id += 1
        self.id = t._next_id
        t._stack.append(self)
        ann = t._annotation_for(self.name, self.step)
        if ann is not None:
            ann.__enter__()
            self._annotation = ann
        self._ts_ns = time.time_ns()
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur_ns = time.perf_counter_ns() - self._t0_ns
        t = self._tracer
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if t._stack and t._stack[-1] is self:
            t._stack.pop()
        rec = {"name": self.name, "id": self.id, "parent": self.parent,
               "ts_ns": self._ts_ns,
               "ts_us": round(self._ts_ns / 1e3, 3),
               "dur_us": round(dur_ns / 1e3, 3),
               "depth": self.depth}
        if self.tenant is not None:
            rec["tenant"] = self.tenant
        if self.step is not None:
            rec["step"] = self.step
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        if self.tags:
            rec["tags"] = self.tags
        t.log.append(rec)
        return False


class Tracer:
    """Span factory + event log + host counters for one process."""

    def __init__(self, capacity: int = 4096):
        self.log = EventLog(capacity)
        self._counters: dict[str, int] = {}
        self._pending: list = []           # (name, device scalar)
        self._stack: list = []
        self._next_id = 0
        self._annotate = False
        self._trace_annotation = None      # jax.profiler classes, lazy
        self._step_annotation = None
        self._metadata_key_was = None      # jax config before annotations

    # -- span / counter entry points ----------------------------------------

    def span(self, name: str, tenant: Optional[str] = None,
             step: Optional[int] = None, **tags) -> Span:
        return Span(self, name, tenant, step, tags)

    def count(self, name: str, n: int = 1) -> None:
        self._counters[name] = self._counters.get(name, 0) + n

    def count_device(self, name: str, value) -> None:
        """Keep ``value`` (a device scalar, or an int) for ``name``;
        it is added to ``counters`` when they are read. Past
        ``PENDING_CAP`` kept values the oldest are folded in then, so
        a long traced run holds no more than that many."""
        self._pending.append((name, value))
        if len(self._pending) > PENDING_CAP:
            self._fold(self._pending[:PENDING_CAP // 2])
            del self._pending[:PENDING_CAP // 2]

    def _fold(self, pending: list) -> None:
        for name, value in pending:
            self.count(name, int(value))

    @property
    def counters(self) -> dict:
        """Host and device counters by name, as Python ints. Reading
        them syncs any device scalar still kept."""
        if self._pending:
            self._fold(self._pending)
            self._pending.clear()
        return self._counters

    def _annotation_for(self, name: str, step: Optional[int]):
        """The jax.profiler bridge: host spans double as device-profile
        annotations when opted in, so Perfetto device tracks line up
        with the host span tree. ``step`` spans map to
        ``StepTraceAnnotation`` (the profiler's step marker)."""
        if not self._annotate:
            return None
        if step is not None and self._step_annotation is not None:
            return self._step_annotation(name, step_num=step)
        if self._trace_annotation is not None:
            return self._trace_annotation(name)
        return None

    def enable_jax_annotations(self) -> None:
        import jax
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        self._trace_annotation = TraceAnnotation
        self._step_annotation = StepTraceAnnotation
        self._annotate = True
        # the persistent compile cache keys programs without their op
        # metadata by default, so an executable it loads keeps the op
        # names (named scopes) of whichever build compiled it first and
        # a profile would show those; key on metadata while profiles
        # are wanted (restored by disable())
        if self._metadata_key_was is None:
            self._metadata_key_was = \
                jax.config.jax_compilation_cache_include_metadata_in_key
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)

    def disable_jax_annotations(self) -> None:
        self._annotate = False
        if self._metadata_key_was is not None:
            import jax
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key",
                self._metadata_key_was)
            self._metadata_key_was = None

    def reset(self) -> None:
        """Forget events, counters (kept device scalars too), and the
        open-span stack (test/benchmark hook)."""
        self.log.clear()
        self._counters.clear()
        self._pending.clear()
        self._stack.clear()

    # -- exporters ----------------------------------------------------------

    def export_jsonl(self, path: str) -> None:
        """JSON-lines: one ``{"type": "span", ...}`` object per event,
        plus one trailing ``{"type": "counters", ...}`` record carrying
        the host and device counters and the ring-buffer drop count."""
        with open(path, "w") as fh:
            for ev in self.log.events():
                fh.write(json.dumps({"type": "span", **ev}) + "\n")
            fh.write(json.dumps({"type": "counters",
                                 "counters": dict(self.counters),
                                 "dropped": self.log.dropped,
                                 "total_spans": self.log.total}) + "\n")

    def export_chrome_trace(self, path: str) -> None:
        """Chrome ``trace_event`` JSON (Perfetto-loadable): complete
        "X" events on one thread track — nesting comes from ts/dur
        containment, tags ride in ``args``; ``ts`` is on the profiler's
        clock, so the file lines up with a device profile."""
        with open(path, "w") as fh:
            json.dump(chrome_trace_events(self.log.events()), fh)

    def summary(self) -> dict:
        """Per-span-name aggregates over the retained events:
        ``{name: {count, total_ms, self_ms, p50_us, p99_us}}``
        (percentiles are exact over the retained window — the ring
        buffer bounds it)."""
        return span_summary(self.log.events())


# ---------------------------------------------------------------------------
# Pure helpers shared with the CLI (which reads exported JSONL files)
# ---------------------------------------------------------------------------

def chrome_trace_events(events: list) -> dict:
    out = []
    for ev in events:
        args = dict(ev.get("tags", {}))
        if ev.get("tenant") is not None:
            args["tenant"] = ev["tenant"]
        if ev.get("step") is not None:
            args["step"] = ev["step"]
        out.append({"ph": "X", "name": ev["name"],
                    "cat": ev.get("tenant") or "repro",
                    "ts": ev["ts_us"], "dur": ev["dur_us"],
                    "pid": 0, "tid": 0, "args": args})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def self_times_us(events: list) -> list:
    """Each event's duration less the time its children cover (the
    events whose ``parent`` is its ``id``), in µs, in event order.
    Records without ids (older exports) count their whole duration."""
    children: dict = {}
    for ev in events:
        if ev.get("parent") is not None:
            children[ev["parent"]] = children.get(ev["parent"], 0.0) \
                + ev["dur_us"]
    return [ev["dur_us"] - children.get(ev.get("id"), 0.0)
            if ev.get("id") is not None else ev["dur_us"]
            for ev in events]


def span_summary(events: list) -> dict:
    by_name: dict[str, list] = {}
    own: dict[str, float] = {}
    for ev, self_us in zip(events, self_times_us(events)):
        by_name.setdefault(ev["name"], []).append(ev["dur_us"])
        own[ev["name"]] = own.get(ev["name"], 0.0) + self_us
    out = {}
    for name in sorted(by_name):
        durs = sorted(by_name[name])
        n = len(durs)
        pct = lambda q: durs[min(n - 1, int(q * (n - 1) + 0.5))]  # noqa: E731
        out[name] = {"count": n,
                     "total_ms": round(sum(durs) / 1e3, 3),
                     "self_ms": round(own[name] / 1e3, 3),
                     "p50_us": round(pct(0.50), 1),
                     "p99_us": round(pct(0.99), 1)}
    return out


# ---------------------------------------------------------------------------
# The module-level API (what every instrumented site calls)
# ---------------------------------------------------------------------------

_TRACER = Tracer()


def tracer() -> Tracer:
    """The process-wide tracer."""
    return _TRACER


def enabled() -> bool:
    return _ENABLED


def enable(*, capacity: int | None = None,
           jax_annotations: bool = False) -> Tracer:
    """Turn span tracing on. ``capacity`` resizes the ring buffer
    (clearing it); ``jax_annotations=True`` additionally mirrors every
    span into ``jax.profiler`` annotations so device profiles line up
    with host spans, and keys the persistent compile cache on op
    metadata so profiled programs carry this build's named scopes.
    Host counters are unaffected (always on)."""
    global _ENABLED
    if capacity is not None and capacity != _TRACER.log.capacity:
        _TRACER.log = EventLog(capacity)
    if jax_annotations:
        _TRACER.enable_jax_annotations()
    _ENABLED = True
    return _TRACER


def disable() -> None:
    """Turn span tracing off (the default). Already-recorded events and
    counters are kept — export or ``tracer().reset()`` as needed."""
    global _ENABLED
    _ENABLED = False
    _TRACER.disable_jax_annotations()


def span(name: str, tenant: Optional[str] = None,
         step: Optional[int] = None, **tags):
    """A span context manager — or the shared no-op when disabled.

    The disabled path is ONE global flag check + returning a shared
    stateless object; the ``api`` benchmark holds it to <= 5% of a
    facade dispatch."""
    if not _ENABLED:
        return _NULL_SPAN
    return _TRACER.span(name, tenant, step, **tags)


def count(name: str, n: int = 1) -> None:
    """Bump a host counter (always on — independent of ``enable()``)."""
    _TRACER.count(name, n)


def count_device(name: str, value) -> None:
    """Add a device scalar to a counter without a sync: nothing while
    tracing is off; on, the scalar is kept and read (as an int) only
    when ``tracer().counters`` or the JSONL export is."""
    if _ENABLED:
        _TRACER.count_device(name, value)
