"""Reduce a JAX profiler trace to the benchmark's device numbers.

``reduce_trace`` reads one ``.xplane.pb`` with ``jax.profiler.ProfileData``
and returns, for the host span that marks the measured window
(``bench.window``):

* ``window_s``: the span's length;
* ``busy_s``: the union of device operation intervals inside it,
  averaged over the devices that ran anything;
* ``device_ops``: the operations that took most device time, summed by
  name, with their seconds: self time, so a ``while`` op counts the
  time its body's ops do not cover;
* ``idle_gaps``: the longest stretches with no device operation, each
  named by the innermost host span that was open at its midpoint (the
  benchmark's own ``bench.*`` annotations and the program's
  ``repro.obs`` spans, which the traced run mirrors into the profiler).

Device operations are the events on a device plane's "XLA Ops" line
(a TPU plane is named ``/device:TPU:<n>``), each named
``<program>/<op>`` after the "XLA Modules" event that encloses it. Host
spans are the events of the host thread that opened the window span.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

WINDOW_SPAN = "bench.window"
TOP = 10
MIN_GAP_NS = 1000          # shorter stretches are op boundaries, not idle


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def is_device_ops_line(plane_name: str, line_name: str) -> bool:
    return plane_name.startswith("/device:") and line_name == "XLA Ops"


def op_name(event_name: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` (a TPU op's HLO text) ->
    ``fusion.3``; ``jit_f(123)`` (a program) -> ``jit_f``; other names
    pass through."""
    return event_name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def with_programs(ops: list, modules: list) -> list:
    """Prefix each op with the name of the program whose run encloses
    its start."""
    if not modules:
        return ops
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and modules[i][1] >= s:
            name = f"{modules[i][2]}/{name}"
        out.append((s, e, name))
    return out


def load(path: str, device_line=None):
    """(device intervals by plane, host threads) of one trace.

    Device intervals are ``(start_ns, end_ns, name)``; host threads map
    a thread's name to its ``(start_ns, end_ns, name)`` events."""
    from jax.profiler import ProfileData
    device_line = device_line or is_device_ops_line
    with open(path, "rb") as fh:
        pd = ProfileData.from_serialized_xspace(fh.read())
    device: dict[str, list] = defaultdict(list)
    host: dict[str, list] = {}
    for plane in pd.planes:
        modules = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                    op_name(e.name)) for line in plane.lines
                   if line.name == "XLA Modules" for e in line.events]
        for line in plane.lines:
            is_device = device_line(plane.name, line.name)
            if not is_device and not plane.name.startswith("/host:"):
                continue
            events = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                       op_name(e.name) if is_device else e.name)
                      for e in line.events]
            if is_device:
                device[plane.name].extend(with_programs(events, modules))
            else:
                host[f"{plane.name}/{line.name}"] = events
    return dict(device), host


def union(intervals, lo: float, hi: float):
    """(busy length, gaps) of intervals clipped to [lo, hi]; gaps are
    ``(start, end)`` stretches covered by no interval."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals
                   if e > lo and s < hi)
    busy, gaps, cursor = 0.0, [], lo
    for s, e in spans:
        if s > cursor:
            gaps.append((cursor, s))
        if e > cursor:
            busy += e - max(s, cursor)
            cursor = e
    if hi > cursor:
        gaps.append((cursor, hi))
    return busy, gaps


def self_times(events, lo: float, hi: float) -> dict[str, float]:
    """Per-name device time inside [lo, hi], each event less the time
    of the events nested in it (a loop op encloses its body's ops)."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []             # [end, name, self time]

    def close(entry):
        out[entry[1]] += entry[2]

    for s, e, name in sorted((max(s, lo), min(e, hi), n)
                             for s, e, n in events if e > lo and s < hi):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for entry in stack:
        close(entry)
    return out


def innermost(events, t: float) -> str:
    """Name of the shortest host event that contains time ``t``."""
    best = None
    for s, e, name in events:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "(no host span)"


def reduce_events(device: dict, host: dict, window_span: str = WINDOW_SPAN,
                  top: int = TOP) -> dict:
    """The reduction itself, on loaded events (see ``load``)."""
    window, thread = None, None
    for name, events in host.items():
        for ev in events:
            if ev[2] == window_span and (window is None
                                         or ev[1] - ev[0] > window[1] -
                                         window[0]):
                window, thread = ev, name
    if window is None:
        raise ValueError(f"the trace has no {window_span!r} host span")
    lo, hi = window[0], window[1]
    used = {p: ev for p, ev in device.items()
            if any(e > lo and s < hi for s, e, _ in ev)}
    if not used:
        raise ValueError("no device operation ran inside the window")
    busy_total, all_gaps = 0.0, []
    op_time: dict[str, float] = defaultdict(float)
    for plane, events in used.items():
        busy, gaps = union(events, lo, hi)
        busy_total += busy
        all_gaps.extend(g for g in gaps if g[1] - g[0] >= MIN_GAP_NS)
        for name, t in self_times(events, lo, hi).items():
            op_time[name] += t
    spans = [ev for ev in host[thread]
             if ev[2] != window_span and not ev[2].startswith("$")]
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / len(used) / 1e9,
        "devices": len(used),
        "device_ops": [[n, t / 1e9] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[innermost(spans, (s + e) / 2), (e - s) / 1e9]
                      for s, e in all_gaps[:top]],
    }


def reduce_trace(trace_dir: str, device_line=None) -> dict:
    """Load the newest trace under ``trace_dir`` and reduce it."""
    device, host = load(find_xplane(trace_dir), device_line)
    return reduce_events(device, host)
