"""Name device time by the program's named scopes.

``jax.profiler.ProfileData`` gives each event its own stats but not
the stats of its metadata, and a TPU keeps an op's name path there:
the ``tf_op`` stat (``jit(_cc_jit)/while/body/hook/scatter-min``),
which carries the scopes the program traced the op under. This module
decodes the protobuf wire format of the few XSpace fields that hold
it: planes, their ``event_metadata`` and ``stat_metadata``, and the
stats ``tf_op`` and ``program_id``. It skips every plane's ``lines``
without decoding them, and needs nothing beyond the standard library.

``read(path)`` returns, per plane, the map from an op's key (its
program and op name, as ``trace_reduce.load`` names device events) to
its ``tf_op`` path. ``scope_seconds`` joins that map to the trace's
device events, clips them to the ``bench.window`` span and sums self
time (``trace_reduce.self_times``) by the innermost scope of ``SCOPES``
in each op's path, so an op of a ``compress`` nested in a ``hook``
counts once. A CPU trace has no ``tf_op``: there each op's path comes
from the HLO of the executables the process holds (``live_paths``), so
the readers work in the traced run's own process. Both are computed
once per file.

    python benchmarks/chip/xspace.py [trace.xplane.pb]

prints the window's device seconds by scope for a trace (the newest
under the benchmark's trace directory by default).
"""
from __future__ import annotations

import functools
import json
import os
import re
import sys
import warnings
from collections import defaultdict

import trace_reduce

# the program's named scopes (repro.core.rounds, repro.core.sampled)
SCOPES = ("hook", "compress", "segment_scan", "cleanup", "sampled.csr",
          "sampled.sample", "sampled.census", "sampled.pack")
UNSCOPED = "(unscoped)"
_MODULE = re.compile(r"^(\S+)\((\d+)\)$")  # "jit_f(<program id>)"
# an HLO instruction and its op_name: "  %sort.6 = ... op_name=\"a/b\""
_HLO_OP = re.compile(r'^\s*(?:ROOT )?%?([\w.-]+) = [^\n]*?metadata='
                     r'\{[^}\n]*?op_name="([^"]*)"', re.M)


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """Yield ``(number, value)`` for each field of one message: an int
    for varint and fixed-width fields, a memoryview for
    length-delimited ones (strings, bytes, nested messages)."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _entry(view) -> tuple[int, memoryview]:
    """A map entry: (int64 key, message value)."""
    key, value = 0, memoryview(b"")
    for num, v in fields(view):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane(view) -> tuple[str, list, dict]:
    """(name, event metadata, stat metadata names) of an XPlane; each
    event metadata is ``(name, [(stat id, value)])``."""
    name, events, stat_names = "", [], {}
    for num, v in fields(view):
        if num == 2:
            name = _text(v)
        elif num == 4:                       # event_metadata map
            md_name, md_stats = "", []
            for n2, v2 in fields(_entry(v)[1]):
                if n2 == 2:
                    md_name = _text(v2)
                elif n2 == 5:
                    md_stats.append(_stat(v2))
            events.append((md_name, md_stats))
        elif num == 5:                       # stat_metadata map
            key, md = _entry(v)
            stat_names[key] = next(
                (_text(v2) for n2, v2 in fields(md) if n2 == 2), "")
    return name, events, stat_names


def _stat(view) -> tuple[int, object]:
    """(metadata id, value) of an XStat holding a string or an
    integer (``tf_op`` and ``program_id``); None for other values."""
    mid, value = 0, None
    for num, v in fields(view):
        if num == 1:
            mid = v
        elif num == 5:
            value = _text(v)
        elif num in (3, 4):
            value = v
    return mid, value


@functools.lru_cache(maxsize=8)
def _read(path: str, _stamp) -> dict:
    with open(path, "rb") as fh:
        data = fh.read()
    planes = {}
    for num, view in fields(data):
        if num != 1:
            continue
        name, events, stat_names = _plane(view)
        modules, ops = {}, []
        for md_name, md_stats in events:
            got = {stat_names.get(k): v for k, v in md_stats}
            m = _MODULE.match(md_name)
            if got.get("tf_op"):
                ops.append((md_name, got.get("program_id"), got["tf_op"]))
            elif m:
                modules[int(m.group(2))] = m.group(1)
        paths = {}
        for md_name, program, tf_op in ops:
            op = trace_reduce.op_name(md_name)
            key = f"{modules[program]}/{op}" if program in modules else op
            paths.setdefault(key, tf_op)
        planes[name] = paths
    return planes


def read(path: str) -> dict:
    """``{plane name: {op key: tf_op path}}`` of one ``.xplane.pb``."""
    st = os.stat(path)
    return _read(os.path.abspath(path), (st.st_mtime_ns, st.st_size))


def scope_of(tf_op: str, scopes=SCOPES) -> str:
    """The innermost of ``scopes`` in an op's name path."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def live_paths() -> dict:
    """``{program/op: op name path}`` from the HLO of every executable
    this process holds. A CPU trace carries no ``tf_op``, so there the
    paths come from here, in the process that ran the programs."""
    import jax
    out = {}
    for exe in jax.devices()[0].client.live_executables():
        for module in exe.hlo_modules():
            for op, path in _HLO_OP.findall(module.to_string()):
                out.setdefault(f"{module.name}/{op}", path)
    return out


def _device_events(profile) -> dict:
    """Device op events by plane, ``(start_ns, end_ns, program/op)``:
    the program is the event's ``hlo_module`` stat where it has one
    (the CPU's XLA thread), else the "XLA Modules" event around it, as
    ``trace_reduce.load`` names a TPU's ops."""
    out = defaultdict(list)
    for plane in profile.planes:
        modules = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                    trace_reduce.op_name(e.name)) for line in plane.lines
                   if line.name == "XLA Modules" for e in line.events]
        for line in plane.lines:
            if not trace_reduce.is_device_ops_line(plane.name, line.name):
                continue
            events = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                       trace_reduce.op_name(e.name)) for e in line.events]
            if modules:
                out[plane.name] += trace_reduce.with_programs(events,
                                                              modules)
                continue
            with warnings.catch_warnings():    # reading stats warns
                warnings.simplefilter("ignore", DeprecationWarning)
                programs = [dict(e.stats).get("hlo_module")
                            for e in line.events]
            out[plane.name] += [(s, e, f"{p}/{n}" if p else n)
                                for (s, e, n), p in zip(events, programs)]
    return out


@functools.lru_cache(maxsize=8)
def _scope_seconds(path: str, _stamp, scopes: tuple) -> dict | None:
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    windows = [(float(e.start_ns), float(e.start_ns + e.duration_ns))
               for plane in profile.planes if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events
               if e.name == trace_reduce.WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    tf_ops, live = read(path), None
    out: dict[str, float] = defaultdict(float)
    used = 0
    for plane, events in _device_events(profile).items():
        times = trace_reduce.self_times(events, lo, hi)
        if not times:
            continue
        used += 1
        names = tf_ops.get(plane)
        if not names:
            names = live = live_paths() if live is None else live
        for name, t in times.items():
            out[scope_of(names.get(name, ""), scopes)] += t / 1e9
    if not used:
        return None
    return {scope: t / used for scope, t in out.items()}


def scope_seconds(path: str | None = None,
                  scopes: tuple = SCOPES) -> dict | None:
    """Device self time (seconds, averaged over the devices that ran)
    inside the ``bench.window`` span, by innermost scope; ops under no
    scope sum under ``UNSCOPED``. ``path`` defaults to the newest trace
    under the benchmark's trace directory. None without a window."""
    if path is None:
        import bench
        path = trace_reduce.find_xplane(str(bench.TRACE_DIR))
    st = os.stat(path)
    return _scope_seconds(os.path.abspath(path),
                          (st.st_mtime_ns, st.st_size), tuple(scopes))


def per_unit_ms(ctx, scope: str) -> float | None:
    """A scope's device time per unit of the window, in milliseconds:
    None outside a traced run, without units, or where no op of the
    trace falls under the scope."""
    if ctx.trace is None or not ctx.units:
        return None
    seconds = (scope_seconds() or {}).get(scope)
    return None if not seconds else seconds * 1e3 / ctx.units


if __name__ == "__main__":
    times = scope_seconds(sys.argv[1] if len(sys.argv) > 1 else None)
    print(json.dumps(times, indent=1, sort_keys=True))
