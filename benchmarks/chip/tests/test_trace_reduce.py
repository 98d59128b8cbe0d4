"""The trace reduction: busy share, idle gaps and breakdown."""
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def cpu_ops(plane: str, line: str) -> bool:
    """On the CPU the XLA client thread stands in for a device plane."""
    return plane.startswith("/host:") and line.startswith("tf_XLAPjRt")


def test_union_merges_overlaps_and_clips_to_the_window():
    busy, gaps = trace_reduce.union(
        [(-5, 10, "a"), (5, 20, "b"), (30, 40, "c"), (90, 120, "d")], 0, 100)
    assert busy == 10 + 10 + 10 + 10
    assert gaps == [(20, 30), (40, 90)]


def test_reduce_events_names_gaps_by_the_innermost_host_span(monkeypatch):
    monkeypatch.setattr(trace_reduce, "MIN_GAP_NS", 5)
    device = {"/device:TPU:0": [(0, 10, "fusion"), (10, 20, "sort"),
                                (30, 40, "fusion")]}
    host = {"/host:CPU/python": [(0, 100, "bench.window"),
                                 (0, 25, "bench.solve"),
                                 (25, 100, "bench.wait"),
                                 (24, 29, "service.tick"),
                                 (50, 60, "$frame"),
                                 ]}
    r = trace_reduce.reduce_events(device, host)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["devices"] == 1
    assert r["device_ops"][0] == ["fusion", pytest.approx(20e-9)]
    assert [g[0] for g in r["idle_gaps"]] == ["bench.wait", "service.tick"]
    assert r["idle_gaps"][0][1] == pytest.approx(60e-9)


def test_busy_time_is_averaged_over_the_devices_that_ran():
    device = {"/device:TPU:0": [(0, 50, "a")],
              "/device:TPU:1": [(0, 30, "a")],
              "/device:TPU:2": [(200, 300, "idle outside the window")]}
    host = {"t": [(0, 100, "bench.window")]}
    r = trace_reduce.reduce_events(device, host)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(40e-9)


def test_a_trace_without_window_or_device_work_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_events({}, {"t": [(0, 1, "other")]})
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce_events({"/device:TPU:0": [(5, 6, "a")]},
                                   {"t": [(10, 20, "bench.window")]})


def test_recorded_cpu_trace():
    """Three sorts with a 20 ms host sleep after each, recorded with the
    benchmark's profiler options on the CPU."""
    device, host = trace_reduce.load(str(DATA / "cpu_window.xplane.pb"),
                                     cpu_ops)
    r = trace_reduce.reduce_events(device, host)
    assert r["window_s"] == pytest.approx(0.0937, rel=0.01)
    assert 0 < r["busy_s"] < r["window_s"]
    names = [n for n, _ in r["device_ops"]]
    assert "sort.0" in names
    assert r["idle_gaps"][0][0] == "bench.wait"
    assert r["idle_gaps"][0][1] >= 0.02
    assert len(r["idle_gaps"]) <= trace_reduce.TOP


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))
    p = tmp_path / "plugins" / "profile" / "x" / "host.xplane.pb"
    p.parent.mkdir(parents=True)
    p.write_bytes((DATA / "cpu_window.xplane.pb").read_bytes())
    assert trace_reduce.find_xplane(str(tmp_path)) == str(p)


def test_recorded_tpu_trace():
    """Three 2^20-element sorts with a 5 ms host sleep after each,
    recorded on a TPU v5 lite with the benchmark's profiler options."""
    device, host = trace_reduce.load(str(DATA / "tpu_window.xplane.pb"))
    assert list(device) == ["/device:TPU:0"]
    r = trace_reduce.reduce_events(device, host)
    assert r["window_s"] == pytest.approx(0.02345, rel=0.001)
    assert r["busy_s"] == pytest.approx(0.00252, rel=0.01)
    assert r["device_ops"][0][0] == "jit__lambda/sort.6"
    assert [g[0] for g in r["idle_gaps"]] == ["bench.wait"] * 3
    assert all(g[1] > 0.005 for g in r["idle_gaps"])


def test_op_name_keeps_the_hlo_instruction_name():
    assert trace_reduce.op_name(
        "%sort.6 = (s32[8]{0}) sort(s32[8]{0} %x), dimensions={0}") == \
        "sort.6"
    assert trace_reduce.op_name("fusion.2") == "fusion.2"


def test_self_times_subtract_nested_ops():
    events = [(0, 100, "while"), (10, 30, "fusion"), (40, 50, "fusion"),
              (42, 45, "inner"), (200, 210, "copy")]
    t = trace_reduce.self_times(events, 0, 205)
    assert t == {"while": 70, "fusion": 27, "inner": 3, "copy": 5}


def test_ops_are_named_after_their_program():
    ops = [(5, 6, "fusion"), (15, 16, "sort"), (30, 31, "copy")]
    modules = [(0, 10, "jit_a"), (12, 20, "jit_b")]
    assert [n for *_, n in trace_reduce.with_programs(ops, modules)] == \
        ["jit_a/fusion", "jit_b/sort", "copy"]
