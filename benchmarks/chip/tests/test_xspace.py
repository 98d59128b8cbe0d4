"""Device time named by the program's scopes: the XSpace reader and the
per-layer readers built on it."""
from pathlib import Path

import pytest

import trace_reduce
import xspace

DATA = Path(__file__).resolve().parent / "data"
TPU = str(DATA / "tpu_window.xplane.pb")
SCOPED_READERS = {"hook_ms": "hook", "sample_csr_ms": "sampled.csr",
                  "compress_sweep_ms": "compress"}


def test_tpu_trace_maps_each_op_to_its_name_path():
    paths = xspace.read(TPU)
    assert paths["/device:TPU:0"]["jit__lambda/sort.6"] == \
        "jit(<lambda>)/jit(sort)/sort:"
    assert paths["/device:TPU:0"]["jit__lambda/multiply_add_fusion"] == \
        "jit(<lambda>)/add:"
    assert paths["/host:CPU"] == {}


def test_the_reader_agrees_with_tensorflows_xplane_proto():
    pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = pb2.XSpace()
    space.ParseFromString(Path(TPU).read_bytes())
    want = {}
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        modules, ops = {}, {}
        for md in plane.event_metadata.values():
            stats = {names[s.metadata_id]: s for s in md.stats}
            if "tf_op" in stats:
                ops[trace_reduce.op_name(md.name)] = (
                    modules, stats["program_id"].uint64_value,
                    stats["tf_op"].str_value)
            else:
                name, _, pid = md.name.rstrip(")").rpartition("(")
                if md.name.endswith(")") and pid.isdigit():
                    modules[int(pid)] = name
        want[plane.name] = {f"{m[p]}/{op}" if p in m else op: path
                            for op, (m, p, path) in ops.items()}
    assert xspace.read(TPU) == want


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/while/body/segment_scan/while/body/hook/scatter-min", "hook"),
    ("jit(f)/segment_scan/while/body/closed_call/compress/while/body/gather",
     "compress"),
    ("jit(f)/while/body/hook/compress/gather:", "compress"),
    ("jit(_sample_phase_jit)/sampled.csr/sort:", "sampled.csr"),
    ("jit(<lambda>)/jit(sort)/sort:", xspace.UNSCOPED),
    ("", xspace.UNSCOPED)])
def test_an_op_counts_under_the_innermost_listed_scope(path, scope):
    assert xspace.scope_of(path) == scope


def test_scope_seconds_clip_to_the_window_and_count_self_time():
    """On the recorded TPU trace, scoping by an op's own name gives the
    self time the breakdown gives that op, and the rest of the window's
    device time sums under no scope."""
    device, host = trace_reduce.load(TPU)
    r = trace_reduce.reduce_events(device, host)
    want = dict(r["device_ops"])["jit__lambda/sort.6"]
    got = xspace.scope_seconds(TPU, scopes=("sort",))
    assert set(got) == {"sort", xspace.UNSCOPED}
    assert got["sort"] == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(
        sum(t for _, t in r["device_ops"]))


@pytest.mark.parametrize("workload,route", [("toy.static", "sampled"),
                                            ("grid.static", "adaptive")])
def test_readers_on_a_cpu_traced_run(toy, cpu_trace, workload, route):
    """A traced CPU run of a toy cell: the ingest spans are read, and
    the CPU's op paths come from the HLO of the programs the run
    compiled, so each scope reader reads that scope's device time."""
    import bench
    import trace_reduce
    from repro.obs import trace as obs
    out = toy(workload, trace=1)
    res = out["result"]
    assert res["correct"] and out["notes"]["routes"] == [route]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["ingest_skew_ms"] > 0 and m["ingest_put_ms"] > 0
    scopes = xspace.scope_seconds(
        trace_reduce.find_xplane(str(bench.TRACE_DIR)))
    assert sum(scopes.values()) == pytest.approx(res["device"]["busy_s"])
    units = res["attempted"]
    assert m["hook_ms"] == pytest.approx(scopes["hook"] * 1e3 / units)
    sweeps = obs.tracer().counters["solve.jump_sweeps"]
    assert m["compress_sweep_ms"] == pytest.approx(
        scopes["compress"] * 1e3 / sweeps)
    if route == "sampled":
        assert m["sample_csr_ms"] == pytest.approx(
            scopes["sampled.csr"] * 1e3 / units)
        assert {"sampled.sample", "sampled.census", "sampled.pack"} \
            <= set(scopes)
    else:
        assert "sample_csr_ms" not in m
        assert {"segment_scan", "cleanup"} <= set(scopes)


def test_scope_readers_stay_silent_without_scopes(toy, cpu_trace,
                                                  monkeypatch):
    """A program without the scopes (the parent of the change that
    added them) leaves every op unscoped: the readers report nothing
    and raise nothing."""
    monkeypatch.setattr(xspace, "live_paths", lambda: {})
    res = toy("toy.static", trace=1)["result"]
    assert res["correct"]
    assert not set(SCOPED_READERS) & set(res["metrics"])
    assert "ingest_skew_ms" in res["metrics"]
