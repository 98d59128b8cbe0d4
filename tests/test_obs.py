"""PR 7 acceptance: the runtime telemetry layer (``repro.obs``).

Pins the tentpole contracts: histogram quantile math against an
``np.percentile`` oracle, merge-associativity of the device ``Metrics``
pytree, ring-buffer wraparound, the no-op cost model of disabled
spans, plan-provenance tags (via the ``ExecutionPlan.as_dict()``
schema snapshot), the always-on autotune/deprecation counters, the
exporters + CLI — and the headline invariant: the INSTRUMENTED
steady-state service tick (spans + on-device metrics + SLO recording
all enabled) still performs zero host transfers under
``jax.transfer_guard("disallow")``.
"""
import json
import time
import warnings

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import WORK_SPEC, HistogramSpec, Metrics
from repro.obs.slo import DEFAULT_LATENCY_SPEC, SLORecorder
from repro.obs.trace import EventLog


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts disabled with an empty tracer and leaves the
    process-wide state the way it found it (disabled is the default)."""
    obs.disable()
    obs.tracer().reset()
    yield
    obs.disable()
    obs.tracer().reset()


# ---------------------------------------------------------------------------
# histogram math
# ---------------------------------------------------------------------------

def test_histogram_quantile_matches_np_percentile_oracle():
    """Fixed-bucket quantiles vs the exact oracle over random latency
    samples: within one log-bucket (``spec.resolution()``) at p50, p90,
    and p99 — the documented error bound of the SLO layer."""
    spec = DEFAULT_LATENCY_SPEC
    rng = np.random.default_rng(7)
    for trial in range(3):
        # log-uniform latencies spanning 10µs .. 1s
        samples = 10.0 ** rng.uniform(-5, 0, size=4000)
        counts = np.zeros(spec.num_bins, np.int64)
        for s in samples:
            spec.observe(counts, s)
        for q in (0.50, 0.90, 0.99):
            est = spec.quantile(counts, q)
            true = float(np.percentile(samples, q * 100))
            ratio = est / true
            bound = spec.resolution() * 1.05
            assert 1 / bound <= ratio <= bound, (trial, q, est, true)


def test_histogram_quantile_edge_cases():
    spec = HistogramSpec(lo=1.0, hi=1000.0, num_bins=16)
    counts = np.zeros(16, np.int64)
    assert np.isnan(spec.quantile(counts, 0.5))
    spec.observe(counts, 1e-9)           # underflow bucket
    assert spec.quantile(counts, 0.5) == spec.lo
    counts[:] = 0
    spec.observe(counts, 1e9)            # overflow bucket
    assert spec.quantile(counts, 0.5) == spec.hi


def test_device_bucketing_matches_host_bucketing():
    """``bucket_device`` (the jitted scatter index) and the host
    ``bucket`` agree on every bucket boundary neighborhood."""
    import jax.numpy as jnp
    vals = np.concatenate([[0.0, 0.5, 1.0, 1.5],
                           WORK_SPEC.edges[:5] * 0.999,
                           WORK_SPEC.edges[:5] * 1.001,
                           [2.0**29, 2.0**31]]).astype(np.float32)
    host = WORK_SPEC.bucket(vals)
    dev = np.array([int(WORK_SPEC.bucket_device(jnp.asarray(v)))
                    for v in vals])
    np.testing.assert_array_equal(dev, host)


# ---------------------------------------------------------------------------
# Metrics pytree
# ---------------------------------------------------------------------------

def _mutated_metrics(seed: int) -> Metrics:
    """A Metrics accumulator after a few recorded batches (device)."""
    import jax.numpy as jnp

    from repro.core.rounds import WorkCounters
    from repro.obs.metrics import record_mutation
    rng = np.random.default_rng(seed)
    m = Metrics.zeros()
    for k in range(3):
        work = WorkCounters.zeros().add(
            hook_ops=int(rng.integers(1, 1000)),
            jump_sweeps=int(rng.integers(1, 20)))
        m = record_mutation(
            m, work, jnp.int32(int(rng.integers(1, 500))),
            jnp.int32(k), jnp.int32(k + int(rng.integers(0, 2))),
            kind="insert" if k % 2 == 0 else "delete")
    return m


def test_metrics_merge_is_associative_and_commutative():
    """Per-tenant accumulators must fold in any order: (a+b)+c ==
    a+(b+c) and a+b == b+a, leaf-exact."""
    a, b, c = (_mutated_metrics(s) for s in (1, 2, 3))
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    for l_leaf, r_leaf in zip(left, right):
        np.testing.assert_array_equal(np.asarray(l_leaf),
                                      np.asarray(r_leaf))
    ab, ba = a.merge(b), b.merge(a)
    for l_leaf, r_leaf in zip(ab, ba):
        np.testing.assert_array_equal(np.asarray(l_leaf),
                                      np.asarray(r_leaf))


def test_metrics_flush_reports_named_counters():
    from repro.obs.metrics import flush
    out = flush(_mutated_metrics(4))
    assert out["counters"]["absorbs"] == 2
    assert out["counters"]["deletes"] == 1
    assert out["counters"]["edges_absorbed"] > 0
    assert out["histograms"]["absorb_edges"]["count"] == 2
    assert "p50" in out["histograms"]["absorb_edges"]
    json.dumps(out)                      # plain-JSON by construction


# ---------------------------------------------------------------------------
# ring buffer + spans
# ---------------------------------------------------------------------------

def test_event_log_wraparound():
    log = EventLog(capacity=8)
    for i in range(20):
        log.append({"i": i})
    assert len(log) == 8
    assert log.total == 20
    assert log.dropped == 12
    assert [e["i"] for e in log.events()] == list(range(12, 20))
    log.clear()
    assert len(log) == 0 and log.dropped == 0 and log.events() == []


def test_event_log_before_wrap_keeps_everything():
    log = EventLog(capacity=8)
    for i in range(5):
        log.append({"i": i})
    assert [e["i"] for e in log.events()] == [0, 1, 2, 3, 4]
    assert log.dropped == 0


def test_disabled_span_is_shared_noop():
    """Disabled mode returns ONE shared stateless object — the <=5%
    overhead gate's mechanism (flag check, not try/except)."""
    assert not obs.enabled()
    s1, s2 = obs.span("a"), obs.span("b", tenant="t", big=1)
    assert s1 is s2
    assert s1.enabled is False
    with s1 as inner:
        inner.tag(anything=1)
    assert len(obs.tracer().log) == 0    # nothing recorded


def test_span_nesting_depth_tags_and_order():
    obs.enable()
    with obs.span("outer", tenant="t0", a=1):
        with obs.span("inner") as sp:
            sp.tag(b=2)
    evs = obs.tracer().log.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    inner, outer = evs
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert inner["tags"] == {"b": 2}
    assert outer["tenant"] == "t0" and outer["tags"] == {"a": 1}
    assert outer["dur_us"] >= inner["dur_us"]


def test_span_records_ids_parents_and_self_time():
    """Each record names the span open around it; ``span_summary``
    takes the children's time off the parent's (choosing-metrics §4)."""
    obs.enable()
    with obs.span("outer"):
        with obs.span("a"):
            time.sleep(0.002)
        with obs.span("b"):
            with obs.span("c"):
                pass
    evs = {e["name"]: e for e in obs.tracer().log.events()}
    assert evs["outer"]["parent"] is None
    assert evs["a"]["parent"] == evs["b"]["parent"] == evs["outer"]["id"]
    assert evs["c"]["parent"] == evs["b"]["id"]
    assert len({e["id"] for e in evs.values()}) == 4
    assert all(e["ts_us"] == pytest.approx(e["ts_ns"] / 1e3)
               for e in evs.values())
    summ = obs.tracer().summary()
    own = evs["outer"]["dur_us"] - evs["a"]["dur_us"] - evs["b"]["dur_us"]
    assert summ["outer"]["self_ms"] == pytest.approx(own / 1e3, abs=2e-3)
    assert summ["b"]["self_ms"] == pytest.approx(
        (evs["b"]["dur_us"] - evs["c"]["dur_us"]) / 1e3, abs=2e-3)
    assert summ["a"]["self_ms"] == summ["a"]["total_ms"]


def test_span_ts_ns_is_on_the_profilers_clock(tmp_path):
    """A record's ``ts_ns`` lies within 1 ms of its mirrored annotation
    in a profile captured around it (the trace's times count from its
    ``profile_start_time``)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    obs.enable(jax_annotations=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with obs.span("obs.clock_probe"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    profile = ProfileData.from_file(path)
    start = dict(profile.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    marks = sorted(start + ev.start_ns for plane in profile.planes
                   for line in plane.lines for ev in line.events
                   if ev.name == "obs.clock_probe")
    recs = sorted(e["ts_ns"] for e in obs.tracer().log.events())
    assert len(marks) == len(recs) == 3
    assert all(abs(m - r) < 1e6 for m, r in zip(marks, recs))


def test_count_device_keeps_device_scalars_until_read():
    """Off, a device counter does nothing; on, it keeps the scalar with
    no transfer and folds it into ``counters`` as an int when read."""
    import jax
    import jax.numpy as jnp
    x = jnp.int32(5) + 2
    obs.count_device("t.sweeps", x)
    assert "t.sweeps" not in obs.tracer().counters
    obs.enable()
    with jax.transfer_guard("disallow"):
        obs.count_device("t.sweeps", x)
        obs.count_device("t.sweeps", x)
        obs.count_device("t.calls", 1)
    assert obs.tracer().counters == {"t.sweeps": 14, "t.calls": 1}
    assert type(obs.tracer().counters["t.sweeps"]) is int


def test_count_device_folds_its_oldest_values_past_the_cap(monkeypatch):
    from repro.obs import trace
    monkeypatch.setattr(trace, "PENDING_CAP", 8)
    obs.enable()
    for _ in range(9):
        obs.count_device("t.n", 1)
    assert len(obs.tracer()._pending) == 5
    assert obs.tracer()._counters == {"t.n": 4}
    assert obs.tracer().counters == {"t.n": 9}


def test_span_records_error_and_unwinds_stack():
    obs.enable()
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    (ev,) = obs.tracer().log.events()
    assert ev["error"] == "RuntimeError"
    assert obs.tracer()._stack == []


def test_jax_profiler_annotation_bridge_smoke():
    """Opt-in bridge constructs real jax.profiler annotations (no
    profiler session active — they must be harmless no-ops), and keys
    the persistent compile cache on op metadata until disabled, so a
    cached executable cannot bring another build's scope names into a
    profile."""
    import jax
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    obs.enable(jax_annotations=True)
    assert getattr(jax.config, key) is True
    with obs.span("annotated"):
        pass
    with obs.span("stepped", step=3):    # StepTraceAnnotation path
        pass
    assert [e["name"] for e in obs.tracer().log.events()] == \
        ["annotated", "stepped"]
    obs.disable()
    assert getattr(jax.config, key) == before


# ---------------------------------------------------------------------------
# exporters + CLI
# ---------------------------------------------------------------------------

def _make_trace():
    tracer = obs.enable(capacity=64)
    with obs.span("tick", tenant="a", step=1):
        with obs.span("absorb", tenant="a", edges=10):
            pass
    obs.count("autotune.miss")
    return tracer


def test_export_jsonl_roundtrip(tmp_path):
    tracer = _make_trace()
    path = tmp_path / "trace.jsonl"
    tracer.export_jsonl(str(path))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    spans = [ln for ln in lines if ln["type"] == "span"]
    (tail,) = [ln for ln in lines if ln["type"] == "counters"]
    assert [s["name"] for s in spans] == ["absorb", "tick"]
    assert spans[1]["step"] == 1
    assert tail["counters"]["autotune.miss"] == 1
    assert tail["dropped"] == 0


def test_export_chrome_trace_is_perfetto_shaped(tmp_path):
    tracer = _make_trace()
    path = tmp_path / "trace.json"
    tracer.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(ev)
    args = {e["name"]: e["args"] for e in doc["traceEvents"]}
    assert args["absorb"]["edges"] == 10
    assert args["tick"]["tenant"] == "a"


def test_cli_summary_and_perfetto(tmp_path, capsys):
    from repro.obs.__main__ import main
    tracer = _make_trace()
    trace = tmp_path / "t.jsonl"
    tracer.export_jsonl(str(trace))
    assert main(["summary", str(trace)]) == 0
    out = capsys.readouterr().out
    assert "absorb" in out and "autotune.miss = 1" in out
    out_json = tmp_path / "t.json"
    assert main(["perfetto", str(trace), str(out_json)]) == 0
    assert len(json.loads(out_json.read_text())["traceEvents"]) == 2


# ---------------------------------------------------------------------------
# plan schema + facade spans
# ---------------------------------------------------------------------------

# THE as_dict schema snapshot: tracer tags and explain() both render
# from this dict — a key change here is a trace-format change and must
# be deliberate.
EXPECTED_PLAN_KEYS = [
    "backend", "batch_size", "bucket", "bucket_key", "density",
    "lift_steps", "num_edges", "num_nodes", "num_segments",
    "predicted", "reason", "segmentation",
]
EXPECTED_SEGMENTATION_KEYS = [
    "num_segments", "padded_edges", "segment_size", "source",
]


def test_plan_as_dict_schema_snapshot():
    from repro.api import Solver
    plan = Solver.open([[0, 1], [1, 2], [2, 3]], num_nodes=8).plan()
    d = plan.as_dict()
    assert sorted(d) == EXPECTED_PLAN_KEYS
    assert sorted(d["segmentation"]) == EXPECTED_SEGMENTATION_KEYS
    json.dumps(d)                        # JSON-clean by contract
    # the renderer consumes the same dict: every scalar fact in the
    # dict appears verbatim in the rendered explain()
    text = plan.explain()
    assert f"backend={d['backend']} ({d['reason']})" in text
    assert f"bucket={d['bucket_key']}" in text
    assert f"|E|={d['num_edges']}" in text
    assert d["segmentation"]["source"] in text


def test_solver_solve_span_tags_carry_plan_provenance():
    from repro.api import Solver
    obs.enable()
    s = Solver.open([[0, 1], [1, 2]], num_nodes=8, name="tenant-x")
    s.solve()
    d = s.last_plan.as_dict()
    (ev,) = [e for e in obs.tracer().log.events()
             if e["name"] == "solver.solve"]
    assert ev["tenant"] == "tenant-x"
    assert ev["tags"]["backend"] == d["backend"]
    assert ev["tags"]["reason"] in ("autotune", "heuristic")
    assert ev["tags"]["bucket"] == d["bucket_key"]
    # policy + plan.run spans nested under the facade call
    names = {e["name"] for e in obs.tracer().log.events()}
    assert {"policy.select", "plan.run"} <= names


def test_solver_open_spans_its_host_ingest_and_solve_counts_work():
    """``solver.open`` encloses the ingest steps of a host graph, and a
    traced solve keeps its hook rounds and sweeps as device counters."""
    from repro.api import Solver
    obs.enable()
    s = Solver.open(np.array([[0, 1], [1, 2], [4, 5]]), num_nodes=8,
                    name="o")
    res = s.solve("adaptive")
    evs = obs.tracer().log.events()
    (op,) = [e for e in evs if e["name"] == "solver.open"]
    assert op["tenant"] == "o"
    ingest = [e for e in evs if e["name"].startswith("ingest.")]
    assert [e["name"] for e in ingest] == \
        ["ingest.convert", "ingest.skew", "ingest.put"]
    assert all(e["parent"] == op["id"] for e in ingest)
    c = obs.tracer().counters
    assert c["solve.hook_rounds"] == int(res.work.hook_rounds) > 0
    assert c["solve.jump_sweeps"] == int(res.work.jump_sweeps) > 0


def test_solver_mutation_spans_and_device_metrics():
    from repro.api import Solver
    obs.enable()
    s = Solver.open(num_nodes=16, name="m")
    s.insert([[0, 1], [1, 2]])
    s.insert([[2, 3]])
    s.delete([[1, 2]])
    evs = obs.tracer().log.events()
    ins = [e for e in evs if e["name"] == "solver.insert"]
    dels = [e for e in evs if e["name"] == "solver.delete"]
    assert len(ins) == 2 and len(dels) == 1
    assert all(e["tenant"] == "m" for e in ins + dels)
    assert all("route" in e["tags"] for e in ins + dels)
    # metrics attached automatically (tracing was on) and flushed
    # through the audited sink
    out = s.metrics_summary()
    counters = out["counters"]
    assert counters["absorbs"] + counters["rebuilds"] == 2
    assert counters["deletes"] + counters["rebuilds"] >= 1
    # merge across sessions == counter-wise sum
    s2 = Solver.open(num_nodes=16, name="m2")
    s2.insert([[4, 5]])
    merged = s.metrics.merge(s2.metrics)
    np.testing.assert_array_equal(
        np.asarray(merged.counts),
        np.asarray(s.metrics.counts) + np.asarray(s2.metrics.counts))


def test_query_spans_cover_all_kinds():
    from repro.api import Solver
    obs.enable()
    s = Solver.open([[0, 1], [2, 3]], num_nodes=8, name="q")
    s.same_component([[0, 1]])
    s.component_size([0, 2])
    s.num_components()
    s.component_histogram()
    names = [e["name"] for e in obs.tracer().log.events()]
    for kind in ("same_component", "component_size", "num_components",
                 "component_histogram"):
        assert f"solver.query.{kind}" in names


def _lowered_text(program: str) -> str:
    import jax.numpy as jnp

    from repro.core.cc import _cc_jit
    from repro.core.rounds import WorkCounters
    from repro.core.sampled import _residue_scan_jit, _sample_phase_jit
    edges, true = jnp.zeros((64, 2), jnp.int32), jnp.int32(64)
    # ``*_labels``: the labels-only programs solve() runs
    forest = not program.endswith("_labels")
    if program == "cc_adaptive":
        lowered = _cc_jit.lower(edges, None, num_nodes=32,
                                method="adaptive", num_segments=4)
    elif program.startswith("sample_phase"):
        lowered = _sample_phase_jit.lower(edges, true, num_nodes=32, k=2,
                                          sample_rounds=2, forest=forest)
    else:
        lowered = _residue_scan_jit.lower(
            edges, true, jnp.arange(32, dtype=jnp.int32),
            jnp.full((32, 2), -1, jnp.int32) if forest else None,
            WorkCounters.zeros(), num_nodes=32, num_segments=4,
            fused=False, interpret=True, forest=forest)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("program,scopes", [
    ("cc_adaptive", {"segment_scan", "cleanup", "hook", "compress"}),
    ("sample_phase", {"sampled.csr", "sampled.sample", "sampled.census",
                      "hook", "compress"}),
    ("residue_scan", {"sampled.pack", "segment_scan", "cleanup", "hook",
                      "compress"}),
    ("sample_phase_labels", {"sampled.csr", "sampled.sample",
                             "sampled.census", "hook", "compress"}),
    ("residue_scan_labels", {"sampled.pack", "segment_scan", "cleanup",
                             "hook", "compress"})])
def test_round_phases_carry_named_scopes_in_hlo(program, scopes):
    """The phases' named scopes reach the op names of the
    lowered programs, where the profiler's ``tf_op`` paths come from."""
    import re
    names = set(re.findall(r'loc\("([^"]+)"', _lowered_text(program)))
    parts = {part for name in names for part in name.split("/")}
    assert scopes <= parts, scopes - parts


@pytest.mark.parametrize("method", ["sampled", "adaptive", "auto"])
def test_static_solve_syncs_nothing_with_tracing_off(method):
    """Solver.open -> solve on a host graph copies host to device and
    reads nothing back while tracing is off: no ``int()``, no
    ``device_get`` of a device value on the path."""
    import jax

    from _graphgen import power_law
    from repro.api import Solver
    from repro.graphs.device import DeviceGraph
    n = 2048
    edges = np.asarray(power_law(n, 16384, seed=5), np.int32)
    want = np.asarray(Solver.open(edges, n).solve(method).labels)  # warm
    assert not obs.enabled()
    with jax.transfer_guard_device_to_host("disallow"):
        s = Solver.open(DeviceGraph.from_edges(edges, n))
        labels = s.solve(method).labels
    np.testing.assert_array_equal(np.asarray(labels), want)
    if method != "adaptive":
        assert s.last_method == "sampled"


# ---------------------------------------------------------------------------
# always-on counters
# ---------------------------------------------------------------------------

def test_autotune_hit_miss_counters_always_on():
    from repro.connectivity import policy
    assert not obs.enabled()             # counters must not need enable()
    cache = policy.AutotuneCache()
    cache.lookup(1000, 4000)
    cache.record(1000, 4000, "adaptive", 1.0)
    cache.lookup(1000, 4000)
    cache.lookup(1000, 4000)
    c = obs.tracer().counters
    assert c["autotune.miss"] == 1
    assert c["autotune.hit"] == 2


def test_deprecation_shim_hits_counted_every_call():
    from repro import _deprecation
    _deprecation.reset()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _deprecation.warn_once("obs_test_shim", "repro.api.Solver")
        _deprecation.warn_once("obs_test_shim", "repro.api.Solver")
        _deprecation.warn_once("obs_test_shim", "repro.api.Solver")
    assert len(caught) == 1              # warn-once contract unchanged
    assert obs.tracer().counters["deprecated.obs_test_shim"] == 3


# ---------------------------------------------------------------------------
# SLO recorder
# ---------------------------------------------------------------------------

def test_slo_recorder_per_tenant_and_exact_global_merge():
    rec = SLORecorder()
    rng = np.random.default_rng(0)
    lat_a = 10.0 ** rng.uniform(-4, -2, 500)     # 100µs..10ms
    lat_b = 10.0 ** rng.uniform(-3, -1, 500)     # 1ms..100ms
    for v in lat_a:
        rec.record("a", "same_component", float(v))
    for v in lat_b:
        rec.record("b", "same_component", float(v))
    summ = rec.summary()
    assert set(summ["tenants"]) == {"a", "b"}
    row_a = summ["tenants"]["a"]["same_component"]
    assert row_a["count"] == 500
    assert row_a["p50_ms"] <= row_a["p90_ms"] <= row_a["p99_ms"]
    # global = exact bucket merge, not an average of percentiles
    g = summ["global"]["same_component"]
    assert g["count"] == 1000
    merged = rec.merged(kinds=("same_component",))
    assert g["p99_ms"] == round(merged.quantile(0.99) * 1e3, 4)
    bound = rec.spec.resolution() * 1.05
    both = np.concatenate([lat_a, lat_b])
    true_p50 = float(np.percentile(both, 50))
    est_p50 = merged.quantile(0.50)
    assert 1 / bound <= est_p50 / true_p50 <= bound


def test_slo_merge_rejects_mismatched_bucket_specs():
    """Bucket counts only add exactly when every stream shares one
    edge layout. A recorder whose ``_hists`` were populated externally
    (the fleet's per-device merge path) with a different spec must
    raise, not silently read percentiles off the wrong edges — and the
    same check guards ``merge_recorders`` at both the recorder and the
    per-stream level."""
    from repro.obs.metrics import HistogramSpec
    from repro.obs.slo import LatencyHistogram, merge_recorders

    other_spec = HistogramSpec(lo=1e-3, hi=1.0, num_bins=8)
    rec = SLORecorder()
    rec.record("a", "insert", 0.01)
    # smuggle a foreign-layout stream in, the way an external populator
    # (bad merge code) would
    rec._hists[("b", "insert")] = LatencyHistogram(other_spec)
    rec._hists[("b", "insert")].record(0.01)
    with pytest.raises(ValueError, match="not mergeable"):
        rec.merged()
    # per-tenant read that avoids the bad stream still works
    assert rec.merged(tenant="a").count == 1

    # recorder-level mismatch
    r1, r2 = SLORecorder(), SLORecorder(other_spec)
    r1.record("a", "insert", 0.01)
    r2.record("a", "insert", 0.01)
    with pytest.raises(ValueError, match="not mergeable"):
        merge_recorders([r1, r2])
    # stream-level mismatch behind a matching recorder spec
    r3 = SLORecorder()
    r3._hists[("c", "insert")] = LatencyHistogram(other_spec)
    with pytest.raises(ValueError, match="spec"):
        merge_recorders([r1, r3])
    # clean merge is exact: counts sum per (tenant, kind)
    r4 = SLORecorder()
    r4.record("a", "insert", 0.02)
    r4.record("d", "query", 0.001)
    out = merge_recorders([r1, r4])
    assert out.merged(tenant="a").count == 2
    assert out.merged(tenant="d").count == 1


# ---------------------------------------------------------------------------
# the headline contract: instrumented tick stays transfer-free
# ---------------------------------------------------------------------------

def test_instrumented_service_tick_stays_transfer_free():
    """Spans + on-device Metrics + SLO recording all ENABLED: the
    steady-state coalesced insert AND delete ticks still perform zero
    host transfers (``jax.transfer_guard("disallow")``); telemetry
    materializes only at the explicit ``obs_summary()`` flush."""
    import jax

    import repro.graphs.generators as G
    from repro.connectivity.registry import GraphRegistry
    from repro.connectivity.service import ConnectivityService
    from repro.graphs.device import DeviceGraph

    obs.enable(capacity=4096)
    g = G.grid_road(8, extra_prob=0.0, seed=0)
    n, edges = g.num_nodes, np.asarray(g.edges, np.int32)
    reg = GraphRegistry()
    svc = ConnectivityService(reg, slots=16)
    reg.create("t", n)                   # metrics attach (tracing on)
    # warm every jit entry the steady state will hit — including the
    # record_mutation fold (its first call compiles + transfers consts)
    svc.submit_insert("t", edges[:-40])
    svc.run()
    svc.submit_insert("t", edges[-40:-30])
    svc.submit_insert("t", edges[-30:-20])
    svc.run()
    svc.submit_delete("t", edges[:5])
    svc.submit_delete("t", edges[5:10])
    svc.run()

    # steady state, same shapes, instrumentation live
    svc.submit_insert("t", DeviceGraph.from_edges(edges[-20:-10], n))
    svc.submit_insert("t", DeviceGraph.from_edges(edges[-10:], n))
    svc.submit_delete("t", DeviceGraph.from_edges(edges[10:15], n))
    svc.submit_delete("t", DeviceGraph.from_edges(edges[15:20], n))
    with jax.transfer_guard("disallow"):
        finished = svc.run()
    assert [r.error for r in finished] == [None] * 4

    # the guarded ticks actually recorded telemetry
    names = [e["name"] for e in obs.tracer().log.events()]
    assert "service.tick" in names
    assert "service.insert" in names and "service.delete" in names
    assert svc.slo.hist("t", "insert") is not None
    summary = svc.obs_summary()          # the one explicit sync
    dm = summary["device_metrics"]
    assert dm is not None
    assert dm["counters"]["absorbs"] >= 2
    assert dm["counters"]["deletes"] >= 2
    assert summary["latency"]["tenants"]["t"]["insert"]["count"] >= 2


def test_service_query_latency_lands_in_slo():
    from repro.connectivity.registry import GraphRegistry
    from repro.connectivity.service import ConnectivityService

    obs.enable()
    reg = GraphRegistry()
    svc = ConnectivityService(reg, slots=8)
    reg.create("t", 16)
    svc.submit_insert("t", [[0, 1], [1, 2]])
    svc.submit_query("t", "same_component", [[0, 2], [0, 3]])
    svc.submit_query("t", "count_components")
    svc.run()
    summ = svc.slo.summary()
    t_rows = summ["tenants"]["t"]
    assert t_rows["same_component"]["count"] == 1
    assert t_rows["count_components"]["count"] == 1
    assert t_rows["same_component"]["p50_ms"] > 0


def test_service_slo_not_recorded_when_disabled():
    from repro.connectivity.registry import GraphRegistry
    from repro.connectivity.service import ConnectivityService

    assert not obs.enabled()
    reg = GraphRegistry()
    svc = ConnectivityService(reg, slots=8)
    reg.create("t", 16)
    svc.submit_insert("t", [[0, 1]])
    svc.submit_query("t", "count_components")
    svc.run()
    assert svc.slo.summary()["tenants"] == {}
    assert len(obs.tracer().log) == 0
