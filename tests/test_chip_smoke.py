"""chip_smoke.py's phases at a tiny size on the CPU, its device gate,
and the compile-cache placement rule its entry point applies.

The phases are the same functions the chip run calls at full size; here
they run on small graphs and must still agree with the reference. The
script's ``main()`` itself refuses to run anywhere but on a TPU.
"""
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.graphs import generators as G

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def soc():
    return G.table1_scaled("soc-live-journal", scale=2 ** 11 / 5e6, seed=0)


def test_phase_static_matches_reference(smoke, soc):
    road = G.table1_scaled("usa-osm", scale=2 ** 11 / 24e6, seed=0)
    for graph, route in ((soc, "sampled"), (road, "adaptive")):
        rec = smoke.phase_static(graph, jax.devices()[0])
        assert rec["auto"]["route"] == route, graph.name
        assert rec["adaptive"]["route"] == "adaptive"
        assert rec["auto"]["labels_match"] and rec["adaptive"]["labels_match"]


def test_phase_stream_matches_reference(smoke, soc):
    rec = smoke.phase_stream(soc, np.random.default_rng(1),
                             jax.devices()[0], batches=2, insert_rows=128,
                             delete_rows=32)
    assert rec["labels_match"]
    assert [b["route"] for b in rec["inserts"]] == ["incremental-absorb"] * 2
    assert len(rec["deletes"]) == 2
    assert rec["surviving_edges"] < soc.num_edges + 2 * 128


def test_phase_serve_answers_every_query(smoke, soc):
    rec = smoke.phase_serve(soc, np.random.default_rng(2), jax.devices()[0],
                            insert_rows=128, ticks=2, query_rows=32)
    assert rec["answered"] == 4
    assert rec["failed"] == rec["service_errors"] == rec["wrong"] == 0


def test_phase_mesh_and_fleet_on_one_device(smoke, soc):
    """The four-chip phases, on a one-device mesh: the same code path
    (distributed backend, a sharded whale in the fleet) at any width."""
    devices = jax.devices()[:1]
    rec = smoke.phase_mesh(soc, devices)
    assert rec["route"] == "distributed" and rec["equals_single_device"]
    tenants = [G.rmat(7, 4, seed=3, name="social0"),
               G.grid_road(12, seed=3, name="road0")]
    rec = smoke.phase_fleet(soc, tenants, devices,
                            np.random.default_rng(3),
                            shard_threshold=soc.num_edges, query_rows=16)
    assert rec["answered"] == 2 * (len(tenants) + 1)
    assert rec["failed"] == rec["wrong"] == 0


def test_phase_check_fails_loudly(smoke):
    with pytest.raises(smoke.SmokeFailure, match="wrong"):
        smoke._check(False, "wrong answer")


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert "needs a TPU" in out.err
    for line in out.out.splitlines():
        assert "ok" not in json.loads(line)


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []            # JAX reads the variable itself


def test_compile_cache_dir_is_one_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    path = Path(first)
    assert path.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{path.name}/" in ignored
