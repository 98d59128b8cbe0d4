"""The benchmark's own device programs compile for a described TPU v5e
chip at the sizes the cells run (the grid generator at a smaller side:
its full-size compile takes about a minute)."""
import json
import os

import pytest

from conftest import CHIP


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _key(sharding):
    import jax
    return jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                sharding=sharding)


def _soc():
    return json.loads((CHIP / "configs" / "soc-live-journal.json")
                      .read_text())


def test_rmat_generator_at_soc_size(one_chip):
    import plugins
    p = _soc()["params"]
    rmat = plugins.load("generators", "rmat")
    c = rmat.rmat_edges.lower(
        _key(one_chip), _key(one_chip), scale=p["scale"],
        num_edges=p["num_edges"], a=p["a"], b=p["b"], c=p["c"]).compile()
    assert c.memory_analysis().output_size_in_bytes >= p["num_edges"] * 8


def test_grid_generator(one_chip):
    import plugins
    grid = plugins.load("generators", "grid")
    side, keep, extra = grid.sizes(
        {"side": 512, "keep_share": 0.65, "diag_share": 0.02})
    c = grid.grid_edges.lower(_key(one_chip), side=side, keep=keep,
                              extra=extra).compile()
    assert c.memory_analysis().output_size_in_bytes >= (keep + extra) * 8


def test_control_propagation_at_soc_size(one_chip):
    import jax
    import jax.numpy as jnp
    import drivers
    soc = _soc()
    edges = jax.ShapeDtypeStruct((soc["num_edges"], 2), jnp.int32,
                                 sharding=one_chip)
    labels = jax.ShapeDtypeStruct((soc["num_vertices"],), jnp.int32,
                                  sharding=one_chip)
    drivers.propagate.lower(edges, labels,
                            rounds=drivers.CONTROL_ROUNDS).compile()
