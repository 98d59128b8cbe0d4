"""Compile the main path for a described TPU v5e chip (no chip needed).

The TPU compiler is installed even where no TPU is attached: it
compiles for a chip that is described, not present, and refuses what
the chip's compiler would refuse — a kernel that does not lower, a
program that does not fit. Nothing runs, so these tests say nothing
about results or times.

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process at a time may load the TPU
library, and every test worker imports every test file. Keep all such
compiles in this one file so they land on one worker.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024 ** 3
# the analysis scale bucket (repro.analysis.runner.BUCKETS["scale"])
SCALE_BUCKET = (1 << 20, 1 << 22)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _on(sharding, args):
    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in args]


@pytest.mark.parametrize("name", [
    "backend.adaptive",
    "backend.dynamic.absorb",
    "backend.dynamic.delete_forest",
    "queries.same_component",
    "queries.component_size",
])
def test_main_path_compiles_for_v5e(one_chip, name):
    import repro.analysis.entries  # noqa: F401 — registers query entries
    import repro.api.backends  # noqa: F401 — registers backend entries
    from repro.api.registry import trace_entries
    entry = {e.name: e for e in trace_entries()}[name]
    fn, args, _ = entry.build(*SCALE_BUCKET)
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, (name, used)


class MosaicRefusal(Exception):
    """The TPU kernel compiler refused a kernel with the named reason."""


def _cc_fused(s):
    from repro.kernels.cc_fused.ops import fused_segment_scan
    return (lambda pi, seg, tc: fused_segment_scan(pi, seg, tc,
                                                   interpret=False),
            (s((4096,)), s((4, 1024, 2)), s((4,))))


def _hook(s):
    from repro.kernels.hook.ops import hook_edges_pallas
    return (lambda pi, e: hook_edges_pallas(pi, e, interpret=False),
            (s((4096,)), s((4096, 2))))


def _multi_jump(s):
    from repro.kernels.multi_jump.ops import multi_jump
    return (lambda pi: multi_jump(pi, interpret=False), (s((4096,)),))


def _refused(build, refusal, name):
    return pytest.param(build, refusal, id=name, marks=pytest.mark.xfail(
        raises=MosaicRefusal, strict=True,
        reason=f"Mosaic refuses kernels/{name}: {refusal}"))


@pytest.mark.parametrize("build,refusal", [
    _refused(_cc_fused, "rank 1 block shapes", "cc_fused"),
    _refused(_hook, "Only 2D gather is supported", "hook"),
    _refused(_multi_jump, "Unimplemented primitive .*dynamic_slice",
             "multi_jump"),
])
def test_cc_kernel_lowers_for_v5e(one_chip, build, refusal):
    """Strict xfail: each kernel is refused for its named reason. A
    change that makes one lower turns its case into an XPASS, which
    fails until the mark comes off; any other error fails outright."""
    fn, args = build(lambda shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=one_chip))
    try:
        jax.jit(fn).lower(*args).compile()
    except Exception as e:  # noqa: BLE001 — re-raised unless it matches
        if re.search(refusal, str(e)):
            raise MosaicRefusal(str(e).splitlines()[0]) from e
        raise


# the soc-live-journal cell's graph: |V|, edge rows, residue segments
SOC = (1 << 22, 59_696_054, 28)


def _while_loops(hlo: str) -> dict:
    """``{op path: tuple type}`` of every ``while`` in compiled HLO."""
    return {m.group(2): m.group(1) for m in re.finditer(
        r'^\s*%\S+ = \((.*)\) while\(.*?op_name="[^"/]*/([^"]*)"', hlo,
        re.M)}


def test_labels_only_residue_scan_keeps_pi_in_vmem_at_soc_size(one_chip):
    """At the soc cell's size XLA keeps the 16 MiB ``pi`` in on-chip
    memory (``S(1)``) across the residue's segment scan, where every
    compress sweep gathers it: the placement that took the scan's
    compress from 3.3x slower back to the parent's speed. It is the
    compiler's heuristic, not the program's, so any change to the
    program has to keep it. The cleanup loop's compress holds pi in
    HBM; soc's cleanup runs no hook round."""
    from repro.core.rounds import WorkCounters
    from repro.core.sampled import _residue_scan_jit
    v, e, segments = SOC

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    work = WorkCounters(*(s(()) for _ in WorkCounters._fields))
    hlo = _residue_scan_jit.lower(
        s((e, 2)), s(()), s((v,)), None, work, num_nodes=v,
        num_segments=segments, fused=False, interpret=True,
        forest=False).compile().as_text()
    loops = _while_loops(hlo)
    scan = [loops["segment_scan/while"]] + [
        t for path, t in loops.items()
        if path.startswith("segment_scan/while/")
        and path.endswith("compress/while")]
    assert len(scan) == 2, sorted(loops)
    for carried in scan:
        assert re.search(rf"s32\[{v}\]{{[^}}]*S\(1\)", carried), carried
