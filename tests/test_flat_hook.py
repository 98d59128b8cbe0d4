"""Every composition hooks flat labels, so the root chase is gone.

A full compress runs before every hook (or the labels start flat:
identity, canonical state, a scoped reset of it), so ``pi[pi[x]] ==
pi[x]`` whenever a hook reads ``pi`` and a chase ``pu <- pi[pu]`` can
never move a label. Two guards:

* **invariant** — each path is run twice over the conformance corpus:
  as shipped, and with the chasing hooks below swapped into the same
  compositions (plain and forest hooks with a chase of depth 2).
  Labels, recorded
  parent edges, ``hook_rounds`` and ``jump_sweeps`` must be
  bit-identical; only ``hook_ops`` may differ (the chase is no longer
  issued, so it is no longer billed);
* **structure** — the lowered programs issue 2 gathers per plain hook
  and 5 per forest hook under the ``hook`` named scope, so a chase
  cannot come back unnoticed; the labels-only sampled programs hook
  plainly and scatter into no parent table.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _graphgen import corpus
from repro.core import rounds

CHASE = 2          # the chase depth every composition used to run


def _chase(pi, edges, trace_calls):
    trace_calls.append(1)
    pu, pv = pi[edges[..., 0]], pi[edges[..., 1]]
    for _ in range(CHASE):
        pu, pv = pi[pu], pi[pv]
    return jnp.maximum(pu, pv), jnp.minimum(pu, pv)


def _chasing_win(pi, hi, lo):
    """The forest hooks' win rule: the write landed and lowered
    ``pi[hi]`` (one scatter-min over slots breaks ties). Returns
    ``(new_pi, record_at)``, ``n`` where nothing is recorded."""
    n = pi.shape[0]
    new_pi = pi.at[hi].min(lo)
    won = jnp.logical_and(new_pi[hi] == lo, new_pi[hi] < pi[hi])
    slot = jnp.arange(hi.shape[0], dtype=jnp.int32)
    winner = jnp.full((n,), jnp.iinfo(jnp.int32).max, jnp.int32) \
        .at[jnp.where(won, hi, n)].min(slot, mode="drop")
    return new_pi, jnp.where(won & (winner[hi] == slot), hi, n)


@contextlib.contextmanager
def _chasing():
    """Swap chasing hooks into every composition of ``rounds`` (and the
    fused scan for the chasing jnp one), with jit caches cleared on
    both sides so each program is traced against what is in place.
    Yields a list that grows by one per traced chasing hook."""
    calls = []

    def hook(pi, edges, lift_steps=0):
        hi, lo = _chase(pi, edges, calls)
        return pi.at[hi].min(lo)

    def ops(bill_nodes=None, **_):
        return rounds.RoundOps(
            hook=hook,
            compress=lambda p, w: rounds.compress(p, w,
                                                  bill_nodes=bill_nodes))

    def forest_hook(pi, parents, edges):
        new_pi, at = _chasing_win(pi, *_chase(pi, edges, calls))
        return new_pi, parents.at[at].set(edges, mode="drop")

    def forest_ids_hook(pi, parents, parent_eidx, edges, edge_ids):
        new_pi, at = _chasing_win(pi, *_chase(pi, edges, calls))
        return (new_pi, parents.at[at].set(edges, mode="drop"),
                parent_eidx.at[at].set(edge_ids, mode="drop"))

    mp = pytest.MonkeyPatch()
    mp.setattr(rounds, "hook_edges", hook)
    mp.setattr(rounds, "jnp_round_ops", ops)
    mp.setattr(rounds, "fused_round_ops", ops)
    mp.setattr(rounds, "hook_edges_forest", forest_hook)
    mp.setattr(rounds, "hook_edges_forest_ids", forest_ids_hook)
    jax.clear_caches()
    try:
        yield calls
    finally:
        mp.undo()
        jax.clear_caches()


def _graphs():
    return [(name, n, e) for name, n, e in corpus() if n > 0]


def _out(labels, work, parents=None):
    work = work if isinstance(work, dict) else work._asdict()
    return {"labels": np.asarray(labels),
            "parents": None if parents is None else np.asarray(parents),
            "hook_rounds": int(work["hook_rounds"]),
            "jump_sweeps": int(work["jump_sweeps"])}


def _run_adaptive(n, e):
    from repro.api import solve
    r = solve(e, n, backend="adaptive")
    return _out(r.labels, r.work)


def _run_forest(n, e):
    from repro.core.cc import solve_forest
    r = solve_forest(e, n, method="adaptive")
    return _out(r.labels, r.work, r.parents)


def _run_sampled(n, e):
    from repro.core.cc import solve_forest
    r = solve_forest(e, n, method="sampled")
    return _out(r.labels, r.work, r.parents)


def _run_sampled_labels(n, e):
    from repro.core.sampled import solve_sampled
    r = solve_sampled(e, n)
    return _out(r.labels, r.work, r.parents)


def _run_fused(n, e):
    from repro.api import solve
    r = solve(e, n, backend="pallas_fused")
    return _out(r.labels, r.work)


def _run_incremental(n, e):
    from repro.core.incremental import IncrementalCC
    inc = IncrementalCC(n)
    for batch in np.array_split(e, 3):
        inc.insert(batch)
    return _out(inc.labels, inc.work)


def _run_dynamic(n, e):
    """Forest absorb, then a tree-aware delete, a plain scoped delete
    (stales the forest) and a tree-aware delete that rebuilds it."""
    from repro.core.incremental import DynamicCC
    from repro.graphs.device import DeviceGraph
    dyn = DynamicCC(n)
    dyn.insert(e)
    rng = np.random.default_rng(n)
    dels = e[rng.permutation(len(e))[:max(1, len(e) // 3)]]
    for i, batch in enumerate(np.array_split(dels, 3)):
        g = DeviceGraph.from_edges(np.asarray(batch, np.int32), n)
        (dyn.delete_graph if i == 1 else dyn.delete_graph_forest)(g)
    return _out(dyn.labels, dyn.work, dyn.forest[0])


PATHS = {"adaptive": _run_adaptive, "solve_forest": _run_forest,
         "sampled": _run_sampled, "sampled_labels": _run_sampled_labels,
         "pallas_fused": _run_fused,
         "incremental": _run_incremental, "dynamic_delete": _run_dynamic}


@pytest.mark.parametrize("path", sorted(PATHS) + ["batched"])
def test_flat_hook_matches_chasing_reference(path):
    """Labels, parents, hook_rounds and jump_sweeps of each path equal
    those of the same composition with the chasing hooks, over the
    corpus."""
    graphs = [(name, n, e) for name, n, e in _graphs()
              if len(e) or path != "dynamic_delete"]
    if path == "batched":
        from repro.api import Solver

        def run_all():
            res = Solver.solve_batch([(e, n) for _, n, e in graphs])
            return [_out(r.labels, r.work) for r in res]
    else:
        def run_all():
            return [PATHS[path](n, e) for _, n, e in graphs]
    got = run_all()
    with _chasing() as calls:
        want = run_all()
    assert calls, "the chasing reference was never traced"
    for (name, _, _), g, w in zip(graphs, got, want):
        for key in g:
            if g[key] is None:
                assert w[key] is None, (name, key)
            else:
                np.testing.assert_array_equal(g[key], w[key],
                                              err_msg=f"{name} {key}")


# ---------------------------------------------------------------------------
# Structure: gathers under the hook scope of the lowered programs
# ---------------------------------------------------------------------------

V = 32              # vertices of the traced programs


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


def _hook_gathers(jaxpr) -> int:
    return sum(eqn.primitive.name == "gather" and
               "hook" in str(eqn.source_info.name_stack).split("/")
               for eqn in _eqns(jaxpr))


def _parent_scatters(jaxpr) -> int:
    """Scatters whose operand is a [V, 2] table: the forest's rows."""
    return sum(eqn.primitive.name.startswith("scatter") and
               eqn.invars[0].aval.shape == (V, 2)
               for eqn in _eqns(jaxpr))


def _traced(program: str):
    from repro.core.cc import _cc_jit
    from repro.core.rounds import WorkCounters
    from repro.core.sampled import _residue_scan_jit, _sample_phase_jit
    edges, true = jnp.zeros((64, 2), jnp.int32), jnp.int32(64)
    if program == "cc_adaptive":
        return _cc_jit.trace(edges, None, num_nodes=V, method="adaptive",
                             num_segments=4)
    forest = not program.endswith("_labels")
    if program.startswith("sample_phase"):
        return _sample_phase_jit.trace(edges, true, num_nodes=V, k=2,
                                       sample_rounds=2, forest=forest)
    return _residue_scan_jit.trace(
        edges, true, jnp.arange(V, dtype=jnp.int32),
        jnp.full((V, 2), -1, jnp.int32) if forest else None,
        WorkCounters.zeros(), num_nodes=V, num_segments=4, fused=False,
        interpret=True, forest=forest)


@pytest.mark.parametrize("program,hooks,per_hook", [
    ("cc_adaptive", 2, 2),      # segment scan + cleanup, plain hooks
    ("sample_phase", 2, 5),     # two unrolled forest hook rounds
    ("residue_scan", 2, 5),     # segment scan + cleanup, forest hooks
    ("sample_phase_labels", 2, 2),    # the labels-only programs that
    ("residue_scan_labels", 2, 2)])   # solve() runs: plain hooks
def test_hooks_gather_each_endpoint_once(program, hooks, per_hook):
    """A plain hook gathers ``pi[u]`` and ``pi[v]``; a forest hook adds
    ``new_pi[hi]``, ``pi[hi]`` and ``winner[hi]``. A root chase would
    add two gathers per step. Only a forest hook writes rows of the
    [V, 2] parent table."""
    jaxpr = _traced(program).jaxpr.jaxpr
    assert _hook_gathers(jaxpr) == hooks * per_hook
    assert (_parent_scatters(jaxpr) > 0) == (per_hook == 5)
