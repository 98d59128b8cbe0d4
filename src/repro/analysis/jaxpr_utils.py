"""Jaxpr graph plumbing shared by every checker pass.

* ``trace(entry, bucket)`` closes a ``TraceEntry`` to a jaxpr at a
  symbolic shape bucket (``jax.make_jaxpr`` over ShapeDtypeStructs —
  nothing is allocated or executed). A trace that raises because the
  program tried to materialize a tracer on the host (``.item()``,
  ``np.asarray`` on a traced value, a Python branch on a traced bool)
  is itself a *transfer-freedom violation*, so the failure is captured
  as data (``TraceFailure``) rather than propagated.

* ``AbstractInterpreter`` is a tiny fixed-point abstract interpreter
  over jaxpr graphs: passes subclass it with a value lattice (``top`` /
  ``join`` / ``from_literal``) and per-primitive transfer rules, and it
  handles the structural recursion — ``jit`` call bodies, ``scan`` /
  ``while`` loop bodies (iterated to a join fixed point, widening to
  TOP on non-convergence so loop-carried values never produce phantom
  findings), ``cond`` branches, and custom-derivative call wrappers.
  ``pallas_call`` bodies are deliberately opaque (outputs = TOP): the
  kernels have their own AST-level lint (``repro.analysis.astlint``)
  and their internals follow ref-kernel parity tests, not jaxpr rules.

* ``eqn_site(eqn)`` resolves an equation's source provenance to a
  repo-relative ``file:line`` anchor (first traceback frame under
  ``src/repro``), which findings and suppression pragmas hang off.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Optional

import jax
from jax.core import DropVar
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

REPO_SRC_MARKER = "repro"
_LOOP_FIXPOINT_ITERS = 4


@dataclasses.dataclass
class TracedEntry:
    entry: Any                     # the api.registry.TraceEntry
    bucket: tuple                  # (num_nodes, num_edges)
    jaxpr: Optional[Any]           # ClosedJaxpr on success
    arg_info: list                 # VarInfo per flat invar
    failure: Optional["TraceFailure"] = None

    @property
    def name(self) -> str:
        return self.entry.name


@dataclasses.dataclass
class TraceFailure:
    exc_type: str
    message: str


def trace(entry, bucket: tuple) -> TracedEntry:
    """Close ``entry`` to a jaxpr at ``bucket`` = (V, E)."""
    v, e = bucket
    fn, args, info = entry.build(v, e)
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as err:  # noqa: BLE001 — the failure IS the datum
        return TracedEntry(entry, bucket, None, info,
                           TraceFailure(type(err).__name__, str(err)))
    return TracedEntry(entry, bucket, closed, info)


# ---------------------------------------------------------------------------
# Source provenance
# ---------------------------------------------------------------------------

def eqn_site(eqn) -> tuple[Optional[str], Optional[int]]:
    """(repo-relative file, line) for an equation, via its traceback's
    innermost frame under ``src/repro`` (library internals and jax
    frames are skipped). Best-effort: (None, None) when provenance is
    unavailable (e.g. synthesized equations)."""
    try:
        from jax._src import source_info_util
        frames = list(source_info_util.user_frames(eqn.source_info))
        candidates = frames or [
            source_info_util.raw_frame_to_frame(f)
            for f in (eqn.source_info.traceback.frames
                      if eqn.source_info.traceback else [])]
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return None, None
    for frame in candidates:
        name = getattr(frame, "file_name", "").replace("\\", "/")
        idx = name.rfind("/repro/")
        if idx >= 0:
            return ("src" + name[idx:],
                    int(getattr(frame, "start_line", 0)) or None)
    return None, None


def subjaxpr_params(eqn) -> list:
    """Every ClosedJaxpr/Jaxpr hiding in an equation's params."""
    out = []
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else [val]
        for v in vals:
            if isinstance(v, (ClosedJaxpr, Jaxpr)):
                out.append(v)
    return out


def _as_closed(j):
    if isinstance(j, ClosedJaxpr):
        return j.jaxpr, list(j.consts)
    return j, []


# ---------------------------------------------------------------------------
# The abstract interpreter
# ---------------------------------------------------------------------------

class AbstractInterpreter:
    """Fixed-point abstract interpretation over a closed jaxpr.

    Subclasses define the value lattice — ``top()``, ``join(a, b)``,
    ``from_literal(val, aval)``, ``const_value(const)`` — and
    ``rule(eqn, in_vals) -> list[out_vals]`` for primitive transfer.
    ``visit(eqn, in_vals, out_vals)`` is the finding hook, called for
    every equation INCLUDING inside loop bodies (idempotent findings
    expected — callers dedupe by key).
    """

    def top(self):
        raise NotImplementedError

    def join(self, a, b):
        raise NotImplementedError

    def from_literal(self, val, aval):
        return self.top()

    def const_value(self, const):
        return self.top()

    def rule(self, eqn, in_vals) -> list:
        return [self.top() for _ in eqn.outvars]

    def visit(self, eqn, in_vals, out_vals) -> None:
        pass

    # -- driver ------------------------------------------------------------

    def run(self, closed_jaxpr, in_vals: list) -> list:
        jaxpr, consts = _as_closed(closed_jaxpr)
        env: dict = {}
        for var, const in zip(jaxpr.constvars, consts):
            env[var] = self.const_value(const)
        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = val
        self._eval_eqns(jaxpr, env)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _read(self, env, atom):
        if isinstance(atom, Literal):
            return self.from_literal(atom.val, atom.aval)
        return env.get(atom, self.top())

    def _eval_eqns(self, jaxpr, env) -> None:
        for eqn in jaxpr.eqns:
            in_vals = [self._read(env, a) for a in eqn.invars]
            out_vals = self._dispatch(eqn, in_vals)
            self.visit(eqn, in_vals, out_vals)
            for var, val in zip(eqn.outvars, out_vals):
                if not isinstance(var, DropVar):
                    env[var] = val

    # -- structural primitives ---------------------------------------------

    def _dispatch(self, eqn, in_vals) -> list:
        prim = eqn.primitive.name
        if prim in ("jit", "closed_call", "remat2", "custom_jvp_call",
                    "custom_vjp_call"):
            subs = subjaxpr_params(eqn)
            if subs:
                body = subs[0]
                n = len(_as_closed(body)[0].invars)
                return self.run(body, (in_vals + [self.top()] * n)[:n])
            return [self.top() for _ in eqn.outvars]
        if prim == "cond":
            branches = eqn.params.get("branches", ())
            operands = in_vals[1:]            # drop the predicate index
            outs = None
            for br in branches:
                n = len(_as_closed(br)[0].invars)
                res = self.run(br, (operands + [self.top()] * n)[:n])
                outs = res if outs is None else [
                    self.join(a, b) for a, b in zip(outs, res)]
            return outs if outs is not None \
                else [self.top() for _ in eqn.outvars]
        if prim == "while":
            return self._while(eqn, in_vals)
        if prim == "scan":
            return self._scan(eqn, in_vals)
        if prim == "pallas_call":
            # kernels are audited by the AST lint, not jaxpr rules
            return [self.top() for _ in eqn.outvars]
        return self.rule(eqn, in_vals)

    def _while(self, eqn, in_vals) -> list:
        body = eqn.params["body_jaxpr"]
        cond = eqn.params["cond_jaxpr"]
        bn = eqn.params.get("body_nconsts", 0)
        cn = eqn.params.get("cond_nconsts", 0)
        body_consts = in_vals[cn:cn + bn]
        carry = list(in_vals[cn + bn:])
        for _ in range(_LOOP_FIXPOINT_ITERS):
            nxt = self.run(body, body_consts + carry)
            joined = [self.join(a, b) for a, b in zip(carry, nxt)]
            if joined == carry:
                break
            carry = joined
        else:
            carry = [self.top() for _ in carry]
            self.run(body, body_consts + carry)   # visit at the widened env
        self.run(cond, in_vals[:cn] + carry)
        return carry

    def _scan(self, eqn, in_vals) -> list:
        body = eqn.params["jaxpr"]
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        consts = in_vals[:nc]
        carry = list(in_vals[nc:nc + ncar])
        xs = in_vals[nc + ncar:]             # per-step slice ~ whole array
        ys = None
        for _ in range(_LOOP_FIXPOINT_ITERS):
            outs = self.run(body, consts + carry + xs)
            new_carry = [self.join(a, b)
                         for a, b in zip(carry, outs[:ncar])]
            ys = outs[ncar:] if ys is None else [
                self.join(a, b) for a, b in zip(ys, outs[ncar:])]
            if new_carry == carry:
                break
            carry = new_carry
        else:
            carry = [self.top() for _ in carry]
            outs = self.run(body, consts + carry + xs)
            ys = outs[ncar:]
        return carry + list(ys or [])


def walk_eqns(closed_jaxpr):
    """Yield every equation, recursing into all sub-jaxprs (loop
    bodies, branches, called jaxprs — including pallas kernels)."""
    jaxpr, _ = _as_closed(closed_jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in subjaxpr_params(eqn):
            yield from walk_eqns(sub)


def repo_root() -> Path:
    """The repository root (…/src/repro/analysis → three up)."""
    return Path(__file__).resolve().parents[3]
