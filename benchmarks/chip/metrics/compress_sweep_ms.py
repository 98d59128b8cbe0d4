"""Device self time of the ops traced under the program's ``compress``
scope over the window, divided by the window's ``solve.jump_sweeps``
device counter: the cost of one pointer-jump sweep, which does not move
with how many sweeps a seed's graph needs."""
import xspace


def read(ctx):
    if ctx.trace is None:
        return None
    from repro.obs import trace as obs
    sweeps = obs.tracer().counters.get("solve.jump_sweeps")
    seconds = (xspace.scope_seconds() or {}).get("compress")
    if not sweeps or not seconds:
        return None
    return seconds * 1e3 / sweeps
