"""Median host time of the edges' ``jax.device_put`` in Solver.open
(the program's ``ingest.put`` span: the call, which may return before
the copy to the device ends), over the window's opens."""
import statistics


def read(ctx):
    times = [ev["dur_us"] / 1e3 for ev in ctx.spans
             if ev["name"] == "ingest.put"]
    return statistics.median(times) if times else None
