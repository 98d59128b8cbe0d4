"""Median host time of the degree-skew pass of Solver.open (the
program's ``ingest.skew`` span: a host bincount over both endpoint
columns), over the window's opens."""
import statistics


def read(ctx):
    times = [ev["dur_us"] / 1e3 for ev in ctx.spans
             if ev["name"] == "ingest.skew"]
    return statistics.median(times) if times else None
