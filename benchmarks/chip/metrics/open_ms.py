"""Median host-clock time of Solver.open(graph) to its edges on the device, over the window."""
import statistics


def read(ctx):
    times = ctx.timings.get("open")
    return statistics.median(times) if times else None
