"""Median host-clock time of Solver.insert(batch) to current labels, over the window."""
import statistics


def read(ctx):
    times = ctx.timings.get("insert")
    return statistics.median(times) if times else None
