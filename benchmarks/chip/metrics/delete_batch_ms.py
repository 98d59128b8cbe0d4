"""Median host-clock time of Solver.delete(batch) to current labels, over the window."""
import statistics


def read(ctx):
    times = ctx.timings.get("delete")
    return statistics.median(times) if times else None
