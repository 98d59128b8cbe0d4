"""Median length of the service's ``service.tick`` spans in the window."""
import statistics


def read(ctx):
    ticks = [ev["dur_us"] / 1e3 for ev in ctx.spans
             if ev["name"] == "service.tick"]
    return statistics.median(ticks) if ticks else None
