"""The window's solves against the HBM roofline: the least bytes of one
labelling (every edge read once, every label written once) times the
solves, read at the chip's peak HBM bandwidth, over the device-busy
time of the window."""
import roofline


def read(ctx):
    if ctx.trace is None or ctx.driver != "static" or not ctx.units:
        return None
    least = roofline.least_solve_bytes(ctx.num_nodes, ctx.num_edges)
    at_peak_s = ctx.units * least / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * at_peak_s / ctx.trace["busy_s"]
