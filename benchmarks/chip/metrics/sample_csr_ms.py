"""Device self time of the ops traced under the program's
``sampled.csr`` scope (symmetrize, argsort, searchsorted of the
sampling phase), per solve of the window."""
import xspace


def read(ctx):
    return xspace.per_unit_ms(ctx, "sampled.csr")
