"""Device self time of the ops traced under the program's ``hook``
scope (``repro.core.rounds``), per solve of the window."""
import xspace


def read(ctx):
    return xspace.per_unit_ms(ctx, "hook")
