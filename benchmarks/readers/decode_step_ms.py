"""Device time of the decode program over its count: the modules line's
events of the family's decode program in the traced sub-window."""

from benchmarks.lib import xplane


def read(ctx):
    n, ns = xplane.name_sums(ctx.trace.devices[0].modules,
                             ctx.family.DECODE_MODULE)
    if not n:
        return None
    return ns / n / 1e6
