"""The prefill programs' share of the chip's busy time: operations that
ran inside an event of the family's prefill program on the modules line,
over the busy union."""

from benchmarks.lib import xplane


def read(ctx):
    import re
    dev = ctx.trace.devices[0]
    rx = re.compile(ctx.family.PREFILL_MODULE)
    prefill = xplane.spans(e for e in dev.modules if rx.search(e.name))
    if not prefill:
        return None
    ops = xplane.union(xplane.spans(dev.ops))
    busy = xplane.length(ops)
    inside = busy - xplane.length(xplane.subtract(ops, prefill))
    return 100.0 * inside / busy
