"""Collective time a step that no compute hides: on each chip the union
of the collective operations' intervals minus what other operations
cover, over the steps traced; mean over chips."""

from benchmarks.lib import xplane


def read(ctx):
    steps = ctx.counters["trace_steps"]
    totals = [xplane.collective_exposed_ns(d) for d in ctx.trace.devices]
    if not any(coll for coll, _ in totals):
        return None
    ctx.log("collective time a step: %.4f ms, exposed %.4f ms" % (
        sum(c for c, _ in totals) / len(totals) / steps / 1e6,
        sum(x for _, x in totals) / len(totals) / steps / 1e6))
    return sum(x for _, x in totals) / len(totals) / steps / 1e6
