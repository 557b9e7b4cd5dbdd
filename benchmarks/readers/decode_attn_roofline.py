"""The split-KV decode kernel's share of its roofline.  The kernel is
bound by bytes: the least it must move is the K and V of the live tokens
of every traced round (the family's bytes a token times the live tokens
the benchmark's wrapper counted), over peak bytes/s, over the kernel's
device time."""

from benchmarks.lib import xplane


def read(ctx):
    live = ctx.counters.get("traced_live_tokens")
    dev = ctx.trace.devices[0]
    n, ns = xplane.name_sums(
        xplane.ops_within(dev, ctx.family.DECODE_MODULE),
        xplane.MOSAIC_KERNEL)
    if not live or not n:
        return None
    least_s = (live * ctx.family.kv_bytes_per_token(ctx.config)
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9)
