"""The compressed-convolutional-attention decode kernel's share of its
roofline.  The kernel is bound by bytes: the least it must move is the
cached keys and values of every live token of every traced round (the
family's bytes a token over all layers times the live tokens the
benchmark's wrapper counted), over peak bytes/s, over the device time of
the ``hvd_cca_decode`` calls inside the decode program."""

from benchmarks.lib import xplane


def read(ctx):
    pattern = getattr(ctx.family, "CCA_DECODE_KERNEL", None)
    live = ctx.counters.get("traced_live_tokens")
    if pattern is None or not live:
        return None
    n, ns = xplane.name_sums(
        xplane.ops_within(ctx.trace.devices[0], ctx.family.DECODE_MODULE),
        pattern)
    if not n:
        return None
    least_s = (live * ctx.family.kv_bytes_per_token(ctx.config)
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9)
