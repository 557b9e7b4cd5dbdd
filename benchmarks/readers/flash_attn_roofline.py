"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the attention of the traced steps (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, both from
shapes by the family's count) over the kernels' device time."""

from benchmarks.lib import xplane


def read(ctx):
    c = ctx.counters
    cost = ctx.family.flash_attention_cost(
        ctx.config, c["sequences_per_chip"], c["seq_len"])
    t_flops = cost["flops"] / ctx.peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / ctx.peaks["hbm_bytes_per_s"]
    shares = []
    for dev in ctx.trace.devices:
        n, ns = xplane.name_sums(dev.ops, xplane.MOSAIC_KERNEL)
        if not n:
            return None
        shares.append(100.0 * max(t_flops, t_bytes) * c["trace_steps"]
                      / (ns / 1e9))
    ctx.log("flash attention a step: bound by %s (%.4f ms by operations, "
            "%.4f ms by bytes)" % ("bytes" if t_bytes > t_flops
                                   else "operations",
                                   t_flops * 1e3, t_bytes * 1e3))
    return sum(shares) / len(shares)
