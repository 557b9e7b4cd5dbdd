"""The banded prefill kernel's share of its roofline: for every
``hvd_flash_swa_fwd`` call inside a prefill program of the traced window,
the least time the chip could take for that window layer's attention
over that prompt (the larger of the band's operations over peak FLOP/s
and of the rows it reads and writes once over peak bytes/s, by the
family's count; the prompt's length is the third dim of the call's
result, as the ops line names it), summed, over the calls' device
time."""

import re

from benchmarks.lib import xplane

_RESULT = re.compile(r" = \w+\[(\d+),(\d+),(\d+),(\d+)\]")


def read(ctx):
    pattern = getattr(ctx.family, "SWA_PREFILL_KERNEL", None)
    if pattern is None:
        return None
    rx = re.compile(pattern)
    least_s, ns = 0.0, 0
    for e in xplane.ops_within(ctx.trace.devices[0],
                               ctx.family.PREFILL_MODULE):
        shape = _RESULT.search(e.name) if rx.search(e.name) else None
        if shape is None:
            continue
        cost = ctx.family.swa_prefill_cost(
            ctx.config, int(shape.group(1)) * int(shape.group(3)))
        least_s += max(cost["flops"] / ctx.peaks["bf16_flops_per_s"],
                       cost["bytes"] / ctx.peaks["hbm_bytes_per_s"])
        ns += e.end_ns - e.start_ns
    if not ns:
        return None
    return 100.0 * least_s / (ns / 1e9)
