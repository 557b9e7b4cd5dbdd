"""Device time of the grouped expert matmul a traced decode round: the
``hvd_moe_gmm`` calls inside the decode program over the program's
count on the modules line."""

from benchmarks.lib import xplane
from benchmarks.readers import moe_gmm_roofline


def read(ctx):
    n, ns = moe_gmm_roofline.gmm_calls(ctx)
    rounds, _ = xplane.name_sums(ctx.trace.devices[0].modules,
                                 ctx.family.DECODE_MODULE)
    if not n or not rounds:
        return None
    return ns / rounds / 1e6
