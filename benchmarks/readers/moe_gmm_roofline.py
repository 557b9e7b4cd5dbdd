"""The grouped expert matmul's share of its roofline inside the decode
program.  In a decode round the kernel is bound by bytes: the least it
must move is the three matrices of every expert a live slot chose (the
traced rounds' ``experts_touched`` times the family's bytes an expert: a
true count, read from the program's spans), over peak bytes/s, over the
device time of the ``hvd_moe_gmm`` calls."""

from benchmarks.lib import xplane
from benchmarks.readers import moe_experts_touched_pct


def gmm_calls(ctx):
    """``(count, ns)`` of the ``hvd_moe_gmm`` calls inside the decode
    program, or ``(0, 0)`` for a family that names no such kernel."""
    pattern = getattr(ctx.family, "MOE_GMM_KERNEL", None)
    if pattern is None:
        return 0, 0
    return xplane.name_sums(
        xplane.ops_within(ctx.trace.devices[0], ctx.family.DECODE_MODULE),
        pattern)


def read(ctx):
    n, ns = gmm_calls(ctx)
    experts = sum(moe_experts_touched_pct.touched(ctx))
    if not n or not experts:
        return None
    least_s = (experts * ctx.family.expert_bytes(ctx.config)
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9)
