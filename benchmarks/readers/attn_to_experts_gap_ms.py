"""Device time between a layer's attention and its experts, a traced
decode round: inside the programs of the traced window's whole rounds
(``lib/rounds.py:whole``), from the end of each page-walk call
(``hvd_swa_decode`` | ``hvd_cca_decode``, the family's
``SWA_DECODE_KERNEL`` and ``CCA_DECODE_KERNEL``) to the start of the next
``hvd_moe_gmm`` call, summed over a round's layers, over the rounds.

For a model whose router reads the layer's INPUT the routing is known
before attention, so what lies in this gap is what still depends on
attention's result (the closing projection, the residual, ``norm_2``, the
gather of the routed rows) plus whatever of the router, the top-k and the
layout's sort XLA scheduled late: the time a change that fetches expert
blocks under the attention call, or hides this work there, must move."""

import bisect
import re

from benchmarks.lib import rounds, xplane


def gaps_ns(ops, attention, experts):
    """The gaps of one program's operations ``ops`` (by start): for each
    event that matches ``attention``, the nanoseconds from its end to the
    start of the next event that matches ``experts`` (a layer's first
    grouped matmul), where one follows before the next attention call."""
    out, ended = [], None
    for e in ops:
        if attention.search(e.name):
            ended = e.end_ns
        elif ended is not None and experts.search(e.name):
            out.append(max(e.start_ns - ended, 0))
            ended = None
    return out


def read(ctx):
    walks = [getattr(ctx.family, name, None)
             for name in ("SWA_DECODE_KERNEL", "CCA_DECODE_KERNEL")]
    experts = getattr(ctx.family, "MOE_GMM_KERNEL", None)
    if experts is None or not any(walks):
        return None
    attention = re.compile("|".join(w for w in walks if w))
    experts = re.compile(experts)
    found = rounds.whole_of_run(ctx)
    # One pass over the ops line: the kernels' calls inside the whole
    # rounds' programs, then a round at a time by the program they lie in.
    programs = sorted((r.program.start_ns, r.program.end_ns) for r in found)
    by_program = [[] for _ in programs]
    starts = [lo for lo, _ in programs]
    for e in xplane.ops_inside(ctx.trace.devices[0], programs):
        if attention.search(e.name) or experts.search(e.name):
            by_program[bisect.bisect_right(starts, e.start_ns) - 1].append(e)
    gaps = [g for ops in by_program for g in gaps_ns(ops, attention, experts)]
    if not gaps:
        return None
    ctx.log("attention to experts: %d gaps in %d whole rounds, %.4f ms a "
            "gap" % (len(gaps), len(found), sum(gaps) / len(gaps) / 1e6))
    return sum(gaps) / len(found) / 1e6
