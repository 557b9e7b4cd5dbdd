"""95th percentile of admit_s - arrival_s over the finished requests, on
the engine's arrival-faithful clock."""

from benchmarks.lib import stats


def read(ctx):
    waits = ctx.counters.get("queue_wait_ms")
    if not waits:
        return None
    return stats.percentile(waits, 95)
