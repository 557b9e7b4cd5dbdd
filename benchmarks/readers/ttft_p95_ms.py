"""95th percentile of first_token_s - arrival_s over the finished requests
admitted before the traced sub-window opened, on the engine's
arrival-faithful clock.  On some 80 requests it spreads too widely from
run to run to stand end to end under a bound (PERF.md, section 2)."""

from benchmarks.lib import stats


def read(ctx):
    ttft = ctx.counters.get("ttft_ms")
    if not ttft:
        return None
    return stats.percentile(ttft, 95)
