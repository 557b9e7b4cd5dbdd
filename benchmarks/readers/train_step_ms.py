"""Median device time of the train step's program: the events of the
modules line's heaviest program, median per chip, mean over chips."""

from benchmarks.lib import stats


def read(ctx):
    per_chip = []
    for dev in ctx.trace.devices:
        by_name = {}
        for e in dev.modules:
            by_name.setdefault(e.name, []).append(e.dur_ns)
        if not by_name:
            return None
        heaviest = max(by_name.values(), key=sum)
        per_chip.append(stats.median(heaviest) / 1e6)
    return stats.mean(per_chip)
