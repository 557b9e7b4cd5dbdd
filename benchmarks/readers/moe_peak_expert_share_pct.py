"""How unevenly a traced decode round loads its experts: the mean, over
the traced rounds, of the ``peak_expert_rows`` attribute of the program's
``decode.bookkeep`` span (the most rows ONE expert took in the round, the
largest over the routed layers) over the round's live slots (the
``slots`` attribute of the ``decode.round`` span around it).  Top 1 of 16
routed evenly reads 11-13 with 96 slots (the fullest of 16 bins of 96
draws); one hot expert reads towards 100, and is one long run of row
tiles on one weight block."""

from benchmarks.lib import hostspans


def shares(ctx):
    """``peak_expert_rows / slots`` of each traced round that files both;
    empty where the program files no such attribute."""
    threads = hostspans.of_run(ctx)
    rounds = hostspans.named(threads, "decode.round")
    out = []
    for span in hostspans.named(threads, "decode.bookkeep"):
        peak = span.stats.get("peak_expert_rows")
        around = [r for r in rounds
                  if r.start_ns <= span.start_ns and span.end_ns <= r.end_ns]
        if peak is None or not around:
            continue
        slots = float(around[-1].stats.get("slots", 0))
        if slots > 0:
            out.append(float(peak) / slots)
    return out


def read(ctx):
    rounds = shares(ctx)
    if not rounds:
        return None
    return 100.0 * sum(rounds) / len(rounds)
