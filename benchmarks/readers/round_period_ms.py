"""The decode round as the serve loop sees it, over EVERY round of the
call: the mean time from the end of one round's ``decode.sample_fetch``
to the end of the next round's, over the intervals that hold nothing but
a decode round (``lib/rounds.py:intervals``: no prefill span inside, and
under a millisecond of the ``serve`` root's own time, which is where the
profiler's start and stop and the skip over idle arrivals land).  With
the chip one round ahead that is the chip's decode round plus whatever
idle time preceded it.  In the offline cells every request is queued at
t = 0 in one order, so the call is the same rounds on both sides of any
change: unlike ``decode_step_ms``, whose 100 traced rounds lie wherever
two thirds of the window's seconds fall, this compares like with like.

The mean weighs in the drain, where a round is cheaper; the log gives
each quarter of the rounds by ``slots``, and the full quarter is the
number to predict a round's gain against.

Read from the recorder's ring (``program_span``), not from the trace.
The trace of a traced run is only its cross-check: over the rounds
whose interval holds a decode round alone on BOTH clocks (paired by
``round``; ``lib/rounds.py:device_intervals``), the host's mean beside
the device's own, from the end of program n - 1 to the end of program
n: the decode program's device time plus the idle time before it.
"""

from benchmarks.lib import hostspans, rounds, stats


def by_slots(call, clean):
    """``[(fewest slots, most slots, mean clean interval in ms)]`` for
    each quarter of the rounds, ordered by the ``slots`` of the round's
    ``decode.round``: the drain first, the full batch last."""
    slots = {int(r.attrs["round"]): int(r.attrs["slots"])
             for r in call.named("decode.round")}
    ordered = sorted((slots[n], ns) for n, ns in clean if n in slots)
    out = []
    for q in range(4):
        part = ordered[len(ordered) * q // 4:len(ordered) * (q + 1) // 4]
        if part:
            out.append((part[0][0], part[-1][0],
                        stats.mean(ns for _, ns in part) / 1e6))
    return out


def cross_check(ctx, clean) -> None:
    """Log the host's reading beside the device's over the same rounds,
    how the two clocks counted the traced stretch, and the bounds of
    their lag: all paired by ``round``."""
    threads = hostspans.of_run(ctx)
    pairs = rounds.paired(ctx.trace, threads, ctx.family.DECODE_MODULE)
    if not pairs:
        ctx.log("round period: no cross-check: the trace's fetches carry "
                "no round to pair its decode programs by")
        return
    seen = dict(clean)
    device = rounds.device_intervals(ctx.trace, pairs,
                                     ctx.family.DECODE_MODULE)
    both = sorted(n for n in device if n in seen)
    if both:
        host_ms = stats.mean(seen[n] for n in both) / 1e6
        device_ms = stats.mean(device[n] for n in both) / 1e6
        run_ms = stats.mean(pairs[n][0].dur_ns
                            for n in both) / 1e6
        ctx.log("round period: cross-check over the %d intervals of rounds "
                "%d-%d that hold a decode round alone on both clocks (of "
                "%d on the device's): mean %.4f ms as the host saw them; "
                "from program to program on the device %.4f ms (the "
                "program %.4f + %.4f before it): the host's reading is "
                "%+.2f%% of it" % (
                    len(both), both[0], both[-1], len(device), host_ms,
                    device_ms, run_ms, device_ms - run_ms,
                    100.0 * (host_ms / device_ms - 1.0)))
    else:
        ctx.log("round period: no cross-check: of the %d intervals the "
                "trace holds both programs of, none is a decode round "
                "alone on both clocks" % (len(pairs) - 1))
    # ``decode_step_ms`` is the mean over EVERY decode program of the
    # window, those its edges cut included (on the chip: the last one).
    programs = [pairs[n][0] for n in sorted(pairs)]
    ctx.log("round period: the window's first and last decode programs "
            "read %.4f and %.4f ms in the trace, the %d between them "
            "%.4f ms in the mean: decode_step_ms takes all %d" % (
                programs[0].dur_ns / 1e6, programs[-1].dur_ns / 1e6,
                len(programs) - 2,
                stats.mean(p.dur_ns for p in programs[1:-1] or programs)
                / 1e6, len(programs)))
    fetched = [n for n in sorted(pairs) if pairs[n][2] is not None]
    if len(fetched) > 1:
        first, last = pairs[fetched[0]], pairs[fetched[-1]]
        host_ns = last[2].end_ns - first[2].end_ns
        device_ns = last[0].end_ns - first[0].end_ns
        ctx.log("round period: the two clocks over rounds %d-%d, clean or "
                "not: %.4f ms between the two fetches' ends, %.4f ms "
                "between the two programs' ends (%+.3f%%)" % (
                    fetched[0], fetched[-1], host_ns / 1e6, device_ns / 1e6,
                    100.0 * (host_ns / device_ns - 1.0)))
    lag = rounds.clock_lag_ns(ctx.trace, threads, ctx.family.DECODE_MODULE)
    if lag is not None:
        ctx.log("round period: clock check by round: the device's clock "
                "runs %.4f to %.4f ms behind the host's in this file" % (
                    lag[0] / 1e6, lag[1] / 1e6))


def read(ctx):
    call = rounds.call_of_run(ctx)
    found = rounds.intervals(call)
    clean = [(n, ns) for n, ns, ok in found if ok]
    if not clean:
        raise rounds.RecordsError(
            "no interval between two fetches of the serve call holds a "
            "decode round alone (%d intervals)" % len(found))
    if not call.filed:
        ctx.log("round period: the program files no serve.account and no "
                "round on its fetches (it is from before PR 36): rounds "
                "are counted in the order they were fetched")
    values = [ns for _, ns in clean]
    quarters = by_slots(call, clean)
    ctx.log("round period: %d rounds, %d intervals, %d clean (%.1f%%): "
            "mean %.4f ms, median %.4f ms; by quarter of the rounds: %s; "
            "the full quarter (%d-%d slots) reads %.4f ms: predict a "
            "round's gain against that, the mean weighs in the drain" % (
                call.account["rounds"], len(found), len(clean),
                100.0 * len(clean) / len(found),
                stats.mean(values) / 1e6, stats.median(values) / 1e6,
                "; ".join("%d-%d slots %.4f" % q for q in quarters),
                *quarters[-1]))
    if getattr(ctx, "trace", None) is not None:
        cross_check(ctx, clean)
    return stats.mean(values) / 1e6
