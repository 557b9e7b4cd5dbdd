"""The share of the cache's window group that slots hold: the mean, over
the traced window's whole rounds, of the ``window_pages_held`` attribute
of the round's ``decode.round`` span (pages of a window plane that a
slot's ring holds as the round is dispatched) over the family's
``window_pages`` (every slot's ring at its fullest, ``ceil(window / page)
+ 1`` pages).  A ring grows page by page: at a window of 128 a request
fills it within its prompt and the share is that of the live slots; at
4,096 a short request never fills it, and the share says what sizing the
group by the traffic rather than by every slot's full ring would free."""

from benchmarks.lib import hostspans, rounds


def read(ctx):
    pages = getattr(ctx.family, "window_pages", None)
    if pages is None:
        return None
    spans = rounds.by_round(hostspans.of_run(ctx), "decode.round")
    held = [int(spans[r.number].stats["window_pages_held"])
            for r in rounds.whole_of_run(ctx)
            if r.number in spans
            and "window_pages_held" in spans[r.number].stats]
    if not held:
        return None
    return 100.0 * sum(held) / len(held) / pages(ctx.config)
