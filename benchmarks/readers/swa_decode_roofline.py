"""The window layers' decode walk's share of its roofline.  The kernel is
bound by bytes: the least it must move is the cached keys and values of
every token INSIDE A SLOT'S WINDOW, in every window layer, for every
whole round of the traced window (the ``window_tokens`` of the rounds'
``decode.round`` spans -- the sum over a round's slots of ``min(length +
1, window)`` -- times the family's window layers times its bytes a token
a layer), over peak bytes/s, over the device time of the
``hvd_swa_decode`` calls inside those rounds' programs."""

from benchmarks.lib import hostspans, rounds


def window_tokens(ctx, found):
    """The ``window_tokens`` the whole rounds ``found`` filed, summed; 0
    where the program files no such attribute."""
    spans = rounds.by_round(hostspans.of_run(ctx), "decode.round")
    return sum(int(spans[r.number].stats.get("window_tokens", 0))
               for r in found if r.number in spans)


def read(ctx):
    pattern = getattr(ctx.family, "SWA_DECODE_KERNEL", None)
    if pattern is None:
        return None
    found = rounds.whole_of_run(ctx)
    tokens = window_tokens(ctx, found)
    n, ns = rounds.sums_inside(ctx, found, pattern)
    if not tokens or not n:
        return None
    least_s = (tokens * ctx.family.window_layers(ctx.config)
               * ctx.family.kv_row_bytes(ctx.config)
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9)
