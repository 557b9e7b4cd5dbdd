"""The chunk-pooled decode walk's share of its roofline.  The kernel
(``hvd_eva_decode``) is bound by bytes: the least it must move is every
row a live slot ATTENDS, the exact rows of its aligned window and the
pooled rows of the windows before, once in every layer
(``attended_rows`` of the ``decode.round`` spans of the traced window's
whole rounds, ``lib/rounds.py:whole``; a true count, the program's own,
times ``num_hidden_layers`` and ``families/<family>.py:
kv_bytes_per_row``), over peak bytes/s, over the device time of the
``hvd_eva_decode`` calls inside those rounds' programs.  None where the
family names no such kernel, the program's rounds say no
``attended_rows`` (a program from before PR 51) or no call ran."""

from benchmarks.lib import hostspans, rounds


def walk_calls(ctx):
    """``(rounds, said, calls, ns)``: the whole rounds of the traced
    window, what their ``decode.round`` spans say (a dict a round), and
    the count and device time of the ``hvd_eva_decode`` calls inside
    their programs."""
    pattern = getattr(ctx.family, "EVA_DECODE_KERNEL", None)
    if pattern is None:
        return [], [], 0, 0
    found = rounds.whole_of_run(ctx)
    spans = rounds.by_round(hostspans.of_run(ctx), "decode.round")
    return (found, [spans[r.number].stats for r in found]) + tuple(
        rounds.sums_inside(ctx, found, pattern))


def read(ctx):
    found, said, n, ns = walk_calls(ctx)

    def total(name):
        return sum(int(stats.get(name, 0)) for stats in said)

    rows = total("attended_rows")
    if not found or not n or not rows \
            or any("attended_rows" not in stats for stats in said):
        return None
    per_row = (ctx.config["num_hidden_layers"]
               * ctx.family.kv_bytes_per_row(ctx.config))
    pooled = total("pooled_rows")
    ctx.log("eva decode: %d calls in %d whole rounds, %.1f attended rows a "
            "round a layer (%.1f of them pooled) at %d bytes a row over the "
            "layers, %.3f ms a round; %d chunks pooled and %d window "
            "crossings in the window" % (
                n, len(found), rows / len(found), pooled / len(found),
                per_row, ns / len(found) / 1e6, total("chunks_pooled"),
                total("window_crossings")))
    least_s = rows * per_row / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
