"""The state-space decode update's share of its roofline.  The kernel
(``hvd_ssm_decode``) is bound by bytes: the least it must move is every
live slot's recurrent state in every plane, read once and written once
(twice the ``state_bytes`` of the ``decode.round`` spans of the traced
window's whole rounds, ``lib/rounds.py:whole``; a true count, the
program's own: ``families/<family>.py:ssm_state_bytes_per_slot`` times
the round's ``slots``), over peak bytes/s, over the device time of the
``hvd_ssm_decode`` calls inside those rounds' programs.  None where the
family names no such kernel, the program's rounds say no ``state_bytes``
(a program from before PR 48) or no call ran."""

from benchmarks.lib import hostspans, rounds


def state_calls(ctx):
    """``(rounds, bytes, calls, ns)``: the whole rounds of the traced
    window, the ``state_bytes`` their ``decode.round`` spans name (None
    where one names none), and the count and device time of the
    ``hvd_ssm_decode`` calls inside their programs."""
    pattern = getattr(ctx.family, "SSM_DECODE_KERNEL", None)
    if pattern is None:
        return [], None, 0, 0
    found = rounds.whole_of_run(ctx)
    spans = rounds.by_round(hostspans.of_run(ctx), "decode.round")
    named = [spans[r.number].stats.get("state_bytes") for r in found]
    moved = None if None in named else sum(int(b) for b in named)
    return (found, moved) + tuple(rounds.sums_inside(ctx, found, pattern))


def read(ctx):
    found, moved, n, ns = state_calls(ctx)
    if not found or not moved or not n:
        return None
    per_slot = ctx.family.ssm_state_bytes_per_slot(ctx.config)
    ctx.log("ssm decode: %d calls in %d whole rounds, %.1f live slots a "
            "round at %d bytes of state a slot, %.3f ms a round" % (
                n, len(found), moved / per_slot / len(found), per_slot,
                ns / len(found) / 1e6))
    least_s = 2 * moved / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
