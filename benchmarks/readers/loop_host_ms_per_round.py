"""The host's own work a decode round, from the ``serve.account`` record
the program files when ``serve`` returns: the SELF time (a span's time
less its children's) of the phases in which the serve loop computes,
summed over the call and divided by its rounds.  With the chip one round
ahead this time hides behind the device's round while it is shorter; it
is the floor of a round once the device's round shrinks.  Not in it:
``decode.sample_fetch`` (waiting for the chip), the prefill spans
(``prefill_stall_ms``) and the ``serve`` root's own time (where the
profiler's start and stop land in a traced run).  The log gives each
phase and the account's identity: every span's self time added up,
against the call's wall time.
"""

from benchmarks.lib import rounds

HOST = ("decode.round", "decode.reserve", "decode.args", "decode.dispatch",
        "decode.bookkeep", "serve.arrivals", "serve.admit", "serve.chunks")


def read(ctx):
    call = rounds.call_of_run(ctx)
    spans, n = call.account["spans"], call.account["rounds"]
    if not n:
        raise rounds.RecordsError("the serve call ran no decode round")

    def own_ms(name):
        return spans.get(name, {"self_ns": 0})["self_ns"] / n / 1e6

    per_round = {name: own_ms(name) for name in HOST}
    added, wall = sum(t["self_ns"] for t in spans.values()), \
        call.account["wall_ns"]
    ctx.log("loop host time, ms a round over %d rounds: %s; not in it: "
            "sample_fetch %.4f, the root's own %.4f; the account (%s): "
            "self times add up to %.6f s of %.6f s of wall (%+.4f%%)" % (
                n, " ".join("%s %.4f" % (k, v)
                            for k, v in per_round.items()),
                own_ms("decode.sample_fetch"), own_ms("serve"),
                "filed by the program" if call.filed
                else "added up from the ring: the program files none",
                added / 1e9, wall / 1e9, 100.0 * (added / wall - 1.0)))
    return sum(per_round.values())
