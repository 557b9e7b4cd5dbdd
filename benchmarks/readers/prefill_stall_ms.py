"""What one prefill costs the serve loop: the mean duration of the
``serve.prefill`` spans of the newest ``serve`` call, every one of them,
not only those a traced sub-window happens to hold.  A prefill is
synchronous, so its span is the rest of the decode round in flight (the
prefill queues behind it on the chip), the prefill and its pool write on
the chip, and the first token's way back to the host.  The log splits it
by child span, by prompt length and by ``behind`` (the round in flight
when it was dispatched, -1 where there was none), puts the total beside
the call's rounds, says how many prompts the loop took up (the account's
``prefills``) and what ``prefill_chunk`` and ``reprefill`` spans the mean
leaves out, and gives from the ``request`` records the time from
``prefill_start_s`` to the first token and from admission to
``prefill_start_s``: the part of a time to first token that
``queue_wait_p95_ms`` does not cover.
"""

from benchmarks.lib import rounds, stats

CHILDREN = ("prefill.dispatch", "prefill.write_kv", "prefill.write_state",
            "prefill.sample_fetch")


def ms(records) -> list:
    return [(r.end_ns - r.start_ns) / 1e6 for r in records]


def grouped(prefills, key) -> str:
    groups = {}
    for r in prefills:
        groups.setdefault(key(r), []).append(r)
    return ", ".join("%s: %d at %.4f" % (k, len(v), stats.mean(ms(v)))
                     for k, v in sorted(groups.items()))


def read(ctx):
    call = rounds.call_of_run(ctx)
    prefills = call.named("serve.prefill")
    if not prefills:
        raise rounds.RecordsError(
            "the serve call kept no serve.prefill span")
    ids = {r.id for r in prefills}
    stall = ms(prefills)
    parts = []
    for name in CHILDREN:
        kids = [r for r in call.named(name) if r.parent in ids]
        if kids:
            parts.append("%s %.4f" % (name.split(".", 1)[1],
                                      sum(ms(kids)) / len(prefills)))
    n_rounds = call.account["rounds"]
    ctx.log("prefill stall: %d prefills, mean %.4f ms, median %.4f ms, "
            "largest %.4f ms; a prefill: %s; in all %.4f ms a round over "
            "%d rounds" % (
                len(stall), stats.mean(stall), stats.median(stall),
                max(stall), " ".join(parts),
                sum(stall) / max(n_rounds, 1), n_rounds))
    # What the mean leaves out is said, so that a cell that chunks its
    # prompts or prefills again is not read as if it did neither.
    other = {name: ms(call.named(name))
             for name in ("prefill_chunk", "reprefill")}
    ctx.log("prefill stall: the loop took up %s prompts and the mean is "
            "over %d serve.prefill spans; not in it: %s" % (
                call.account.get("prefills", "an uncounted number of"),
                len(stall), ", ".join(
                    "%d %s spans, %.4f ms in all" % (len(v), name, sum(v))
                    for name, v in other.items())))
    ctx.log("prefill stall by prompt_len: " + grouped(
        prefills, lambda r: int(r.attrs["prompt_len"])))
    if all("behind" in r.attrs for r in prefills):
        ctx.log("prefill stall by whether a round was in flight: " + grouped(
            prefills, lambda r: "behind a round" if r.attrs["behind"] >= 0
            else "behind none"))
    started = [r.attrs for r in call.named("request")
               if r.attrs.get("prefill_start_s") is not None]
    if started:
        first = [(a["first_token_s"] - a["prefill_start_s"]) * 1e3
                 for a in started]
        taken_up = [(a["prefill_start_s"] - a["admit_s"]) * 1e3
                    for a in started]
        ctx.log("prefill stall: of %d requests, prefill start to first "
                "token: median %.4f ms, p95 %.4f ms; admission to prefill "
                "start: median %.4f ms, p95 %.4f ms" % (
                    len(started), stats.median(first),
                    stats.percentile(first, 95), stats.median(taken_up),
                    stats.percentile(taken_up, 95)))
    return stats.mean(stall)
