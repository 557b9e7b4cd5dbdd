"""Device idle time a traced decode round, by what the engine was doing:
the first chip's idle nanoseconds, each laid against the innermost
``hvd.`` span the serve loop had open (``lib/hostspans.py``), summed by
phase and divided by the rounds traced.

    .prepare   decode.reserve + decode.args + decode.dispatch
    .fetch     decode.sample_fetch + decode.finite_fetch
    .bookkeep  decode.bookkeep
    .between   everything else: the round's own glue, the serve loop's
               spans (arrivals, admission, prefills) and no span at all

The four add up to (window - busy) / rounds whatever the file's clocks;
how the sum splits depends on their offset, which ``split`` estimates.  A
phase that drew no idle time reads 0.0.  A program that records no ``hvd.`` span (one from
before PR 24) has all of its idle time under no span: ``.between``.
"""

import re

from benchmarks.lib import hostspans, xplane

MATCH_NS = 5_000_000     # a program begins within this of its dispatch

PHASES = {
    "prepare": ("decode.reserve", "decode.args", "decode.dispatch"),
    "fetch": ("decode.sample_fetch", "decode.finite_fetch"),
    "bookkeep": ("decode.bookkeep",),
}


def clock_lag_ns(trace, threads, decode_module: str):
    """``(least, most)``: how far the device's clock runs behind the
    host's in this file, as far as causality says.  A decode program
    cannot begin before the ``decode.dispatch`` span that enqueues it
    begins (the largest such lead is the least lag) nor end after the
    ``decode.sample_fetch`` that waits for it returns (the smallest
    slack is the most).  None where the trace lacks those spans."""
    rx = re.compile(decode_module)
    dispatches = hostspans.named(threads, "decode.dispatch")
    fetches = hostspans.named(threads, "decode.sample_fetch")
    least = most = None
    for m in trace.devices[0].modules:
        if not rx.search(m.name) or not dispatches:
            continue
        d = min(dispatches, key=lambda s: abs(s.start_ns - m.start_ns))
        if abs(d.start_ns - m.start_ns) > MATCH_NS:
            continue            # its own dispatch lies outside the trace
        lead = d.start_ns - m.start_ns
        least = lead if least is None else max(least, lead)
        f = next((f for f in fetches if f.start_ns >= d.start_ns), None)
        if f is not None:
            slack = f.end_ns - m.end_ns
            most = slack if most is None else min(most, slack)
    return None if least is None or most is None else (least, most)


def split(trace, threads, decode_module: str):
    """``(by_span, rounds, lag)``: idle nanoseconds of the window by
    innermost span name (``None``: no span); the rounds to divide by --
    the ``decode.round`` spans that overlap the device's window or, in a
    trace that has none, the decode program's events on the modules
    line; and the bounds of :func:`clock_lag_ns`.  The profiler's two
    clocks differ from file to file by a millisecond or two, which
    would move idle time from one phase to its neighbour, so the host's
    spans are first moved earlier by the middle of what causality
    allows: exact where a program's way to the device takes as long as
    its result's way back."""
    dev = trace.devices[0]
    lo, hi = xplane.window_of(dev)
    lag = clock_lag_ns(trace, threads, decode_module)
    main = hostspans.main_thread(threads)
    if lag is not None:
        main = hostspans.shifted(main, -(lag[0] + lag[1]) // 2)
    by_span = hostspans.idle_by_span(trace, main)
    rounds = sum(1 for s in hostspans.named(threads, "decode.round")
                 if s.start_ns < hi and s.end_ns > lo)
    if not rounds:
        rounds, _ = xplane.name_sums(dev.modules, decode_module)
    if not rounds:
        raise xplane.TraceError("no decode round in the traced window")
    return by_span, rounds, lag


def phase_ms(by_span: dict, rounds: int) -> dict:
    """The four phases, in ms a round."""
    rest = dict(by_span)
    out = {phase: sum(rest.pop(name, 0) for name in names)
           for phase, names in PHASES.items()}
    out["between"] = sum(rest.values())
    return {phase: ns / rounds / 1e6 for phase, ns in out.items()}


def describe(by_span: dict, rounds: int) -> str:
    def ms(ns):
        return "%.4f" % (ns / rounds / 1e6)

    named = [n for names in PHASES.values() for n in names]
    rest = sorted(((k, v) for k, v in by_span.items() if k not in named),
                  key=lambda kv: -kv[1])
    total = sum(by_span.values())
    outside = by_span.get(None, 0) + by_span.get("serve", 0)
    return ("round idle, ms a round over %d rounds: %s; between: %s; under "
            "the root span or none %s (%.1f%% of %s)" % (
                rounds,
                " ".join(n.split(".", 1)[1] + " " + ms(by_span.get(n, 0))
                         for n in named),
                " ".join((k or "none") + " " + ms(v) for k, v in rest)
                or "nothing",
                ms(outside), 100.0 * outside / total if total else 0.0,
                ms(total)))


def read(ctx):
    threads = hostspans.of_run(ctx)
    by_span, rounds, lag = split(ctx.trace, threads,
                                 ctx.family.DECODE_MODULE)
    phase = ctx.metric["name"].split(".", 1)[1]
    if phase == "prepare":       # the first of the four entries logs
        if not threads:
            ctx.log("round idle: no hvd. span in this trace (a program "
                    "from before PR 24): all idle time reads as between")
        if lag is not None:
            ctx.log("round idle: clock check: the device's clock runs "
                    "%.4f to %.4f ms behind the host's in this file; the "
                    "spans are moved %.4f ms earlier" % (
                        lag[0] / 1e6, lag[1] / 1e6,
                        (lag[0] + lag[1]) // 2 / 1e6))
        ctx.log(describe(by_span, rounds))
    return phase_ms(by_span, rounds)[phase]
