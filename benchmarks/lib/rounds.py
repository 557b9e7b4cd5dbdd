"""A serve call read round by round: the records the program kept of its
newest ``ServingEngine.serve`` call, the round each fetch retires, which
intervals between two fetches hold nothing but a decode round, and how
far the device's clock runs behind the host's in a traced run.

Since PR 36 the program says which round a ``decode.sample_fetch`` and
a ``decode.bookkeep`` retire (the attribute ``round``: under the serve
loop's look-ahead a round's fetch lies under the ``decode.round`` span
of the round AFTER; its ``decode.dispatch`` under its own), and files one
``serve.account`` record a call: self time by span name, and how many
records the call filed, so that a ring that has lost some of them is
refused instead of read as the whole.  A program from before PR 36 (the
parent commit under this PR's benchmark files) has the same spans
without the numbers: rounds are then counted (the loop retires them in
order, once each) and the account is added up from the records' own
parent ids.  Both say so in the log.  That second way (``_added_up``,
``Call.filed``, the counting in ``fetch_ends``, the full-ring rule in
``newest_call``) is needed for one check only, the one that merges PR
36; ROADMAP I1 has the next ``benchmark`` PR delete it with its tests.
"""

import bisect
import dataclasses
import math
import re
from typing import Dict, List, Tuple

from . import hostspans, stats, xplane

PREFILLS = ("serve.prefill", "prefill_chunk", "reprefill")
ROOT_OWN_NS = 1_000_000    # more of the root's own time: not a clean round


class RecordsError(RuntimeError):
    pass


@dataclasses.dataclass
class Call:
    """One ``serve`` call: its root span's record, every record begun
    since (the root's last), and its account: ``spans`` (``{name:
    {count, total_ns, self_ns}}``), ``wall_ns``, ``rounds``.  ``filed``
    is False where the program filed none and it was added up here."""

    serve: object
    records: list
    account: dict
    filed: bool

    def named(self, name: str) -> list:
        return [r for r in self.records if r.name == name]


def _added_up(serve, records) -> dict:
    """The account a program from before PR 36 would have filed, from
    the records' parent ids."""
    child_ns: Dict[int, int] = {}
    for r in records:
        if r.parent is not None:
            child_ns[r.parent] = child_ns.get(r.parent, 0) \
                + r.end_ns - r.start_ns
    spans: Dict[str, dict] = {}
    for r in records:
        if r.end_ns == r.start_ns:
            continue                      # a point record
        t = spans.setdefault(r.name, {"count": 0, "total_ns": 0,
                                      "self_ns": 0})
        t["count"] += 1
        t["total_ns"] += r.end_ns - r.start_ns
        t["self_ns"] += r.end_ns - r.start_ns - child_ns.get(r.id, 0)
    return {"spans": spans, "wall_ns": serve.end_ns - serve.start_ns,
            "rounds": spans.get("decode.round", {"count": 0})["count"]}


def newest_call(recorder=None) -> Call:
    """The newest ``serve`` call in the recorder's ring (the warm-up's
    lies before it).  Raises :class:`RecordsError` where there is none,
    or the ring no longer holds all of its records."""
    from horovod_tpu.timeline import spans
    if recorder is None:
        recorder = spans.recorder()
    everything = recorder.records()
    serves = [r for r in everything if r.name == "serve"]
    if not serves:
        raise RecordsError("the recorder's ring holds no serve span")
    serve = serves[-1]
    records = [r for r in everything if r.start_ns >= serve.start_ns]
    filed = next((r.attrs for r in records if r.name == "serve.account"
                  and r.parent == serve.id), None)
    if filed is not None:
        kept = sum(1 for r in records
                   if r.start_ns <= serve.end_ns and r.id != serve.id
                   and r.name != "serve.account")
        if kept < filed["filed"]:
            raise RecordsError(
                "the serve call filed %d records and the ring holds %d of "
                "them (%d were pushed out during the call): its oldest are "
                "gone" % (filed["filed"], kept, filed["dropped"]))
    elif everything[0].start_ns >= serve.start_ns and \
            len(everything) >= spans.RECORD_RING:
        # No count to hold the ring to: it is whole while something
        # older than the call is still in it, or it is not full.
        raise RecordsError(
            "the ring is full of this serve call's records (%d): its "
            "oldest may be gone" % len(everything))
    return Call(serve, records, filed or _added_up(serve, records),
                filed is not None)


def call_of_run(ctx) -> Call:
    """The call a reader is asked about: from ``ctx.recorder`` where the
    caller gives one (the tests do), else from the program's own."""
    return newest_call(getattr(ctx, "recorder", None))


# -- rounds -------------------------------------------------------------------

def fetch_ends(call: Call) -> Dict[int, int]:
    """``{round: the end of the decode.sample_fetch that retired it}``.
    Where the fetches carry no ``round`` they are counted: the loop
    retires its rounds in the order it dispatched them."""
    fetches = sorted(call.named("decode.sample_fetch"),
                     key=lambda r: r.end_ns)
    return {int(r.attrs.get("round", i)): r.end_ns
            for i, r in enumerate(fetches)}


def _inside(cover: List[xplane.Interval], lo: int, hi: int) -> int:
    """Nanoseconds of the sorted, disjoint ``cover`` inside [lo, hi)."""
    i = max(bisect.bisect_right(cover, (lo, math.inf)) - 1, 0)
    ns = 0
    while i < len(cover) and cover[i][0] < hi:
        ns += max(min(cover[i][1], hi) - max(cover[i][0], lo), 0)
        i += 1
    return ns


def intervals(call: Call) -> List[Tuple[int, int, bool]]:
    """``(round, ns, clean)`` for every round n whose fetch and the
    fetch of round n - 1 are both in the call: the time from the one's
    end to the other's.  Clean: no prefill span overlaps it and under
    ``ROOT_OWN_NS`` of it is the ``serve`` root's own time (where the
    benchmark's wrapper starts and stops the profiler, and where the
    loop skips to the next arrival), so that it holds one decode round
    as the host sees it and whatever idle time preceded it."""
    ends = fetch_ends(call)
    serve = call.serve
    children = [(r.start_ns, r.end_ns) for r in call.records
                if r.parent == serve.id and r.end_ns > r.start_ns]
    own = xplane.subtract([(serve.start_ns, serve.end_ns)], children)
    prefills = xplane.union((r.start_ns, r.end_ns) for r in call.records
                            if r.name in PREFILLS)
    out = []
    for n in sorted(ends):
        if n - 1 not in ends:
            continue
        lo, hi = ends[n - 1], ends[n]
        clean = (_inside(prefills, lo, hi) == 0
                 and _inside(own, lo, hi) < ROOT_OWN_NS)
        out.append((n, hi - lo, clean))
    return out


# -- the two clocks of a traced run ---------------------------------------------

def paired(trace, threads, decode_module: str):
    """``{round: (program, dispatch, fetch)}``: each decode program on
    the first chip's modules line with the ``decode.dispatch`` that
    enqueued it (the one under the ``decode.round`` span of that number)
    and the ``decode.sample_fetch`` that waited for it (the one whose
    ``round`` it is; either may be None at the trace's edge).  The
    programs in the trace are consecutive rounds; WHICH is settled once,
    by the numbering under which the programs' ends lie closest before
    their fetches' ends (a fetch returns as its program ends; a
    numbering off by one is off by a whole round).  Empty where the
    fetches carry no ``round``."""
    rx = re.compile(decode_module)
    programs = [m for m in trace.devices[0].modules if rx.search(m.name)]

    def by_round(name):
        return {int(s.stats["round"]): s
                for s in hostspans.named(threads, name)
                if "round" in s.stats}

    fetches = by_round("decode.sample_fetch")
    held = sorted(by_round("decode.round").items(),
                  key=lambda item: item[1].start_ns)
    starts = [span.start_ns for _, span in held]
    dispatches = {}
    for d in hostspans.named(threads, "decode.dispatch"):
        i = bisect.bisect_right(starts, d.start_ns) - 1
        if i >= 0 and d.end_ns <= held[i][1].end_ns:
            dispatches[held[i][0]] = d
    if not programs or not fetches or not dispatches:
        return {}

    misfit = {}
    for first in range(min(fetches) - len(programs) + 1, max(fetches) + 1):
        gaps = [abs(fetches[first + i].end_ns - p.end_ns)
                for i, p in enumerate(programs) if first + i in fetches]
        if gaps:
            misfit[first] = stats.median(gaps)
    first = min(misfit, key=misfit.get)
    return {first + i: (p, dispatches.get(first + i), fetches.get(first + i))
            for i, p in enumerate(programs)}


def device_intervals(trace, pairs, decode_module: str) -> Dict[int, int]:
    """``{round: ns}`` from the end of decode program n - 1 to the end of
    program n on the device's clock, for the rounds of ``pairs`` (from
    :func:`paired`) between whose two programs no other program began on
    the chip: the device's own clean intervals.  They are not the
    host's: a prefill queues behind the round in flight, so on the
    device it lies in the interval AFTER the one its span overlaps."""
    rx = re.compile(decode_module)
    others = sorted(m.start_ns for m in trace.devices[0].modules
                    if not rx.search(m.name))
    out = {}
    for n in pairs:
        if n - 1 not in pairs:
            continue
        lo, hi = pairs[n - 1][0].end_ns, pairs[n][0].end_ns
        i = bisect.bisect_left(others, lo)
        if i == len(others) or others[i] >= hi:
            out[n] = hi - lo
    return out


def clock_lag_ns(trace, threads, decode_module: str):
    """``(least, most)``: how far the device's clock runs behind the
    host's in this file, as far as causality says, or None where the
    trace lacks the numbered spans.  Program n cannot begin before
    ``decode.dispatch`` n begins (the largest such lead is the least
    lag; it binds where the program began on an idle device) nor end
    after ``decode.sample_fetch`` n returns (the smallest slack is the
    most).  ``readers/round_idle_ms.py:clock_lag_ns`` pairs by time,
    which under the look-ahead is the wrong round."""
    least = most = None
    for program, dispatch, fetch in paired(trace, threads,
                                           decode_module).values():
        if dispatch is not None:
            lead = dispatch.start_ns - program.start_ns
            least = lead if least is None else max(least, lead)
        if fetch is not None:
            slack = fetch.end_ns - program.end_ns
            most = slack if most is None else min(most, slack)
    return None if least is None or most is None else (least, most)
