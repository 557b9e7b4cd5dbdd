"""The readers of the serve loop's own account (PR 36):
``lib/rounds.py`` and ``readers/{round_period_ms,prefill_stall_ms,
loop_host_ms_per_round}.py``, on hand-built records of a serve loop that
runs one round ahead, on a hand-built trace of the same loop seen through
a device clock that lags, and on the records the tiny CPU cell of
``test_benchmark_rehearsal.py`` leaves in the program's recorder."""

import math
import types

import jax
import pytest

from benchmarks.families import llama_dense
from benchmarks.kinds import serve
from benchmarks.lib import hostspans, rounds, xplane
from benchmarks.readers import (loop_host_ms_per_round, prefill_stall_ms,
                                round_idle_ms, round_period_ms)
from horovod_tpu.timeline import spans
from test_benchmark_rehearsal import TINY_LLAMA, TINY_SERVE, _ctx

K = 1000                 # the hand-built loop is written in microseconds
PERIOD = 10_000 * K      # one decode round on the chip
READERS = {"round_period_ms": round_period_ms,
           "prefill_stall_ms": prefill_stall_ms,
           "loop_host_ms_per_round": loop_host_ms_per_round}


# -- a hand-built serve loop, one round ahead ----------------------------------

class Ring:
    """What a reader asks of the program's recorder."""

    def __init__(self, records):
        self._records = records

    def records(self):
        return list(self._records)


def loop(n_rounds=12, numbered=True, account=True, prefill_after=None,
         hole_after=None, hole_ns=0, warm_up=True):
    """The records of one ``serve`` call of ``n_rounds`` rounds, as the
    engine files them.  Program n runs on the chip from ``n * PERIOD +
    800`` us for one ``PERIOD``; the host dispatches round n + 1 and
    then waits for round n's fetch, which returns 30 us after program n
    ends; the last round is read by a catch-up under the root.
    ``prefill_after``: a ``serve.prefill`` of 40 us after that round's
    span (and a ``request`` that it served).  ``hole_after``: ``hole_ns``
    under the root alone after that round's span (the profiler starting,
    in a traced run).  Returns ``(records, spans_by_round)``."""
    ids = iter(range(1, 10 ** 6))
    out, by_round = [], {}
    serve_id = next(ids)
    shift = 0

    def add(name, start, end, parent, **attrs):
        rec = spans.SpanRecord(name, start * K + shift, end * K + shift,
                               next(ids), parent, attrs)
        out.append(rec)
        return rec

    if warm_up:
        out.append(spans.SpanRecord("serve", -5000 * K, -4000 * K,
                                    next(ids), None, {"requests": 2}))
    num = (lambda n: {"round": n}) if numbered else (lambda n: {})
    for n in range(n_rounds):
        p = PERIOD // K
        start = 100 if n == 0 else (n - 1) * p + 1450
        rid = next(ids)
        add("decode.reserve", start, start + 50, rid)
        add("decode.args", start + 50, start + 250, rid)
        d = add("decode.dispatch", start + 250, start + 450, rid,
                leg="serving_decode")
        end = start + 500
        if n:
            f = add("decode.sample_fetch", start + 450, n * p + 830, rid,
                    **num(n - 1))
            add("decode.bookkeep", n * p + 830, n * p + 1200, rid,
                **num(n - 1))
            by_round.setdefault(n - 1, {})["fetch"] = f
            end = n * p + 1250
        out.append(spans.SpanRecord(
            "decode.round", start * K + shift, end * K + shift, rid,
            serve_id, {"round": n, "slots": 1 + n % 4, "ahead": int(n > 0)}))
        by_round.setdefault(n, {}).update(dispatch=d, round=out[-1])
        add("serve.arrivals", end + 50, end + 60, serve_id)
        add("serve.admit", end + 70, end + 150, serve_id)
        if n == prefill_after:
            pid = next(ids)
            add("prefill.dispatch", end + 150, end + 160, pid, rid=7)
            add("prefill.sample_fetch", end + 160, end + 190, pid, rid=7)
            out.append(spans.SpanRecord(
                "serve.prefill", (end + 150) * K + shift,
                (end + 190) * K + shift, pid, serve_id,
                {"rid": 7, "prompt_len": 16, "behind": n}))
            out.append(spans.SpanRecord(
                "request", (end + 195) * K + shift, (end + 195) * K + shift,
                next(ids), serve_id,
                {"rid": 7, "admit_s": 0.25, "prefill_start_s": 0.26,
                 "first_token_s": 0.30}))
        if n == hole_after:
            shift += hole_ns
    last = n_rounds - 1
    p = PERIOD // K
    f = add("decode.sample_fetch", last * p + 1450, n_rounds * p + 830,
            serve_id, **num(last))
    add("decode.bookkeep", n_rounds * p + 830, n_rounds * p + 1200, serve_id,
        **num(last))
    by_round[last]["fetch"] = f
    serve_rec = spans.SpanRecord("serve", 0, (n_rounds * p + 1300) * K + shift,
                                 serve_id, None, {"requests": 3})
    if account:
        kept = list(out[1:] if warm_up else out)
        added = rounds._added_up(serve_rec, kept + [serve_rec])
        at = serve_rec.end_ns - 10
        out.append(spans.SpanRecord(
            "serve.account", at, at, next(ids), serve_id,
            dict(added, filed=len(kept), dropped=0,
                 prefills=int(prefill_after is not None))))
    out.append(serve_rec)
    return out, by_round


def reader_ctx(records, **more):
    logs = []
    return types.SimpleNamespace(recorder=Ring(records), log=logs.append,
                                 metric=None, **more), logs


def test_every_interval_of_a_steady_loop_is_one_round():
    records, _ = loop()
    call = rounds.newest_call(Ring(records))
    assert call.filed and call.serve.attrs == {"requests": 3}
    assert rounds.fetch_ends(call) == {
        n: ((n + 1) * PERIOD // K + 830) * K for n in range(12)}
    assert rounds.intervals(call) == [(n, PERIOD, True)
                                      for n in range(1, 12)]
    ctx, logs = reader_ctx(records)
    assert round_period_ms.read(ctx) == pytest.approx(PERIOD / 1e6)
    assert "12 rounds, 11 intervals, 11 clean (100.0%)" in logs[0]
    assert "1-1 slots 10.0000" in logs[0] and "4-4 slots" in logs[0]
    assert "the full quarter (4-4 slots) reads 10.0000 ms" in logs[0]


def test_a_prefill_inside_an_interval_makes_it_unclean():
    records, _ = loop(prefill_after=4)
    found = rounds.intervals(rounds.newest_call(Ring(records)))
    assert [n for n, _, clean in found if not clean] == [4]
    ctx, logs = reader_ctx(records)
    assert round_period_ms.read(ctx) == pytest.approx(PERIOD / 1e6)
    assert "11 intervals, 10 clean" in logs[0]


def test_a_hole_under_the_root_makes_an_interval_unclean():
    """Two seconds of the root's own time (the profiler's start): the
    interval around it is 2.01 s long and must not reach the mean."""
    records, _ = loop(hole_after=6, hole_ns=2_000_000_000)
    found = rounds.intervals(rounds.newest_call(Ring(records)))
    assert [(n, ns) for n, ns, clean in found if not clean] \
        == [(6, PERIOD + 2_000_000_000)]
    ctx, _ = reader_ctx(records)
    assert round_period_ms.read(ctx) == pytest.approx(PERIOD / 1e6)


def test_a_loop_of_nothing_but_prefills_has_no_round_to_read():
    records, _ = loop(n_rounds=2, prefill_after=1)
    ctx, _ = reader_ctx(records)
    with pytest.raises(rounds.RecordsError, match="decode round alone"):
        round_period_ms.read(ctx)


@pytest.mark.parametrize("reader", sorted(READERS))
def test_a_ring_that_lost_records_of_the_call_is_refused(reader):
    records, _ = loop(prefill_after=4)
    account = next(r for r in records if r.name == "serve.account")
    account.attrs["filed"] += 5
    account.attrs["dropped"] = 5
    ctx, _ = reader_ctx(records)
    with pytest.raises(rounds.RecordsError, match="its oldest are gone"):
        READERS[reader].read(ctx)


def test_no_serve_span_is_refused():
    with pytest.raises(rounds.RecordsError, match="no serve span"):
        rounds.newest_call(Ring([]))


@pytest.mark.parametrize("reader", sorted(READERS))
def test_a_program_without_the_account_is_read_from_its_spans(reader):
    """The parent commit under this PR's benchmark files: the same spans,
    no ``round`` on a fetch, no ``serve.account``.  Rounds are counted,
    the account is added up from parent ids, the number is the same and
    the log says what was done."""
    new, _ = loop(prefill_after=4)
    old, _ = loop(prefill_after=4, numbered=False, account=False)
    got_new = READERS[reader].read(reader_ctx(new)[0])
    ctx, logs = reader_ctx(old)
    assert READERS[reader].read(ctx) == pytest.approx(got_new)
    assert not rounds.newest_call(Ring(old)).filed
    if reader != "prefill_stall_ms":
        assert any("before PR 36" in line or "files none" in line
                   for line in logs)


def test_without_a_count_a_full_ring_of_the_call_alone_is_refused(
        monkeypatch):
    old, _ = loop(numbered=False, account=False, warm_up=False)
    monkeypatch.setattr(spans, "RECORD_RING", len(old))
    with pytest.raises(rounds.RecordsError, match="may be gone"):
        rounds.newest_call(Ring(old))
    # Something older than the call is still in the ring: it is whole.
    old, _ = loop(numbered=False, account=False, warm_up=True)
    monkeypatch.setattr(spans, "RECORD_RING", len(old))
    assert rounds.newest_call(Ring(old)).account["rounds"] == 12


def test_prefill_stall_is_the_mean_span_and_the_log_splits_it():
    records, _ = loop(prefill_after=4)
    ctx, logs = reader_ctx(records)
    assert prefill_stall_ms.read(ctx) == pytest.approx(0.040)
    assert "1 prefills, mean 0.0400 ms" in logs[0]
    assert "dispatch 0.0100 sample_fetch 0.0300" in logs[0]
    assert "0.0033 ms a round over 12 rounds" in logs[0]
    assert "took up 1 prompts and the mean is over 1 serve.prefill" in logs[1]
    assert "0 prefill_chunk spans" in logs[1] and "0 reprefill" in logs[1]
    assert logs[2].endswith("16: 1 at 0.0400")
    assert logs[3].endswith("behind a round: 1 at 0.0400")
    assert "prefill start to first token: median 40.0000 ms" in logs[4]
    assert "admission to prefill start: median 10.0000 ms" in logs[4]
    none, _ = loop()
    with pytest.raises(rounds.RecordsError, match="no serve.prefill"):
        prefill_stall_ms.read(reader_ctx(none)[0])


def test_loop_host_time_is_the_phases_own_time_a_round():
    records, _ = loop()
    ctx, logs = reader_ctx(records)
    # A round: reserve 50, args 200, dispatch 200, bookkeep 370, arrivals
    # 10, admit 80 us, and the round span's own 50: the fetch, some 9 ms
    # of waiting, is not in it.
    assert loop_host_ms_per_round.read(ctx) == pytest.approx(
        (50 + 200 + 200 + 370 + 10 + 80 + 50) / 1e3)
    assert "bookkeep 0.3700" in logs[0] and "filed by the program" in logs[0]
    assert "(+0.0000%)" in logs[0]
    acc = rounds.newest_call(Ring(records)).account
    assert sum(t["self_ns"] for t in acc["spans"].values()) \
        == acc["wall_ns"]


# -- the two clocks of a traced run, under the look-ahead ---------------------

def lagged_trace(lag_us, first=0, last=8, prefill_after=None):
    """Rounds ``first``..``last`` of :func:`loop` as a traced run shows
    them: the decode programs on a device clock that runs ``lag_us``
    behind the host's, the host's spans as ``hvd.`` events.
    ``prefill_after``: a prefill program of 1 us as that round's program
    ends (:func:`loop`'s prefill costs the chip next to nothing)."""
    _, by_round = loop()
    p = PERIOD // K
    programs = [xplane.Event("jit_spmd(%d)" % n, (n * p + 800 - lag_us) * K,
                             ((n + 1) * p + 800 - lag_us) * K)
                for n in range(first, last + 1)]
    if prefill_after is not None:
        at = programs[prefill_after - first].end_ns
        programs.append(xplane.Event("jit_prefill(7)", at, at + K))
        programs.sort(key=lambda e: e.start_ns)
    dev = xplane.DevicePlane(0, list(programs), programs)
    host = []
    for n in range(first, last + 2):
        for rec in by_round[n].values():
            if rec.name == "decode.sample_fetch" and not first <= \
                    rec.attrs["round"] <= last:
                continue
            host.append(hostspans.Span(rec.name, rec.start_ns, rec.end_ns,
                                       dict(rec.attrs)))
    host.sort(key=lambda s: (s.start_ns, -s.end_ns))
    return xplane.Trace([dev], []), [host]


def test_pairing_by_round_recovers_a_lag_where_pairing_by_time_cannot():
    """Round 0 began on an idle chip 450 us after its dispatch began; a
    fetch returns 30 us after its program ends.  Through a clock that
    lags 1.7 ms causality allows 1.25 to 1.73 ms.  Paired by time
    (``round_idle_ms.clock_lag_ns``) a program meets the dispatch of
    the round AFTER and an interval that is empty."""
    trace, threads = lagged_trace(1700)
    least, most = rounds.clock_lag_ns(trace, threads,
                                      llama_dense.DECODE_MODULE)
    assert (least, most) == (1250 * K, 1730 * K)
    old = round_idle_ms.clock_lag_ns(trace, threads,
                                     llama_dense.DECODE_MODULE)
    assert old[0] > old[1]
    # A fetch by its own ``round``; a dispatch by the span it lies under.
    _, by_round = loop()
    pairs = rounds.paired(trace, threads, llama_dense.DECODE_MODULE)
    assert sorted(pairs) == list(range(9))
    for n, (_, d, f) in pairs.items():
        assert f.stats["round"] == n and "round" not in d.stats
        assert d.start_ns == by_round[n]["dispatch"].start_ns


def test_pairing_settles_which_rounds_a_sub_window_holds():
    """A sub-window from round 3 on: the first program's own dispatch is
    in the trace, its numbering found from the fetches; no program began
    on an idle chip, so only the upper bound binds."""
    trace, threads = lagged_trace(1700, first=3, last=8)
    pairs = rounds.paired(trace, threads, llama_dense.DECODE_MODULE)
    assert [(n, f.stats["round"]) for n, (_, _, f) in pairs.items()] \
        == [(n, n) for n in range(3, 9)]
    least, most = rounds.clock_lag_ns(trace, threads,
                                      llama_dense.DECODE_MODULE)
    assert most == 1730 * K and least < 0


def test_spans_without_a_round_cannot_be_paired():
    trace, threads = lagged_trace(0)
    bare = [[hostspans.Span(s.name, s.start_ns, s.end_ns, {})
             for s in threads[0]]]
    assert rounds.paired(trace, bare, llama_dense.DECODE_MODULE) == {}
    assert rounds.clock_lag_ns(trace, bare,
                               llama_dense.DECODE_MODULE) is None


def test_on_the_device_a_prefill_lies_in_the_interval_after():
    """A prefill dispatched while round 5 is in flight runs on the chip
    after program 5: between the ends of programs 5 and 6."""
    trace, threads = lagged_trace(1700, first=3, last=8, prefill_after=5)
    pairs = rounds.paired(trace, threads, llama_dense.DECODE_MODULE)
    assert rounds.device_intervals(
        trace, pairs, llama_dense.DECODE_MODULE) == {
            n: PERIOD for n in (4, 5, 7, 8)}


def test_the_cross_check_compares_the_same_rounds_on_both_clocks(
        monkeypatch):
    """The trace holds programs 3-8, so the intervals of rounds 4-8.  A
    prefill behind round 5 makes the host's interval 5 unclean (its span
    lies there) and the device's interval 6 (it runs there): the check
    is over the three rounds clean on both clocks, each side from its
    own timestamps, not from the window's whole idle time."""
    records, _ = loop(prefill_after=5)
    trace, threads = lagged_trace(1700, first=3, last=8, prefill_after=5)
    ctx, logs = reader_ctx(records, trace=trace, family=llama_dense,
                           cell={"name": "tiny"})
    monkeypatch.setattr(hostspans, "of_run", lambda ctx: threads)
    assert round_period_ms.read(ctx) == pytest.approx(PERIOD / 1e6)
    check = next(line for line in logs if "cross-check" in line)
    assert "the 3 intervals of rounds 4-8 that hold a decode round alone " \
        "on both clocks (of 4 on the device's)" in check
    assert "mean 10.0000 ms as the host saw them" in check
    assert "on the device 10.0000 ms (the program 10.0000 + 0.0000" in check
    assert "+0.00%" in check
    edges = next(line for line in logs if "first and last" in line)
    assert "read 10.0000 and 10.0000 ms" in edges and "takes all 6" in edges
    clocks = next(line for line in logs if "the two clocks" in line)
    assert "rounds 3-8" in clocks and "50.0000 ms between the two fetches" \
        in clocks and "(+0.000%)" in clocks
    assert any("1.7300 ms behind" in line for line in logs)


def test_no_cross_check_where_the_trace_has_no_round_to_pair_by(
        monkeypatch):
    records, _ = loop()
    trace, threads = lagged_trace(0)
    bare = [[hostspans.Span(s.name, s.start_ns, s.end_ns, {})
             for s in threads[0]]]
    ctx, logs = reader_ctx(records, trace=trace, family=llama_dense,
                           cell={"name": "tiny"})
    monkeypatch.setattr(hostspans, "of_run", lambda ctx: bare)
    assert round_period_ms.read(ctx) == pytest.approx(PERIOD / 1e6)
    assert any("no cross-check" in line for line in logs)


# -- the tiny CPU cell ------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_run():
    """``kinds/serve.py:run`` on the rehearsal's tiny cell, untraced: the
    window's ``serve`` is the newest call in the program's recorder."""
    traffic = dict(TINY_SERVE, arrival="at_zero", num_requests=12)
    ctx, logs = _ctx(TINY_LLAMA, traffic, llama_dense, jax.devices()[:1])
    out = serve.run(ctx)
    assert out["failed"] == 0
    return out


@pytest.mark.parametrize("reader", sorted(READERS))
def test_each_reader_reads_the_tiny_cells_records(tiny_run, reader):
    logs = []
    ctx = types.SimpleNamespace(log=logs.append, metric=None,
                                counters=tiny_run["counters"])
    value = READERS[reader].read(ctx)
    assert math.isfinite(value) and value > 0
    assert logs and not any("before PR 36" in line for line in logs)
    call = rounds.newest_call()
    assert call.filed and call.account["rounds"] \
        == tiny_run["counters"]["decode_steps"]
