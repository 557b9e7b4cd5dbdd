"""The readers of the program's own spans (PR 24): ``lib/hostspans.py``
and ``readers/{round_idle_ms,token_gap_p99_ms,train_dispatch_ms}.py``, on
hand-built traces and records and on two small traces recorded on the
v5e with the spans in them (``data/*.spans.*``: four decode rounds of
``mistral_7b_offline``, two steps of ``bert_large_dp1``;
``benchmarks/tools/record.py``)."""

import gzip
import json
import os
import shutil
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.families import bert, llama_dense
from benchmarks.lib import hostspans, peaks, stats, xplane
from benchmarks.readers import (round_idle_ms, token_gap_p99_ms,
                                train_dispatch_ms)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DECODE = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
US = 1000          # the hand-built traces are written in microseconds


# -- hand-built traces ------------------------------------------------------

def xspace(tmp_path, device_ops, modules, host, name="t.xplane.pb"):
    """An xplane file of one TPU plane (``device_ops`` and ``modules``:
    ``(name, start_us, end_us)``) and one host thread (``host``:
    ``(name, start_us, end_us[, stats])``)."""
    from jax.profiler import ProfileData

    def plane(plane_name, lines):
        ids, stat_ids, out = {}, {}, []
        for line_name, events in lines:
            evs = []
            for ev in events:
                label, start, end = ev[:3]
                mid = ids.setdefault(label, len(ids) + 1)
                st = "".join(
                    "stats { metadata_id: %d %s } " % (
                        stat_ids.setdefault(k, len(stat_ids) + 1),
                        ("int64_value: %d" % v) if isinstance(v, int)
                        else ('str_value: "%s"' % v))
                    for k, v in (ev[3] if len(ev) > 3 else {}).items())
                evs.append("events { metadata_id: %d offset_ps: %d "
                           "duration_ps: %d %s}" % (
                               mid, start * 10 ** 6,
                               (end - start) * 10 ** 6, st))
            out.append('lines { name: "%s" %s }' % (line_name,
                                                    " ".join(evs)))
        meta = "".join('event_metadata { key: %d value { id: %d name: "%s" '
                       '} } ' % (i, i, label.replace('"', '\\"'))
                       for label, i in ids.items())
        smeta = "".join('stat_metadata { key: %d value { id: %d name: "%s" '
                        '} } ' % (i, i, k) for k, i in stat_ids.items())
        return 'planes { name: "%s" %s %s %s }' % (
            plane_name, meta, smeta, " ".join(out))

    text = plane("/device:TPU:0", [("XLA Ops", device_ops),
                                   ("XLA Modules", modules)])
    text += plane("/host:CPU", [("python", host)])
    path = tmp_path / name
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def two_rounds(tmp_path, with_spans=True):
    """Two decode rounds, in microseconds.  The device is busy 100-1000
    and 2000-2900 (and for a sliver 1100-1110: the finite screen's
    program); the host's spans cover what lies between.  The clocks
    agree: a program begins 40 us after its dispatch began at the
    soonest and a fetch returns 40 us after its program ended, so
    causality allows a lag of -40 to 40 us and the readers take 0."""
    ops = [(DECODE, 100, 1000), ("%reduce.2 = f32[] reduce()", 1100, 1110),
           (DECODE, 2000, 2900)]
    modules = [("jit_spmd(1)", 100, 1000), ("jit__reduce_sum(2)", 1100, 1110),
               ("jit_spmd(1)", 2000, 2900)]
    host = [("bench.decode_once", 0, 1500), ("bench.decode_once", 1600, 3300)]
    if with_spans:
        host += [
            ("hvd.decode.round", 10, 1490, {"round": 0, "live_tokens": 40}),
            ("hvd.decode.args", 20, 60), ("hvd.decode.dispatch", 60, 90),
            ("hvd.decode.sample_fetch", 95, 1040),
            ("hvd.decode.finite_fetch", 1050, 1300),
            ("hvd.decode.bookkeep", 1310, 1480),
            ("hvd.serve.arrivals", 1520, 1560),
            ("hvd.decode.round", 1610, 3290, {"round": 1, "live_tokens": 42}),
            ("hvd.decode.reserve", 1620, 1700),
            ("hvd.decode.args", 1700, 1900),
            ("hvd.decode.dispatch", 1900, 1990, {"leg": "serving_decode"}),
            ("hvd.decode.sample_fetch", 1995, 2940)]
    return xspace(tmp_path, ops, modules, host)


def test_load_keeps_names_order_and_stats(tmp_path):
    threads = hostspans.load(two_rounds(tmp_path))
    assert len(threads) == 1                 # bench. events are not ours
    spans = threads[0]
    assert [s.name for s in spans][:3] == [
        "decode.round", "decode.args", "decode.dispatch"]
    rounds = hostspans.named(threads, "decode.round")
    assert [s.stats for s in rounds] == [
        {"round": 0, "live_tokens": 40}, {"round": 1, "live_tokens": 42}]
    assert hostspans.named(threads, "decode.dispatch")[1].stats == {
        "leg": "serving_decode"}
    assert hostspans.main_thread(threads) is spans
    assert hostspans.main_thread([]) == []


def test_innermost_pieces_are_disjoint_and_cover_the_spans(tmp_path):
    spans = hostspans.load(two_rounds(tmp_path))[0]
    pieces = hostspans.innermost(spans)
    assert all(a < b for a, b, _ in pieces)
    assert all(p[1] <= q[0] for p, q in zip(pieces, pieces[1:]))
    assert sum(b - a for a, b, _ in pieces) == xplane.length(
        (s.start_ns, s.end_ns) for s in spans)
    assert pieces[:4] == [
        (10 * US, 20 * US, "decode.round"), (20 * US, 60 * US, "decode.args"),
        (60 * US, 90 * US, "decode.dispatch"),
        (90 * US, 95 * US, "decode.round")]


def test_idle_goes_to_the_innermost_span_nanosecond_by_nanosecond(tmp_path):
    path = two_rounds(tmp_path)
    trace = xplane.load_trace(path)
    by_span = hostspans.idle_by_span(trace, hostspans.load(path)[0])
    # Window 100-2900; idle: 1000-1100 and 1110-2000.
    assert by_span == {name: us * US for name, us in {
        "decode.sample_fetch": 40 + 5,        # 1000-1040, 1995-2000
        "decode.round": 10 + 10 + 10 + 10 + 5,    # the round's own glue
        "decode.finite_fetch": 50 + 190,      # 1050-1100, 1110-1300
        "decode.bookkeep": 170, "serve.arrivals": 40,
        None: 30 + 50,                        # 1490-1520, 1560-1610
        "decode.reserve": 80, "decode.args": 200, "decode.dispatch": 90,
    }.items()}
    busy_s, window_s = xplane.busy_and_window_s(trace)
    assert sum(by_span.values()) == round((window_s - busy_s) * 1e9)


def _reader_ctx(path, metric, family=llama_dense, counters=None, logs=None):
    trace = xplane.load_trace(path)
    busy_s, window_s = xplane.busy_and_window_s(trace)
    return types.SimpleNamespace(
        trace=trace, xplane_path=path, counters=counters or {},
        family=family, busy_s=busy_s, window_s=window_s,
        metric={"name": metric},
        log=(logs.append if logs is not None else lambda msg: None))


def test_round_idle_phases_sum_to_the_idle_time_a_round(tmp_path):
    path = two_rounds(tmp_path)
    logs = []
    got = {p: round_idle_ms.read(_reader_ctx(
        path, "round_idle_ms." + p, logs=logs))
        for p in ("prepare", "fetch", "bookkeep", "between")}
    assert got == {"prepare": pytest.approx(0.370 / 2),
                   "fetch": pytest.approx(0.285 / 2),
                   "bookkeep": pytest.approx(0.170 / 2),
                   "between": pytest.approx(0.165 / 2)}
    ctx = _reader_ctx(path, "round_idle_ms.prepare")
    assert sum(got.values()) == pytest.approx(
        (ctx.window_s - ctx.busy_s) * 1e3 / 2)
    # Logged once, by the first entry: the finer split, the rounds, and
    # what lies under the root span or none.
    assert len(logs) == 2 and "over 2 rounds" in logs[1]
    assert "finite_fetch 0.1200" in logs[1] and "none 0.0400" in logs[1]
    assert "or none 0.0400 (8.1% of 0.4950)" in logs[1]
    assert "clock check" in logs[0]


def _lagged(tmp_path, lag_us, name):
    """Two rounds whose programs take 30 us to reach the device and
    whose results take 30 us back, seen through a device clock that runs
    ``lag_us`` behind the host's."""
    ops = [(DECODE, 100 - lag_us, 1000 - lag_us),
           (DECODE, 2000 - lag_us, 2900 - lag_us)]
    modules = [("jit_spmd(1)", a, b) for _, a, b in ops]
    host = [("hvd.decode.round", 50, 1500),
            ("hvd.decode.dispatch", 70, 90),
            ("hvd.decode.sample_fetch", 95, 1030),
            ("hvd.decode.bookkeep", 1040, 1400),
            ("hvd.decode.round", 1600, 3000),
            ("hvd.decode.args", 1610, 1960),
            ("hvd.decode.dispatch", 1970, 1990),
            ("hvd.decode.sample_fetch", 1995, 2930)]
    return xspace(tmp_path, ops, modules, host, name=name)


@pytest.mark.parametrize("lag_us", [0, 40, 90])
def test_the_clock_lag_is_bounded_by_causality_and_taken_out(tmp_path,
                                                             lag_us):
    """A program cannot begin before its dispatch span begins nor end
    after the fetch that waits for it returns; the split moves the spans
    by the middle of that range, so it reads the same through any lag."""
    path = _lagged(tmp_path, lag_us, "lag%d.xplane.pb" % lag_us)
    trace, threads = xplane.load_trace(path), hostspans.load(path)
    assert round_idle_ms.clock_lag_ns(
        trace, threads, llama_dense.DECODE_MODULE) == (
            (lag_us - 30) * US, (lag_us + 30) * US)
    by_span, rounds, _ = round_idle_ms.split(
        trace, threads, llama_dense.DECODE_MODULE)
    assert rounds == 2
    # Idle 1000-2000 on the host's clock: fetch's return, bookkeeping,
    # glue and no span, args, dispatch, the program's way to the device.
    assert by_span == {name: us * US for name, us in {
        "decode.sample_fetch": 30 + 5, "decode.round": 10 + 100 + 10 + 10 + 5,
        "decode.bookkeep": 360, None: 100, "decode.args": 350,
        "decode.dispatch": 20}.items()}


def test_clock_check_needs_the_dispatch_and_fetch_spans(tmp_path):
    path = two_rounds(tmp_path, with_spans=False)
    assert round_idle_ms.clock_lag_ns(
        xplane.load_trace(path), hostspans.load(path),
        llama_dense.DECODE_MODULE) is None


def test_a_phase_without_idle_time_reads_zero_not_none(tmp_path):
    ops = [(DECODE, 100, 1000), (DECODE, 1200, 2000)]
    modules = [("jit_spmd(1)", 100, 1000), ("jit_spmd(1)", 1200, 2000)]
    host = [("hvd.decode.round", 0, 1100), ("hvd.decode.sample_fetch", 50,
                                            1090),
            ("hvd.decode.round", 1100, 2100),
            ("hvd.decode.args", 1100, 1250)]
    path = xspace(tmp_path, ops, modules, host)
    got = {p: round_idle_ms.read(_reader_ctx(path, "round_idle_ms." + p))
           for p in ("prepare", "fetch", "bookkeep", "between")}
    assert got["bookkeep"] == 0.0 and got["bookkeep"] is not None
    assert got["between"] == pytest.approx(0.010 / 2)
    assert got["fetch"] == pytest.approx(0.090 / 2)
    assert got["prepare"] == pytest.approx(0.100 / 2)


def test_a_trace_without_the_programs_spans_still_reads(tmp_path):
    """A program from before PR 24 (the parent commit under this PR's
    benchmark files): every reader returns a number and says what it
    read instead."""
    path = two_rounds(tmp_path, with_spans=False)
    logs = []
    got = {p: round_idle_ms.read(_reader_ctx(
        path, "round_idle_ms." + p, logs=logs))
        for p in ("prepare", "fetch", "bookkeep", "between")}
    assert got == {"prepare": 0.0, "fetch": 0.0, "bookkeep": 0.0,
                   "between": pytest.approx(0.990 / 2)}
    assert any("no hvd. span" in line for line in logs)
    host = [("bench.train_step_call", 0, 300),
            ("bench.train_step_call", 400, 900)]
    tpath = xspace(tmp_path, [(DECODE, 100, 1000)], [("jit_step(1)", 100,
                                                      1000)], host,
                   name="train.xplane.pb")
    logs = []
    assert train_dispatch_ms.read(_reader_ctx(
        tpath, "train_dispatch_ms", family=bert, logs=logs)) \
        == pytest.approx(0.400)
    assert any("bench.train_step_call" in line for line in logs)


def test_no_round_at_all_raises(tmp_path):
    path = xspace(tmp_path, [("%copy.1 = f32[] copy()", 0, 10)],
                  [("jit_scatter(3)", 0, 10)], [("hvd.serve.admit", 0, 5)])
    with pytest.raises(xplane.TraceError):
        round_idle_ms.read(_reader_ctx(path, "round_idle_ms.fetch"))


def test_train_dispatch_is_the_median_step_annotation(tmp_path):
    host = [("hvd.train_step", 0, 300, {"step_num": 4}),
            ("hvd.step", 5, 295, {"step": 4}),
            ("hvd.train_step", 400, 1000, {"step_num": 5}),
            ("hvd.train_step", 1100, 1500, {"step_num": 6}),
            ("bench.train_step_call", 0, 2000)]
    path = xspace(tmp_path, [(DECODE, 100, 1000)],
                  [("jit_step(1)", 100, 1000)], host)
    assert train_dispatch_ms.read(_reader_ctx(
        path, "train_dispatch_ms", family=bert)) == pytest.approx(0.400)


# -- hand-built request records ---------------------------------------------

def _file_requests(token_times_by_rid, under_serve=True):
    from horovod_tpu.timeline import spans
    rec = spans.recorder()
    rec.reset()
    with rec.span(spans.PHASE, name="serve"):
        rec.file("request", under="serve", rid=99,
                 token_times=[0.0, 9.0])          # an older serve's: below
    with rec.span(spans.PHASE, name="serve" if under_serve else "other"):
        for rid, times in token_times_by_rid.items():
            rec.file("request", under="serve", rid=rid, token_times=times)
    return rec


def test_token_gap_takes_the_newest_serve_and_the_untraced_tokens():
    times = {0: [0.10, 0.15, 0.20, 0.40], 1: [0.30, 0.35, 1.35]}
    _file_requests(times)
    logs = []
    ctx = types.SimpleNamespace(counters={"trace_started_at": 1.0},
                                log=logs.append, metric=None)
    want = [50.0, 50.0, 200.0, 50.0]       # the 1,000 ms gap is traced
    assert token_gap_p99_ms.read(ctx) == pytest.approx(
        stats.percentile(want, 99))
    assert "4 gaps of 2 requests" in logs[0] and "median 50.0000" in logs[0]
    ctx.counters = {"trace_started_at": None}
    assert token_gap_p99_ms.read(ctx) == pytest.approx(
        stats.percentile(want + [1000.0], 99))
    assert token_gap_p99_ms.gaps_ms(
        [{"token_times": [0.1]}, {"token_times": []}]) == []


def test_token_gap_of_a_tiny_serve_equals_the_requests_own_times():
    """The records a real ``serve`` files, read back by the reader."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
    from horovod_tpu.serving import Request, ServingEngine
    from horovod_tpu.timeline import spans
    params = LlamaLM(LLAMA_SERVE, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    eng = ServingEngine(
        LLAMA_SERVE, params, slots=2, page_size=8, max_len=32,
        mesh=Mesh(np.asarray(jax.devices()[:1], dtype=object), ("tp",)))
    spans.recorder().reset()
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32) + i,
                    max_new_tokens=4) for i in range(3)]
    assert eng.serve(reqs).completed == 3
    want = [g * 1e3 for r in reqs for g in r.token_gaps]
    assert len(want) == 9
    ctx = types.SimpleNamespace(counters={}, log=lambda m: None, metric=None)
    assert token_gap_p99_ms.read(ctx) == pytest.approx(
        stats.percentile(want, 99))


def test_token_gap_without_records_reads_the_histogram(monkeypatch):
    """The parent commit's program: no ``records`` on the recorder."""
    from horovod_tpu.timeline import metrics, spans
    monkeypatch.setattr(spans, "recorder", lambda: object())
    snap = {"horovod_serving_token_latency_seconds": {
        "type": "histogram", "count": 100, "sum": 4.0,
        "buckets": {"0.025": 0, "0.05": 100, "+Inf": 100}}}
    monkeypatch.setattr(metrics.registry(), "snapshot", lambda: snap)
    logs = []
    ctx = types.SimpleNamespace(counters={}, log=logs.append, metric=None)
    assert token_gap_p99_ms.read(ctx) == pytest.approx(49.75)
    assert "before PR 24" in logs[0]


# -- the two traces recorded with the spans in them ---------------------------

def _recorded(tmp_path, cell):
    stem = os.path.join(HERE, "data", cell + ".spans")
    dst = tmp_path / (cell + ".xplane.pb")
    with gzip.open(stem + ".xplane.pb.gz", "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(stem + ".counters.json") as f:
        counters = json.load(f)
    data = bench_run.load_cell(ROOT, cell)
    trace = xplane.load_trace(str(dst))
    busy_s, window_s = xplane.busy_and_window_s(trace)
    logs = []
    ctx = types.SimpleNamespace(
        trace=trace, xplane_path=str(dst), counters=counters,
        config=data["config"], traffic=data["traffic"], cell=data["cell"],
        chips=1, family=bert if cell.startswith("bert") else llama_dense,
        peaks=peaks.peaks_for("TPU v5 lite"), busy_s=busy_s,
        window_s=window_s, log=logs.append, metric=None)
    return ctx, logs


@pytest.fixture(scope="module")
def offline(tmp_path_factory):
    return _recorded(tmp_path_factory.mktemp("offline"),
                     "mistral_7b_offline")


@pytest.fixture(scope="module")
def dp1(tmp_path_factory):
    return _recorded(tmp_path_factory.mktemp("dp1"), "bert_large_dp1")


def test_recorded_rounds_carry_the_engines_spans(offline):
    ctx, _ = offline
    threads = hostspans.load(ctx.xplane_path)
    rounds = hostspans.named(threads, "decode.round")
    n = ctx.counters["traced_rounds"]
    assert len(rounds) == n == 4
    # The program's own count of what a round's attention reads is the
    # count the benchmark's wrapper made from outside.
    assert sum(s.stats["live_tokens"] for s in rounds) \
        == ctx.counters["traced_live_tokens"]
    assert [s.stats["round"] for s in rounds] == list(range(
        rounds[0].stats["round"], rounds[0].stats["round"] + n))
    main = hostspans.main_thread(threads)
    for rnd in rounds:
        kids = {s.name for s in main
                if rnd.start_ns <= s.start_ns and s.end_ns <= rnd.end_ns
                and s is not rnd}
        assert {"decode.reserve", "decode.args", "decode.dispatch",
                "decode.sample_fetch", "decode.finite_fetch",
                "decode.bookkeep"} <= kids
    assert hostspans.named(threads, "decode.dispatch")[0].stats == {
        "leg": "serving_decode"}
    # On the device the kernel has its name, and the accepted readers
    # find what they found.
    dev = ctx.trace.devices[0]
    kernels = [e for e in xplane.ops_within(dev, llama_dense.DECODE_MODULE)
               if xplane.MOSAIC_KERNEL in e.name]
    assert len(kernels) == 16 * n
    assert all(e.name.startswith("%hvd_flash_decode") for e in kernels)
    count, _ = xplane.name_sums(dev.modules, llama_dense.DECODE_MODULE)
    assert count == n


def test_recorded_round_idle_sums_to_the_traces_idle_time(offline):
    ctx, logs = offline
    got = {}
    for phase in ("prepare", "fetch", "bookkeep", "between"):
        ctx.metric = {"name": "round_idle_ms." + phase}
        got[phase] = bench_run.reader_for(ctx.metric["name"]).read(ctx)
    assert all(v is not None and v >= 0.0 for v in got.values())
    idle_ms = (ctx.window_s - ctx.busy_s) * 1e3 / 4
    assert sum(got.values()) == pytest.approx(idle_ms, rel=1e-6)
    assert 1.0 < idle_ms < 10.0
    # The second program's round trip alone is over a millisecond.
    assert got["fetch"] > 1.0
    assert any("over 4 rounds" in line for line in logs)


def test_recorded_requests_have_a_timestamp_a_token():
    with open(os.path.join(HERE, "data",
                           "mistral_7b_offline.spans.requests.json")) as f:
        requests = json.load(f)
    assert len(requests) > 10
    for a in requests:
        t = a["token_times"]
        assert t == sorted(t) and t[0] == a["first_token_s"]
        assert a["arrival_s"] <= a["admit_s"] <= t[0] <= t[-1] <= a["done_s"]
    gaps = token_gap_p99_ms.gaps_ms(requests)
    assert len(gaps) > 500
    # A decode round on the chip takes 40-50 ms; a stall is a gap well
    # above it.
    assert 35.0 < stats.median(gaps) < 60.0
    assert stats.percentile(gaps, 99) > stats.median(gaps)


def test_recorded_steps_carry_the_step_annotation(dp1):
    ctx, logs = dp1
    steps = hostspans.named(hostspans.load(ctx.xplane_path), "train_step")
    assert len(steps) == ctx.counters["trace_steps"] == 2
    assert steps[1].stats["step_num"] == steps[0].stats["step_num"] + 1
    ctx.metric = {"name": "train_dispatch_ms"}
    value = bench_run.reader_for("train_dispatch_ms").read(ctx)
    assert value == pytest.approx(
        stats.median([s.dur_ns for s in steps]) / 1e6)
    assert 0.0 < value < 88.0          # the host is not the pace
    # Forward, dq and dk/dv can be told apart on the ops line.
    dev = ctx.trace.devices[0]
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        n, _ = xplane.name_sums(dev.ops, r"^%" + name + r"\.\d+ = ")
        assert n == 24 * 2, name
    n, _ = xplane.name_sums(dev.ops, xplane.MOSAIC_KERNEL)
    assert n == 72 * 2
