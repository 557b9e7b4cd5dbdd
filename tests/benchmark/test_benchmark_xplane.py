"""The reduction from a profiler trace to numbers, on made-up intervals
and on two small traces recorded on the v5e (``data/``): one BERT-Large
step of ``bert_large_dp1`` and three decode rounds with their prefills of
``mistral_7b_chat_steady`` (PR 23, ``.chip_tmp/record.py`` of that PR)."""

import gzip
import json
import os
import shutil
import types

import pytest

from benchmarks.families import llama_dense
from benchmarks.lib import peaks, xplane
from benchmarks.lib.xplane import DevicePlane, Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
AR = ('%all-reduce.3 = f32[1024]{0} all-reduce(f32[1024]{0} %x), '
      'replica_groups={}')
FUSION_AFTER_AR = ('%fusion.9 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), '
                   'kind=kLoop')


def ev(name, start, end):
    return Event(name, start, end)


# -- made-up intervals ---------------------------------------------------------

@pytest.mark.parametrize("intervals,want", [
    ([(0, 5), (3, 8)], 8), ([(0, 5), (5, 8)], 8), ([(0, 5), (7, 8)], 6),
    ([(2, 3), (0, 10)], 10), ([], 0), ([(4, 4)], 0)])
def test_union_length(intervals, want):
    assert xplane.length(intervals) == want


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(3, 5)], [(0, 3), (5, 10)]),
    ([(0, 10), (20, 30)], [(5, 22)], [(0, 5), (22, 30)]),
    ([(0, 10)], [], [(0, 10)]), ([(0, 10)], [(0, 10)], []),
    ([(0, 10)], [(-5, 2), (8, 20)], [(2, 8)])])
def test_subtract(a, b, want):
    assert xplane.subtract(a, b) == want


def test_clip():
    assert xplane.clip([(0, 10), (20, 30), (40, 50)], 5, 45) == [
        (5, 10), (20, 30), (40, 45)]


def test_busy_is_a_union_never_a_sum():
    dev = DevicePlane(0, [ev("while", 0, 100), ev("a", 10, 30),
                          ev("b", 40, 90), ev("c", 110, 120)], [])
    assert xplane.window_of(dev) == (0, 120)
    assert xplane.busy_ns(dev) == 110
    assert xplane.busy_ns(dev, (50, 115)) == 55
    assert xplane.self_times(dev.ops) == {
        "a": 20, "b": 50, "while": 30, "c": 10}


@pytest.mark.parametrize("name,want", [
    (AR, "all-reduce"), (FUSION_AFTER_AR, "fusion"),
    ('%x = (f32[2]{0}, u32[]) all-reduce-start(f32[2]{0} %y)',
     "all-reduce-start"), ("plain-name", "plain-name"),
    ('%c.1 = bf16[8]{0:T(8)} custom-call(s32[3]{0} %z), '
     'custom_call_target="tpu_custom_call"', "custom-call")])
def test_opcode(name, want):
    assert xplane.opcode(name) == want


def test_a_fusion_that_reads_an_all_reduce_is_not_a_collective():
    assert xplane.is_collective(AR)
    assert not xplane.is_collective(FUSION_AFTER_AR)


def test_collective_exposure():
    # 0-40 compute; all-reduce 30-70, of which 30-40 and 60-70 are
    # covered by compute; a while loop that merely holds it covers nothing.
    dev = DevicePlane(0, [
        ev("%while.1 = () while()", 0, 100),
        ev("%fusion.1 = f32[] fusion()", 0, 40), ev(AR, 30, 70),
        ev("%fusion.2 = f32[] fusion()", 60, 90)], [])
    assert xplane.collective_exposed_ns(dev) == (40, 20)


def test_several_chips_give_the_mean_never_the_sum():
    a = DevicePlane(0, [ev("x", 0, 60), ev("y", 80, 100)], [])
    b = DevicePlane(1, [ev("x", 0, 40), ev("y", 80, 100)], [])
    busy, window = xplane.busy_and_window_s(Trace([a, b], []))
    assert busy == pytest.approx(70e-9) and window == pytest.approx(100e-9)


def test_idle_gaps_go_to_the_innermost_annotation():
    dev = DevicePlane(0, [ev("x", 0, 10), ev("y", 30, 40), ev("z", 90, 95)],
                      [])
    host = [ev("bench.decode_once", 0, 100), ev("bench.fetch", 12, 28)]
    gaps = dict(map(tuple, xplane.idle_gaps(Trace([dev], host))))
    assert gaps == {"bench.fetch": 20e-9, "bench.decode_once": 50e-9}


def test_ops_within_a_module():
    dev = DevicePlane(0, [ev("a", 0, 10), ev("b", 20, 30), ev("c", 50, 60)],
                      [ev("jit_spmd(1)", 0, 35), ev("jit__prefill(2)", 45,
                                                    70)])
    inside = xplane.ops_within(dev, llama_dense.DECODE_MODULE)
    assert [e.name for e in inside] == ["a", "b"]
    assert [e.name for e in xplane.ops_within(
        dev, llama_dense.PREFILL_MODULE)] == ["c"]


# -- files ------------------------------------------------------------------------

def _write(tmp_path, text_proto, name="t.xplane.pb"):
    from jax.profiler import ProfileData
    path = tmp_path / name
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text_proto))
    return str(path)


@pytest.mark.parametrize("text_proto", [
    "",
    'planes { name: "/host:CPU" lines { name: "python" } }',
    'planes { name: "/device:TPU:0" lines { name: "XLA Ops" } }',
    'planes { name: "/device:TPU:0" lines { name: "Steps" } }'])
def test_an_empty_or_host_only_trace_raises(tmp_path, text_proto):
    with pytest.raises(xplane.TraceError):
        xplane.load_trace(_write(tmp_path, text_proto))


def test_no_trace_file_raises(tmp_path):
    with pytest.raises(xplane.TraceError):
        xplane.find_xplane(str(tmp_path))


def _recorded(tmp_path, name):
    src = os.path.join(HERE, "data", name + ".xplane.pb.gz")
    dst = tmp_path / (name + ".xplane.pb")
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(HERE, "data", name + ".counters.json")) as f:
        counters = json.load(f)
    return xplane.load_trace(str(dst)), counters


def _sweep_busy(events):
    """Busy time another way: a sweep over start and end points."""
    points = sorted([(e.start_ns, 1) for e in events]
                    + [(e.end_ns, -1) for e in events],
                    key=lambda p: (p[0], -p[1]))
    busy, depth, since = 0, 0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


@pytest.fixture(scope="module")
def bert_trace(tmp_path_factory):
    return _recorded(tmp_path_factory.mktemp("bert"), "bert_large_dp1")


@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    return _recorded(tmp_path_factory.mktemp("serve"),
                     "mistral_7b_chat_steady")


def test_recorded_bert_step(bert_trace):
    trace, _ = bert_trace
    assert len(trace.devices) == 1
    dev = trace.devices[0]
    lo, hi = xplane.window_of(dev)
    busy = xplane.busy_ns(dev)
    assert busy == _sweep_busy(dev.ops)
    assert 0 < busy <= hi - lo
    # One step of 88 ms, all but idle-free.
    assert 0.080 < busy / 1e9 < 0.100 and busy / (hi - lo) > 0.99
    half = xplane.busy_ns(dev, (lo, (lo + hi) // 2))
    assert 0 < half < busy
    # Flash attention forward, dq and dk/dv in each of the 24 layers.
    n, ns = xplane.name_sums(dev.ops, xplane.MOSAIC_KERNEL)
    assert n == 72 and 0 < ns < busy
    assert sum(xplane.self_times(dev.ops).values()) == pytest.approx(
        busy, rel=0.02)
    assert not any(xplane.is_collective(e.name) for e in dev.ops)
    assert xplane.collective_exposed_ns(dev) == (0, 0)
    assert any(e.name == "bench.train_step_call" for e in trace.host)
    top = xplane.top_ops(trace)
    assert 1 <= len(top) <= 10 and all(s > 0 for _, s in top)


def test_recorded_serving_window(serve_trace):
    trace, counters = serve_trace
    dev = trace.devices[0]
    busy = xplane.busy_ns(dev)
    assert busy == _sweep_busy(dev.ops)
    lo, hi = xplane.window_of(dev)
    assert 0 < busy < hi - lo          # the host loop leaves gaps
    n, ns = xplane.name_sums(dev.modules, llama_dense.DECODE_MODULE)
    assert n == counters["traced_rounds"] == 3
    assert 0.040 < ns / n / 1e9 < 0.055
    kernels = xplane.ops_within(dev, llama_dense.DECODE_MODULE)
    k, _ = xplane.name_sums(kernels, xplane.MOSAIC_KERNEL)
    assert k == 16 * 3                  # one split-KV call a layer a round
    assert sum(e.name == "bench.decode_once" for e in trace.host) == 3
    gaps = xplane.idle_gaps(trace)
    assert gaps and gaps[0][0].startswith(("bench.", "host:"))
    assert sum(s for _, s in gaps) == pytest.approx(
        (hi - lo - busy) / 1e9, rel=1e-6)


@pytest.mark.parametrize("metric,cell", [
    ("train_step_ms", "bert"), ("flash_attn_roofline", "bert"),
    ("device_idle_pct.train", "bert"), ("decode_step_ms.steady", "serve"),
    ("prefill_share_pct", "serve"), ("device_idle_pct.steady", "serve"),
    ("decode_attn_roofline", "serve")])
def test_readers_on_the_recorded_traces(metric, cell, bert_trace,
                                        serve_trace):
    from benchmarks import run as bench_run
    from benchmarks.families import bert
    trace, counters = bert_trace if cell == "bert" else serve_trace
    workload = ("bert_large_dp1" if cell == "bert"
                else "mistral_7b_chat_steady")
    data = bench_run.load_cell(ROOT, workload)
    busy_s, window_s = xplane.busy_and_window_s(trace)
    ctx = types.SimpleNamespace(
        trace=trace, counters=counters, config=data["config"],
        traffic=data["traffic"], cell=data["cell"], chips=1,
        family=bert if cell == "bert" else llama_dense,
        peaks=peaks.peaks_for("TPU v5 lite"), busy_s=busy_s,
        window_s=window_s, log=lambda msg: None, metric=None)
    value = bench_run.reader_for(metric).read(ctx)
    if metric == "prefill_share_pct" and value is None:
        pytest.skip("no prefill fell inside the three recorded rounds")
    assert value is not None and 0 < value < 1e6
    if metric.endswith("_roofline") or metric.startswith("device_idle"):
        assert value <= 100.0


@pytest.mark.parametrize("metric,key", [
    ("queue_wait_p95_ms", "queue_wait_ms"), ("ttft_p95_ms.steady", "ttft_ms")])
def test_request_readers_take_the_tail_or_nothing(metric, key):
    from benchmarks import run as bench_run
    reader = bench_run.reader_for(metric)
    ms = [float(i) for i in range(1, 101)]
    ctx = types.SimpleNamespace(counters={key: ms}, metric=None)
    assert reader.read(ctx) == pytest.approx(95.05)
    assert reader.read(types.SimpleNamespace(counters={key: []})) is None
    assert reader.read(types.SimpleNamespace(counters={})) is None


def test_an_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
