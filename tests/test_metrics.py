"""Unified metrics plane (PR 6 tentpole).

Registry semantics (thread-safe counters/gauges/histograms, Prometheus
text escaping, null-object behaviour when disabled), the per-step
:class:`StepReport` sampled around the jitted step, the ``/metrics``
HTTP endpoint end-to-end during a real CPU train loop, and
``fusion.explain_plan`` agreeing with the exchange's own bucket plan.

Byte-for-byte contracts: the StepReport wire accounting must equal
``zero_report``'s figures on the ZeRO-1 path and
``wire_payload_bytes``-over-``ef_bucket_plan`` on the error-feedback
path.
"""

import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hv
from horovod_tpu.collectives.compression import (parse_compression,
                                                 wire_payload_bytes)
from horovod_tpu.controller import fusion
from horovod_tpu.core.state import global_state
from horovod_tpu.optim import distributed as _dist
from horovod_tpu.timeline import Timeline
from horovod_tpu.timeline import metrics as M


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Every test starts from an empty registry and uninitialized hvd."""
    hv.shutdown()
    M.reset_metrics()
    yield
    hv.shutdown()
    M.reset_metrics()


# -- registry primitives ----------------------------------------------------

def test_counter_concurrency_8_threads():
    c = M.registry().counter("t_conc_total", "concurrency probe")
    n_threads, per_thread = 8, 1000

    def worker():
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread


def test_counter_rejects_negative_increment():
    c = M.registry().counter("t_neg_total")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_histogram_bucket_arithmetic():
    h = M.Histogram(buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 5.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    # le semantics (v <= bound) with CUMULATIVE counts.
    assert snap["buckets"] == {"0.1": 2, "1": 4, "10": 5, "+Inf": 6}
    assert snap["count"] == 6
    np.testing.assert_allclose(snap["sum"], 106.65)


def test_histogram_rejects_bad_bounds():
    with pytest.raises(ValueError):
        M.Histogram(buckets=())
    with pytest.raises(ValueError):
        M.Histogram(buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        M.Histogram(buckets=(2.0, 1.0))


def test_histogram_renders_cumulative_le_lines():
    reg = M.registry()
    h = reg.histogram("t_hist_seconds", "probe", buckets=(0.5, 2.0))
    h.observe(0.1)
    h.observe(1.0)
    text = reg.render()
    assert "# TYPE t_hist_seconds histogram" in text
    assert 't_hist_seconds_bucket{le="0.5"} 1' in text
    assert 't_hist_seconds_bucket{le="2"} 2' in text
    assert 't_hist_seconds_bucket{le="+Inf"} 2' in text
    assert "t_hist_seconds_count 2" in text


def test_prometheus_label_and_help_escaping():
    reg = M.registry()
    g = reg.gauge("t_esc", 'tricky "help"\nwith newline',
                  labelnames=("name",))
    g.labels(name='a"b\\c\nd').set(1)
    text = reg.render()
    assert '# HELP t_esc tricky "help"\\nwith newline' in text
    assert 't_esc{name="a\\"b\\\\c\\nd"} 1' in text


def test_label_validation_and_kind_conflict():
    reg = M.registry()
    fam = reg.gauge("t_lbl", labelnames=("codec",))
    with pytest.raises(ValueError):
        fam.labels(wrong="x")
    with pytest.raises(ValueError):
        fam.set(1.0)  # labelled family has no solo child
    with pytest.raises(ValueError):
        reg.counter("t_lbl")  # same name, different kind


def test_disabled_registry_is_noop(monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS", "0")
    reg = M.registry()
    assert not reg.enabled
    c = reg.counter("t_off_total")
    assert c is M.NULL_METRIC
    c.inc()
    c.labels(anything="goes").observe(3)
    assert c.value == 0.0
    assert reg.render() == ""
    assert reg.snapshot() == {}
    # Flip back on: families register normally again.
    monkeypatch.setenv("HOROVOD_METRICS", "1")
    reg.counter("t_on_total").inc()
    assert reg.counter("t_on_total").value == 1


def test_snapshot_shapes():
    reg = M.registry()
    reg.counter("t_snap_total").inc(3)
    reg.gauge("t_snap_g", labelnames=("k",)).labels(k="a").set(2.5)
    reg.histogram("t_snap_h", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["t_snap_total"] == {"type": "counter", "value": 3}
    assert snap["t_snap_g"]["samples"] == [
        {"labels": {"k": "a"}, "value": 2.5}]
    assert snap["t_snap_h"]["count"] == 1
    assert snap["t_snap_h"]["buckets"] == {"1": 1, "+Inf": 1}


def test_broken_collector_does_not_kill_scrape():
    reg = M.registry()
    reg.counter("t_sane_total").inc()

    def boom():
        raise RuntimeError("collector bug")

    reg.add_collector(boom)
    reg.add_collector(boom)  # idempotent by identity
    assert len(reg._collectors) == 1
    assert "t_sane_total 1" in reg.render()


def test_record_step_report_feeds_families():
    report = M.StepReport(step=4, wall_time_s=0.08, steps_per_exec=4,
                          microbatches=2, codec="fp16",
                          exchanged_bytes=500, uncompressed_bytes=1000)
    M.record_step_report(report)
    assert M.last_step_report() == report
    reg = M.registry()
    assert reg.counter("horovod_step_total").value == 4
    assert reg.counter("horovod_wire_bytes_total").value == 2000
    assert reg.gauge("horovod_wire_bytes_per_step").value == 500
    assert reg.gauge("horovod_compression_ratio").value == 2.0
    hist = reg.histogram("horovod_step_time_seconds").snapshot()
    assert hist["count"] == 1  # one dispatch covers 4 steps
    np.testing.assert_allclose(hist["sum"], 0.02)


# -- step report <-> exchange accounting -----------------------------------

def _quadratic_loss(p, b):
    return jnp.mean((b[0] @ p["w"] + p["b"] - b[1]) ** 2)


def _batch(rng, rows=16):
    x = jnp.asarray(rng.randn(rows, 6), jnp.float32)
    y = jnp.asarray(rng.randn(rows, 4), jnp.float32)
    return hv.shard_batch((x, y))


def _fresh_params():
    rng = np.random.RandomState(0)
    return {"w": rng.randn(6, 4).astype(np.float32),
            "b": np.zeros((4,), np.float32)}


def test_step_report_matches_zero_report():
    hv.init()
    opt = optax.adam(1e-2)
    params = hv.replicate(_fresh_params())
    state = hv.zero_init(opt, params)
    step = hv.make_train_step(_quadratic_loss, opt, zero_stage=1)
    rng = np.random.RandomState(1)
    params, state, _ = step(params, state, _batch(rng))
    rep = M.last_step_report()
    assert rep is not None and rep.zero_stage == 1
    want = hv.zero_report(opt, _fresh_params(), world=hv.size())
    assert rep.exchanged_bytes == want["zero1_exchanged_bytes_per_chip"]
    assert rep.uncompressed_bytes == \
        want["replicated_allreduce_bytes_per_chip"]
    assert rep.codec == "none"


def test_step_report_matches_ef_wire_accounting():
    hv.init()
    comp = parse_compression("powersgd:2")
    opt = hv.DistributedOptimizer(optax.sgd(0.05), compression="powersgd:2")
    params = hv.replicate(_fresh_params())
    state = hv.replicate(opt.init(_fresh_params()))
    step = hv.make_train_step(_quadratic_loss, opt)
    rng = np.random.RandomState(2)
    params, state, _ = step(params, state, _batch(rng))
    rep = M.last_step_report()
    assert rep is not None and rep.codec == comp.__name__
    spec = _dist.ef_bucket_plan(jax.tree.leaves(params), None, comp)
    want = sum(wire_payload_bytes(comp, sum(s.size for s in lspecs),
                                  jnp.dtype(dt).itemsize)
               for dt, lspecs in spec.buffers)
    assert rep.exchanged_bytes == want
    raw = sum(int(x.size) * jnp.dtype(x.dtype).itemsize
              for x in jax.tree.leaves(params))
    assert rep.uncompressed_bytes == raw


def test_step_report_plain_codec_and_instrumented_lower():
    hv.init()
    opt = hv.DistributedOptimizer(optax.sgd(0.05), compression="fp16")
    comp = parse_compression("fp16")
    params = hv.replicate(_fresh_params())
    state = hv.replicate(opt.init(_fresh_params()))
    step = hv.make_train_step(_quadratic_loss, opt)
    # The instrumentation wrapper must still expose the jit surface
    # (donation-audit tests call .lower on the returned step).
    assert hasattr(step, "lower")
    rng = np.random.RandomState(3)
    for _ in range(3):
        params, state, _ = step(params, state, _batch(rng))
    rep = M.last_step_report()
    assert rep.step == 3 and rep.steps_per_exec == 1
    spec = fusion.plan_buckets(jax.tree.leaves(params), None)
    want = sum(wire_payload_bytes(comp, sum(s.size for s in lspecs),
                                  jnp.dtype(dt).itemsize)
               for dt, lspecs in spec.buffers)
    assert rep.exchanged_bytes == want
    assert M.registry().counter("horovod_step_total").value == 3


# -- /metrics endpoint end-to-end -------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
        return resp.status, resp.headers.get("Content-Type"), \
            resp.read().decode()


@pytest.mark.integration
def test_metrics_endpoint_end_to_end(monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS_PORT", "0")
    hv.init()
    server = global_state().metrics_server
    assert server is not None

    opt = hv.DistributedOptimizer(optax.sgd(0.05), compression="fp16")
    params = hv.replicate(_fresh_params())
    state = hv.replicate(opt.init(_fresh_params()))
    step = hv.make_train_step(_quadratic_loss, opt)
    rng = np.random.RandomState(4)
    for _ in range(3):
        params, state, loss = step(params, state, _batch(rng))
    assert np.isfinite(float(loss))

    status, ctype, text = _get(server.port, "/metrics")
    assert status == 200
    assert ctype == M.CONTENT_TYPE
    families = [ln.split()[3] for ln in text.splitlines()
                if ln.startswith("# TYPE ")]
    assert len(families) >= 8
    for name in ("horovod_step_total", "horovod_step_time_seconds",
                 "horovod_wire_bytes_total", "horovod_wire_bytes_per_step",
                 "horovod_compression_ratio",
                 "horovod_plan_buckets",
                 "horovod_plan_cache_hits_total",
                 "horovod_plan_cache_misses_total",
                 "horovod_deferred_fused_buckets_total"):
        assert f"# TYPE {name} " in text, name
    assert "horovod_step_total 3" in text
    assert 'horovod_step_time_seconds_bucket{le="+Inf"} 3' in text

    status, ctype, body = _get(server.port, "/metrics.json")
    assert status == 200 and ctype == "application/json"
    snap = json.loads(body)
    assert snap["horovod_step_total"]["value"] == 3
    assert snap == hv.metrics_snapshot()

    assert _get(server.port, "/healthz")[0] == 200
    with pytest.raises(urllib.error.HTTPError):
        _get(server.port, "/nope")

    hv.shutdown()
    assert global_state().metrics_server is None


def test_metrics_server_optional_hmac():
    from horovod_tpu.run.http_kv import _signable
    from horovod_tpu.run.metrics_server import MetricsServer
    from horovod_tpu.run.secret import compute_digest
    import time

    M.registry().counter("t_auth_total").inc()
    server = MetricsServer(port=0, secret_key="s3cret")
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(server.port, "/metrics")
        assert e.value.code == 403
        ts = repr(time.time())
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/metrics",
            headers={"X-Hvd-Ts": ts,
                     "X-Hvd-Sig": compute_digest(
                         "s3cret", _signable("GET", "/metrics", ts, b""))})
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200
            assert "t_auth_total 1" in resp.read().decode()
    finally:
        server.stop()


def test_metrics_port_requires_metrics_enabled(monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS", "0")
    monkeypatch.setenv("HOROVOD_METRICS_PORT", "0")
    hv.init()
    assert global_state().metrics_server is None


# -- explain_plan <-> emitted exchange --------------------------------------

def test_explain_plan_matches_plan_buckets():
    thr = 4096
    leaves = [jax.ShapeDtypeStruct(s, "float32")
              for s in ((100, 100), (512,), (64, 64), (7,))]
    rows = fusion.explain_plan(leaves, threshold_bytes=thr, register=False)
    spec = fusion.plan_buckets(leaves, thr)
    assert len(rows) == len(spec.buffers)
    for row, (dt, lspecs) in zip(rows, spec.buffers):
        size = sum(s.size for s in lspecs)
        assert row["dtype"] == str(jnp.dtype(dt))
        assert row["leaves"] == len(lspecs)
        assert row["elements"] == size
        assert row["bytes"] == size * jnp.dtype(dt).itemsize
        assert row["wire_bytes"] == row["bytes"]  # uncompressed
        assert row["codec"] == "none"
        assert f"thr={thr}" in row["fuse_key"]


def test_explain_plan_matches_ef_exchange_plan():
    comp = parse_compression("powersgd:2")
    leaves = [jax.ShapeDtypeStruct(s, "float32")
              for s in ((100, 100), (512,), (64, 64))]
    rows = fusion.explain_plan(leaves, threshold_bytes=16384,
                               compression="powersgd:2", register=False)
    spec = _dist.ef_bucket_plan(leaves, 16384, comp)
    assert len(rows) == len(spec.buffers)
    for row, (dt, lspecs) in zip(rows, spec.buffers):
        size = sum(s.size for s in lspecs)
        assert row["bytes"] == size * jnp.dtype(dt).itemsize
        assert row["wire_bytes"] == wire_payload_bytes(
            comp, size, jnp.dtype(dt).itemsize)
        assert row["wire_bytes"] < row["bytes"]
        assert row["codec"] == comp.__name__


def test_explain_plan_matches_emitted_step_exchange():
    """The acceptance contract: explain_plan's totals equal the
    StepReport's wire accounting for the SAME params + codec."""
    hv.init()
    opt = hv.DistributedOptimizer(optax.sgd(0.05), compression="powersgd:2")
    params = hv.replicate(_fresh_params())
    state = hv.replicate(opt.init(_fresh_params()))
    step = hv.make_train_step(_quadratic_loss, opt)
    rng = np.random.RandomState(5)
    params, state, _ = step(params, state, _batch(rng))
    rep = M.last_step_report()
    thr = opt.update._hvd_exchange["fusion_threshold"]
    rows = fusion.explain_plan(params, threshold_bytes=thr,
                               compression="powersgd:2")
    assert sum(r["wire_bytes"] for r in rows) == rep.exchanged_bytes
    assert sum(r["bytes"] for r in rows) == rep.uncompressed_bytes
    # register=True published the rows as gauges.
    reg = M.registry()
    assert reg.gauge("horovod_plan_buckets").value == len(rows)
    first = rows[0]
    fam = reg.gauge("horovod_plan_bucket_bytes",
                    labelnames=("bucket", "dtype"))
    assert fam.labels(bucket=str(first["bucket"]),
                      dtype=first["dtype"]).value == first["bytes"]


def test_render_plan_table_and_empty():
    leaves = [jax.ShapeDtypeStruct((64, 64), "float32")]
    rows = fusion.explain_plan(leaves, threshold_bytes=1 << 20,
                               compression="fp16", register=False)
    text = fusion.render_plan(rows)
    lines = text.splitlines()
    assert lines[0].split()[:3] == ["bucket", "dtype", "leaves"]
    assert "total: 1 bucket(s), 16384 bytes raw, 8192 bytes wire" in text
    assert "(ratio 2.0x)" in text
    assert fusion.render_plan([]) == "(empty plan: no leaves)"


def test_explain_plan_cli(monkeypatch, capsys):
    from horovod_tpu.run import launch
    monkeypatch.setenv("HOROVOD_COMPRESSION", "fp16")
    assert launch.run_command(["--explain-plan"]) == 0
    out = capsys.readouterr().out
    assert "bucket" in out and "fp16" in out
    assert "total:" in out


# -- Timeline.close regression (satellite) -----------------------------------

def test_timeline_double_close_is_idempotent(tmp_path):
    path = tmp_path / "tl.json"
    tl = Timeline(str(path))
    tl.counter("x", 1.0)
    tl.close()
    tl.close()  # atexit fires this again after shutdown: must be a no-op
    doc = json.loads(path.read_text())
    assert any(ev.get("ph") == "C" for ev in doc)


def test_timeline_concurrent_close_single_footer(tmp_path):
    path = tmp_path / "tl.json"
    tl = Timeline(str(path))
    tl.counter("x", 2.0)
    threads = [threading.Thread(target=tl.close) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # Exactly one closing "]" -- concurrent closers must not double-write.
    text = path.read_text()
    assert text.count("]") == 1
    json.loads(text)


def test_timeline_close_survives_drain_failure(tmp_path, monkeypatch):
    tl = Timeline(str(tmp_path / "tl.json"))

    def boom():
        raise OSError("disk full")

    monkeypatch.setattr(tl, "_drain", boom)
    with pytest.raises(OSError):
        tl.close()
    assert tl._file.closed  # file still released despite the raise
    tl.close()  # and the second close is a clean no-op
