"""GP Bayesian autotuner tests (ParameterManager + bayesian_optimization
parity: gaussian_process.cc / bayesian_optimization.cc behavior)."""

import numpy as np
import pytest

from horovod_tpu.autotune import Autotuner
from horovod_tpu.autotune.gp import (BayesianOptimizer, GaussianProcess,
                                     expected_improvement)
from horovod_tpu.core.config import Config


def test_gp_interpolates_and_is_uncertain_away_from_data():
    gp = GaussianProcess(length_scale=0.3, noise=1e-6)
    X = np.array([[0.0], [0.5], [1.0]])
    y = np.array([0.0, 1.0, 0.0])
    gp.fit(X, y)
    mu, sigma = gp.predict(X)
    np.testing.assert_allclose(mu, y, atol=1e-2)
    assert sigma.max() < 0.1  # confident at the data
    mu2, sigma2 = gp.predict(np.array([[0.25]]))
    assert sigma2[0] > sigma.max()  # less confident between points
    assert 0.0 < mu2[0] < 1.0


def test_expected_improvement_prefers_high_mean_and_high_uncertainty():
    mu = np.array([1.0, 2.0, 1.0])
    sigma = np.array([0.1, 0.1, 2.0])
    ei = expected_improvement(mu, sigma, best=1.5)
    assert ei[1] > ei[0]  # higher mean wins over equal uncertainty
    assert ei[2] > ei[0]  # exploration: high variance beats low


def test_bayesian_optimizer_finds_peak_on_grid():
    # Objective peaked at grid point 7 of 12.
    grid = [[float(i)] for i in range(12)]
    opt = BayesianOptimizer(grid, warmup=4)
    truth = lambda i: -(i - 7.0) ** 2  # noqa: E731
    for _ in range(9):
        i = opt.suggest()
        assert i is not None
        opt.observe(i, truth(i))
    assert opt.best_index is not None
    assert abs(opt.best_index - 7) <= 1


def test_threshold_inert_sample_scores_its_siblings():
    """A sample of a step that builds no fusion bucket scores every
    candidate that differs from it in the threshold alone (they are the
    same compiled step): with two cycle times the tuner runs two samples,
    not one a threshold, and logs only what it ran."""
    t = Autotuner(Config(autotune=True), steps_per_sample=1,
                  cycle_candidates=[1.0, 5.0])
    thresholds = {g[0] for g in t.grid}
    assert len(thresholds) >= 5 and len(t.grid) == 2 * len(thresholds)
    guard = 0
    while not t.done and guard < 40:
        # The first step of a sample is the compile and is not scored.
        t.record_step(0.01 * t.cycle_time_ms(), nbytes=1 << 20,
                      threshold_inert=True)
        guard += 1
    assert t.done
    assert len(t._samples) == 2
    assert {s[1] for s in t._samples} == {1.0, 5.0}
    assert t.cycle_time_ms() == 1.0
    assert t._opt.n_observed == len(t.grid)


def test_autotuner_converges_to_best_throughput(tmp_path):
    """Feed synthetic step times where 32 MiB @ 1ms is fastest; the tuner
    must lock in at (or adjacent to) the peak and log every sample."""
    log = tmp_path / "at.csv"
    cfg = Config(autotune=True, autotune_log=str(log))
    t = Autotuner(cfg, steps_per_sample=1)
    peak = (32 * 1024 * 1024, 1.0)

    def step_time(thr, cyc):
        # Smooth bowl in log-threshold and cycle distance around the peak.
        d = (abs(np.log2(thr / peak[0])) + abs(np.log2(cyc / peak[1])))
        return 0.01 * (1.0 + 0.3 * d)

    guard = 0
    while not t.done and guard < 100:
        t.record_step(step_time(t.fusion_threshold(), t.cycle_time_ms()),
                      nbytes=100 * 1024 * 1024)
        guard += 1
    assert t.done
    # Best within a factor of 4 of the true peak threshold.
    assert peak[0] / 4 <= t.fusion_threshold() <= peak[0] * 4
    text = log.read_text()
    assert text.startswith("fusion_threshold_bytes,cycle_time_ms,")
    assert "# best," in text


def test_autotuner_warm_start_skips_resampling(tmp_path):
    log = tmp_path / "warm.csv"
    cfg = Config(autotune=True, autotune_log=str(log))
    t1 = Autotuner(cfg, steps_per_sample=1)
    while not t1.done:
        t1.record_step(0.01 if t1.fusion_threshold() == 32 * 1024 * 1024
                       else 0.02, nbytes=1 << 20)
    best = (t1.fusion_threshold(), t1.cycle_time_ms())
    # Second run warm-starts from the log: already at max_samples, so it
    # finishes immediately with the same best.
    t2 = Autotuner(cfg, steps_per_sample=1)
    assert t2.done
    assert (t2.fusion_threshold(), t2.cycle_time_ms()) == best


def test_autotuner_warm_start_preserves_log_rows(tmp_path):
    """A warm-started run must not truncate the persisted samples."""
    log = tmp_path / "keep.csv"
    cfg = Config(autotune=True, autotune_log=str(log))
    t1 = Autotuner(cfg, steps_per_sample=1)
    while not t1.done:
        t1.record_step(0.01, nbytes=1 << 20)
    rows1 = [l for l in log.read_text().splitlines()
             if l and not l.startswith(("fusion", "#"))]
    t2 = Autotuner(cfg, steps_per_sample=1)
    assert t2.done  # warm start covers the whole budget
    rows2 = [l for l in log.read_text().splitlines()
             if l and not l.startswith(("fusion", "#"))]
    assert rows2 == rows1  # log survives the restart intact


def test_autotuner_skips_cycle_axis_without_torch_shim(monkeypatch):
    import sys
    monkeypatch.delitem(sys.modules, "horovod_tpu.torch_api",
                        raising=False)
    monkeypatch.delitem(sys.modules, "horovod_tpu.torch", raising=False)
    t = Autotuner(Config(autotune=True), steps_per_sample=1)
    cycles = {c for _, c, *_rest in t.grid}
    assert cycles == {Config().cycle_time}


def test_autotuner_tunes_cycle_axis_with_torch_shim(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "horovod_tpu.torch_api",
                        sys.modules[__name__])  # any module object works
    t = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert len({c for _, c, *_rest in t.grid}) > 1


def test_autotuner_hierarchical_axis_requires_two_level_mesh(hvd):
    """Flat mesh (single-process default): nothing to choose, the
    hierarchical axis stays fixed; a (dcn, ici) mesh opens it."""
    import jax
    import horovod_tpu as hv_mod
    from horovod_tpu.parallel.mesh import build_mesh

    t = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {h for _t, _c, h, *_rest in t.grid} == {0}

    hv_mod.shutdown()
    mesh = build_mesh(jax.devices()[:8], hierarchical=True, dcn_size=2)
    hv_mod.init(mesh=mesh)
    try:
        t2 = Autotuner(Config(autotune=True), steps_per_sample=1)
        assert {h for _t, _c, h, *_rest in t2.grid} == {0, 1}
    finally:
        hv_mod.shutdown()
        hv_mod.init()


def test_autotuner_compression_axis_is_opt_in(monkeypatch):
    from horovod_tpu.collectives.compression import Compression

    t = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {k for _t, _c, _h, k, *_rest in t.grid} == {0}
    assert t.compression_override(Compression.none) is Compression.none

    monkeypatch.setenv("HOROVOD_AUTOTUNE_COMPRESSION", "1")
    t2 = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {k for _t, _c, _h, k, *_rest in t2.grid} == {0, 1, 2, 3}
    # Force a sample on the bf16 / fp8 codecs and check the overrides
    # resolve.
    for want, codec in [(1, Compression.bf16), (3, Compression.fp8)]:
        for i, cfg in enumerate(t2.grid):
            if cfg[3] == want:
                t2._idx = i
                break
        assert t2.compression_override(Compression.none) is codec


def test_autotuner_zero_axis_is_opt_in(monkeypatch):
    """The ZeRO exchange axis only opens on a zero-configured run with
    HOROVOD_AUTOTUNE_ZERO=1; otherwise it is pinned to the configured
    stage (the state layout is fixed at step-build time -- only the
    exchange over the sharded arena is searchable)."""
    t = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert not t.tunes_zero
    assert {z for _t, _c, _h, _k, z, *_rest in t.grid} == {0}

    # Env alone is not enough: a replicated run has no zero exchange.
    monkeypatch.setenv("HOROVOD_AUTOTUNE_ZERO", "1")
    t2 = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert not t2.tunes_zero
    assert {z for _t, _c, _h, _k, z, *_rest in t2.grid} == {0}

    # Zero-configured run without the env: pinned to 1.
    monkeypatch.delenv("HOROVOD_AUTOTUNE_ZERO")
    t3 = Autotuner(Config(autotune=True, zero_stage=1), steps_per_sample=1)
    assert not t3.tunes_zero
    assert {z for _t, _c, _h, _k, z, *_rest in t3.grid} == {1}

    # Both: the axis opens and the accessor tracks the current sample.
    monkeypatch.setenv("HOROVOD_AUTOTUNE_ZERO", "1")
    t4 = Autotuner(Config(autotune=True, zero_stage=1), steps_per_sample=1)
    assert t4.tunes_zero
    assert {z for _t, _c, _h, _k, z, *_rest in t4.grid} == {0, 1}
    for want in (0, 1):
        for i, cfg in enumerate(t4.grid):
            if cfg[4] == want:
                t4._idx = i
                break
        assert t4.zero_stage() == want
        assert t4.trace_key()[3] == want


def test_autotuner_chunk_axis_is_opt_in(monkeypatch):
    """HOROVOD_AUTOTUNE_CHUNK=1 opens the exchange-chunk-size axis
    (trace-time knob: it IS part of the trace key); otherwise the axis is
    pinned to the configured HOROVOD_EXCHANGE_CHUNK_MB value."""
    _MiB = 1 << 20
    t = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {cfg[5] for cfg in t.grid} == {0}
    assert t.exchange_chunk_bytes() == 0

    t1 = Autotuner(Config(autotune=True, exchange_chunk_bytes=8 * _MiB),
                   steps_per_sample=1)
    assert {cfg[5] for cfg in t1.grid} == {8 * _MiB}

    monkeypatch.setenv("HOROVOD_AUTOTUNE_CHUNK", "1")
    t2 = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {cfg[5] for cfg in t2.grid} == {0, 4 * _MiB, 16 * _MiB}
    for want in (0, 4 * _MiB, 16 * _MiB):
        for i, cfg in enumerate(t2.grid):
            if cfg[5] == want:
                t2._idx = i
                break
        assert t2.exchange_chunk_bytes() == want
        assert t2.trace_key()[4] == want  # retrace per chunk size


def test_autotuner_steps_axis_is_opt_in_and_build_time(monkeypatch):
    """HOROVOD_AUTOTUNE_STEPS_PER_EXEC=1 opens the steps-per-execution
    axis.  Unlike every other knob it changes the LOOP INPUT SHAPES
    (stacked batches), so it is a build-time knob and must NOT appear in
    the trace key -- the runner rebuilds, it does not just retrace."""
    t = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {cfg[6] for cfg in t.grid} == {1}
    assert t.steps_per_exec() == 1

    t1 = Autotuner(Config(autotune=True, steps_per_exec=8),
                   steps_per_sample=1)
    assert {cfg[6] for cfg in t1.grid} == {8}

    monkeypatch.setenv("HOROVOD_AUTOTUNE_STEPS_PER_EXEC", "1")
    t2 = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {cfg[6] for cfg in t2.grid} == {1, 4, 16}
    assert len(t2.trace_key()) == 7  # thr,hier,comp,zero,chunk,hc,moe -- no k
    for want in (1, 4, 16):
        for i, cfg in enumerate(t2.grid):
            if cfg[6] == want:
                t2._idx = i
                break
        assert t2.steps_per_exec() == want


def test_autotuner_pr1_log_format_warm_starts(tmp_path):
    """6-column logs from the zero-axis era map onto the chunk=0/steps=1
    plane."""
    log = tmp_path / "pr1.csv"
    cfg = Config(autotune=True, autotune_log=str(log))
    thr = 32 * 1024 * 1024
    log.write_text(
        "fusion_threshold_bytes,cycle_time_ms,hierarchical,compression,"
        "zero,score_bytes_per_s\n"
        f"{thr},{Config().cycle_time},0,0,0,456.0\n")
    t = Autotuner(cfg, steps_per_sample=1)
    assert (thr, Config().cycle_time, 0, 0, 0, 0, 1, 1, 0, 0, 456.0) in [
        tuple(s) for s in t._samples]


def test_hierarchical_allreduce_matches_flat_psum(hvd):
    """The explicit two-level schedule the autotuner can select computes
    the same reduction as the XLA-scheduled both-axes psum."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import horovod_tpu as hv_mod
    from horovod_tpu.collectives import ops as cops
    from horovod_tpu.parallel.mesh import build_mesh

    hv_mod.shutdown()
    mesh = build_mesh(jax.devices()[:8], hierarchical=True, dcn_size=2)
    hv_mod.init(mesh=mesh)
    try:
        axes = tuple(mesh.axis_names)
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(8, 7, 3).astype(np.float32))

        def f(xb):
            flat = cops.allreduce(xb[0], hv_mod.Average, axes=axes)
            hier = cops.hierarchical_allreduce(
                xb[0], hv_mod.Average, dcn_axis=axes[0], ici_axis=axes[1])
            return flat[None], hier[None]

        fs = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(axes), out_specs=(P(axes),) * 2))
        flat, hier = map(np.asarray, fs(x))
        np.testing.assert_allclose(hier, flat, rtol=1e-6, atol=1e-6)
        expect = np.asarray(x).mean(axis=0)
        np.testing.assert_allclose(hier[0], expect, rtol=1e-5, atol=1e-6)
    finally:
        hv_mod.shutdown()
        hv_mod.init()


def test_autotune_e2e_explores_hierarchical_axis(tmp_path, hvd):
    """End-to-end on a (2, 4) mesh: the widened tuner samples both
    hierarchical settings through REAL compiled train steps and locks a
    best configuration (BASELINE BERT-config knob validation at test
    scale -- on one real chip world==1 skips collectives entirely, so
    the virtual mesh is where the knob is exercisable)."""
    import jax
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hv_mod
    from horovod_tpu.core.state import global_state
    from horovod_tpu.parallel.mesh import build_mesh

    hv_mod.shutdown()
    mesh = build_mesh(jax.devices()[:8], hierarchical=True, dcn_size=2)
    hv_mod.init(mesh=mesh)
    st = global_state()
    st.autotuner = Autotuner(Config(autotune=True), steps_per_sample=1,
                             max_samples=6)
    try:
        opt = hv_mod.DistributedOptimizer(optax.sgd(0.05))
        params = hv_mod.replicate(
            {"w": jnp.zeros((6, 4), jnp.float32)}, mesh)
        opt_state = hv_mod.replicate(opt.init(params), mesh)
        step = hv_mod.make_train_step(
            lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2), opt,
            mesh=mesh)
        batch = hv_mod.shard_batch(
            (jnp.ones((16, 6), jnp.float32),
             jnp.ones((16, 4), jnp.float32)), mesh)
        losses = []
        guard = 0
        while not st.autotuner.done and guard < 50:
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
            guard += 1
        assert st.autotuner.done
        sampled_h = {s[2] for s in st.autotuner._samples}
        assert sampled_h == {0, 1}  # both algorithms really ran
        assert losses[-1] < losses[0]
    finally:
        st.autotuner = None
        hv_mod.shutdown()
        hv_mod.init()


def test_autotune_value_demo_selects_modeled_optimum(hvd):
    """The demo (examples/autotune_value_demo.py), run live: under an
    injected per-link bandwidth model on a (2, 4) two-level mesh, a
    cold-start tuner with the compression axis opted in locks
    hierarchical+fp8 when the slow DCN tier rewards them, and rejects
    both when uniform fast links make quantize cost and the extra phase
    pure overhead."""
    import importlib.util
    import os
    import jax
    import horovod_tpu as hv_mod
    from horovod_tpu.parallel.mesh import build_mesh

    spec = importlib.util.spec_from_file_location(
        "autotune_value_demo",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "examples",
            "autotune_value_demo.py"))
    demo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(demo)

    hv_mod.shutdown()
    mesh = build_mesh(jax.devices()[:8], hierarchical=True, dcn_size=2)
    hv_mod.init(mesh=mesh)
    try:
        slow_dcn = demo.run_scenario("contended_dcn")
        assert slow_dcn["selected"] == {"hierarchical": 1, "codec": "fp8"}
        uniform = demo.run_scenario("uniform_fast")
        assert uniform["selected"] == {"hierarchical": 0, "codec": "none"}
        # The model really orders the configs the way the selections say.
        costs = slow_dcn["modeled_ms"]
        assert costs["hier1_fp8"] == min(costs.values())
        costs = uniform["modeled_ms"]
        assert costs["hier0_none"] == min(costs.values())
    finally:
        hv_mod.shutdown()
        hv_mod.init()


@pytest.mark.parametrize("compression, min_samples", [
    # The default exchange is leaf-wise (PR 27): it builds no fusion
    # bucket, every threshold candidate is the same compiled step, so one
    # sample scores them all and the tuner locks.
    ("none", 1),
    # fp8 keeps a scale a bucket: the threshold shapes the step, and the
    # tuner explores it as before.
    ("fp8", 4),
], ids=["leafwise", "packed"])
def test_autotune_e2e_flax_step(hvd, compression, min_samples):
    """Round-5: the tuned wrapper also drives make_flax_train_step (the
    RN50/CNN path used by the on-chip autotune demo) -- the tuner
    consumes steps, explores, and locks; training still converges."""
    import jax
    import jax.numpy as jnp
    import optax
    import flax.linen as nn
    import horovod_tpu as hv_mod
    from horovod_tpu.core.state import global_state
    from horovod_tpu.training import make_flax_train_step

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            return nn.Dense(4)(x)

    st = global_state()
    st.autotuner = Autotuner(Config(autotune=True), steps_per_sample=1,
                             max_samples=4)
    try:
        model = Tiny()
        x = jnp.ones((16, 6), jnp.float32)
        y = jnp.zeros((16,), jnp.int32)
        params = hv_mod.replicate(
            model.init(jax.random.PRNGKey(0), x[:2])["params"])
        opt = hv_mod.DistributedOptimizer(optax.sgd(0.1),
                                          compression=compression)
        opt_state = hv_mod.replicate(opt.init(params))
        step = make_flax_train_step(
            lambda v, xx, train: model.apply(v, xx), opt)
        batch = hv_mod.shard_batch((x, y))
        losses, guard = [], 0
        bs = {}
        while not st.autotuner.done and guard < 40:
            params, bs, opt_state, loss = step(params, bs, opt_state,
                                               batch)
            losses.append(float(loss))
            guard += 1
        assert st.autotuner.done
        samples = st.autotuner._samples
        assert len(samples) >= min_samples
        if compression == "none":
            # No two samples differ in the threshold alone.
            assert len({s[1:-1] for s in samples}) == len(samples)
        assert losses[-1] < losses[0]
    finally:
        st.autotuner = None


def test_autotuner_old_log_format_warm_starts(tmp_path):
    """Pre-round-3 3-column logs still warm-start (mapped to the
    hier=0/comp=default plane)."""
    log = tmp_path / "old.csv"
    cfg = Config(autotune=True, autotune_log=str(log))
    thr = 32 * 1024 * 1024
    log.write_text("fusion_threshold_bytes,cycle_time_ms,score\n"
                   f"{thr},{Config().cycle_time},123.0\n")
    t = Autotuner(cfg, steps_per_sample=1)
    assert (thr, Config().cycle_time, 0, 0, 0, 0, 1, 1, 0, 0, 123.0) in [
        tuple(s) for s in t._samples]


def test_autotuner_microbatch_axis_is_opt_in_and_build_time(monkeypatch):
    """HOROVOD_AUTOTUNE_MICROBATCH=1 opens the microbatch axis.  Like
    steps-per-execution it is a BUILD-TIME knob (it changes the step's
    internal loop structure, so the runner rebuilds) and must NOT appear
    in the trace key."""
    t = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {cfg[7] for cfg in t.grid} == {1}
    assert t.microbatches() == 1

    t1 = Autotuner(Config(autotune=True, microbatches=4),
                   steps_per_sample=1)
    assert {cfg[7] for cfg in t1.grid} == {4}
    assert t1.microbatches() == 4

    monkeypatch.setenv("HOROVOD_AUTOTUNE_MICROBATCH", "1")
    t2 = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {cfg[7] for cfg in t2.grid} == {1, 2, 4}
    assert len(t2.trace_key()) == 7  # no microbatch member
    for want in (1, 2, 4):
        for i, cfg in enumerate(t2.grid):
            if cfg[7] == want:
                t2._idx = i
                break
        assert t2.microbatches() == want


def test_autotuner_microbatch_axis_closed_on_zero_runs(monkeypatch):
    """ZeRO's arena exchange is already shard-based; the microbatch axis
    stays pinned on zero-configured runs even when opted in."""
    monkeypatch.setenv("HOROVOD_AUTOTUNE_MICROBATCH", "1")
    t = Autotuner(Config(autotune=True, zero_stage=1), steps_per_sample=1)
    assert {cfg[7] for cfg in t.grid} == {1}


def test_autotuner_warm_start_skips_unusable_rows(tmp_path):
    """NaN/inf scores and unknown column counts are skipped with a
    counted warning, never fatal; the good rows still warm-start."""
    log = tmp_path / "bad.csv"
    cfg = Config(autotune=True, autotune_log=str(log))
    thr = 32 * 1024 * 1024
    ct = Config().cycle_time
    log.write_text(
        "fusion_threshold_bytes,cycle_time_ms,score\n"
        f"{thr},{ct},nan\n"         # NaN score -> poisons the GP
        f"{thr},{ct},inf\n"         # inf score
        "1,2,3,4\n"                 # unknown column count (4)
        f"{thr},{ct},oops\n"        # non-numeric cell
        f"{thr},{ct},123.0\n")      # good row survives
    with pytest.warns(RuntimeWarning, match="skipped 4 unusable row"):
        t = Autotuner(cfg, steps_per_sample=1)
    assert t.warm_start_skipped == 4
    assert (thr, ct, 0, 0, 0, 0, 1, 1, 0, 0, 123.0) in [
        tuple(s) for s in t._samples]


def test_autotuner_warm_start_clean_log_no_warning(tmp_path):
    log = tmp_path / "clean.csv"
    cfg = Config(autotune=True, autotune_log=str(log))
    thr = 32 * 1024 * 1024
    log.write_text("fusion_threshold_bytes,cycle_time_ms,score\n"
                   f"{thr},{Config().cycle_time},42.0\n")
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", RuntimeWarning)
        t = Autotuner(cfg, steps_per_sample=1)
    assert t.warm_start_skipped == 0


def test_autotuner_moe_axis_is_opt_in_and_trace_time(monkeypatch):
    """HOROVOD_AUTOTUNE_MOE=1 opens the MoE all_to_all codec axis; it is
    TRACE-time (the wire cast is part of the traced step) so it rides
    the trace key, unlike the build-time microbatch/steps axes."""
    t = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert {cfg[9] for cfg in t.grid} == {0}
    assert t.moe_codec() == "none"
    assert not t.tunes_moe

    # Without the opt-in the axis pins to the configured codec.
    t1 = Autotuner(Config(autotune=True, moe_compression="bf16"),
                   steps_per_sample=1)
    assert {cfg[9] for cfg in t1.grid} == {1}
    assert t1.moe_codec() == "bf16"

    monkeypatch.setenv("HOROVOD_AUTOTUNE_MOE", "1")
    t2 = Autotuner(Config(autotune=True), steps_per_sample=1)
    assert t2.tunes_moe
    assert {cfg[9] for cfg in t2.grid} == {0, 1, 2}
    for want, name in ((0, "none"), (1, "bf16"), (2, "fp16")):
        for i, cfg in enumerate(t2.grid):
            if cfg[9] == want:
                t2._idx = i
                break
        assert t2.moe_codec() == name
        assert t2.trace_key()[6] == want  # retrace per MoE codec


def test_autotuner_pr11_log_format_warm_starts(tmp_path):
    """10-column logs from before the MoE-codec axis load onto the
    moe=0 plane (positional compat, no skip and no crash)."""
    log = tmp_path / "pr11.csv"
    cfg = Config(autotune=True, autotune_log=str(log))
    thr = 32 * 1024 * 1024
    ct = Config().cycle_time
    log.write_text(
        "fusion_threshold_bytes,cycle_time_ms,hierarchical,compression,"
        "zero,exchange_chunk_bytes,steps_per_exec,microbatches,"
        "hier_dcn_codec,score_bytes_per_s\n"
        f"{thr},{ct},0,0,0,0,1,1,0,321.0\n")
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", RuntimeWarning)
        t = Autotuner(cfg, steps_per_sample=1)
    assert t.warm_start_skipped == 0
    assert (thr, ct, 0, 0, 0, 0, 1, 1, 0, 0, 321.0) in [
        tuple(s) for s in t._samples]
