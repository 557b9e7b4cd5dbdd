"""The ``ouro_loop`` family and the cell ``ouro_2_6b_math_offline`` at a
size a test run can hold: the ``serve`` kind rehearsed on the CPU over a
tiny looped model, the fp8 control put in the program's place failing
``served_logit_gap_max`` where the sound program passes, the reference's
exit rule, the seeded draw of what ``config.json`` gives no values for,
the family's byte counts against ``param_shapes``, the configuration's
file against the published sizes, and the new reader on a synthetic
trace.  No number here is a device metric."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import ouro_loop as family
from benchmarks.kinds import serve
from benchmarks.lib import checks, loadgen, peaks, validate, weights, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "ouro_2_6b_math_offline"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY_OURO = {
    "kind": "serve", "family": "ouro_loop", "vocab_size": 256,
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "total_ut_steps": 3, "early_exit_threshold": 1, "rope_theta": 1e6,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 128,
    "compute_dtype": "float32",
    "serving": {"slots": 4, "page_size": 8, "max_len": 64},
    "limits": {"served_logit_gap_max": 1e-3}}
TINY_MATH = {"arrival": "at_zero", "order": "fixed",
             "prompt_lens": [8, 16], "output_lens": [8, 16],
             "num_requests": 10, "trace_rounds": 4}


def _ctx(config, traffic, seed=2 ** 31 + 7, seconds=0.5, control=""):
    data = {"cell": {"name": "tiny"}, "config": config, "traffic": traffic}
    logs = []
    ctx = bench_run.make_context(data, seed, seconds, "",
                                 jax.devices()[:1], family, logs.append)
    ctx.with_control = control
    return ctx, logs


def _seeded(seed, config=TINY_OURO):
    from horovod_tpu.serving import loop_dense
    cfg = family.program_config(config)
    shapes = loop_dense.param_shapes(cfg, jnp.float32)
    return cfg, family.seeded_assumptions(
        weights.make_weights(seed, shapes, jnp.float32), seed)


def test_serve_kind_tiny_on_the_looped_family():
    ctx, logs = _ctx(TINY_OURO, TINY_MATH)
    out = serve.run(ctx)
    assert out["attempted"] == 10 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    assert checks.all_ok(out["checks"]), [c.line() for c in out["checks"]]
    by_name = {c.name: c.value for c in out["checks"]}
    assert by_name["pool_pages_left_live"] == 0
    assert by_name["compilations_inside_window"] == 0
    assert by_name["served_logit_gap_max"] < 1e-4


def test_serve_kind_catches_an_altered_token_of_the_looped_family(
        monkeypatch):
    from horovod_tpu.serving import engine
    real = engine.greedy_sample
    monkeypatch.setattr(engine, "greedy_sample",
                        lambda logits: (real(logits) + 1) % 256)
    ctx, _ = _ctx(TINY_OURO, TINY_MATH)
    out = serve.run(ctx)
    by_name = {c.name: c for c in out["checks"]}
    assert not by_name["served_logit_gap_max"].ok


def test_serve_kind_catches_a_pass_that_reads_another_passes_plane(
        monkeypatch):
    """The timed path broken underneath: every pass reads the last pass's
    keys and values (a quarter of the cache).  ``correct`` comes out
    false by the same limit."""
    from horovod_tpu.serving import loop_dense
    monkeypatch.setattr(loop_dense, "_plane", lambda first, li: 2 * 2 + li)
    ctx, _ = _ctx(TINY_OURO, TINY_MATH)
    out = serve.run(ctx)
    by_name = {c.name: c for c in out["checks"]}
    assert not by_name["served_logit_gap_max"].ok


def _greedy(params, cfg, prompt, n, pad=32):
    """``n`` greedy tokens after ``prompt`` from the program's prefill, one
    compiled length (causal: padding on the right changes no earlier
    row)."""
    from horovod_tpu.serving import loop_dense
    forward = jax.jit(lambda p, t: loop_dense.prefill_forward(
        p, cfg, t, last_only=False)[0])
    served = []
    for _ in range(n):
        ctx = np.zeros((pad,), np.int32)
        ctx[:len(prompt) + len(served)] = np.concatenate(
            [prompt, np.asarray(served, int)])
        logits = forward(params, jnp.asarray(ctx)[None])
        served.append(int(jnp.argmax(
            logits[0, len(prompt) + len(served) - 1])))
    return served


def test_fp8_control_fails_the_served_comparison_of_the_looped_family():
    """The plain reference in the program's place, computed in fp8: its
    first token lies far below the float32 reference's best, where the
    sound float32 program's lies at it.  Two seeds, one above 2**31."""
    worst_sound, least_control = 0.0, np.inf
    for seed in (5, 2 ** 31 + 6):
        cfg, params = _seeded(seed)
        rng = np.random.RandomState(seed % 1000)
        sample = []
        for n in (12, 20):
            prompt = rng.randint(0, 256, size=n)
            sample.append((prompt, _greedy(params, cfg, prompt, 6)))
        gaps = family.served_gaps(TINY_OURO, params, sample, 32,
                                  with_control=True)
        assert gaps["tokens_compared"] == 12
        worst_sound = max(worst_sound, gaps["served_logit_gap_max"])
        least_control = min(least_control, gaps["control_logit_gap_max"])
    assert worst_sound < 1e-4
    assert least_control > 0.01 and least_control > 100 * worst_sound


def test_the_reference_reads_a_row_out_at_the_pass_its_exit_rule_names():
    """At the published threshold of 1 every row is read out after the
    last pass; the rule itself (the first pass whose running sum of ``p``
    reaches the threshold) is the reference's for any threshold."""
    _, params = _seeded(7)
    ctx = np.random.RandomState(1).randint(0, 256, size=24)
    ref = family.Reference(TINY_OURO, params, 32)
    hs, p = ref.forward(ctx)
    assert p.shape == (3, 32)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-12)
    head = params["params"]["lm_head"]["kernel"]
    last = np.asarray(ref._readout(hs[-1][:24], head))
    np.testing.assert_array_equal(np.asarray(ref.logits(ctx, 0, 24)), last)
    early = family.Reference(dict(TINY_OURO, early_exit_threshold=0.5),
                             params, 32)
    got = np.asarray(early.logits(ctx, 0, 24))
    at = np.argmax(np.cumsum(p[:, :24], axis=0) >= 0.5, axis=0)
    assert len(set(at.tolist())) > 1           # rows leave at different passes
    for row, t in enumerate(at):
        np.testing.assert_array_equal(
            got[row], np.asarray(ref._readout(hs[t][row:row + 1], head))[0])
    # The exit distribution as the paper writes it.
    leave = np.asarray([[0.25, 0.5], [0.5, 0.5], [0.9, 0.1]])
    np.testing.assert_allclose(
        family.exit_distribution(leave),
        [[0.25, 0.5], [0.375, 0.25], [0.375, 0.25]])


def test_the_seeded_draw_puts_every_norm_and_the_bias_off_identity():
    """``lib/weights.py`` draws a scale at one and a bias at zero; the
    family puts each 0.1 off it, another draw a seed and a leaf, and
    leaves the kernels as they were drawn."""
    from horovod_tpu.serving import loop_dense
    cfg, a = _seeded(3)
    _, b = _seeded(4)
    plain = weights.make_weights(3, loop_dense.param_shapes(
        cfg, jnp.float32), jnp.float32)
    blk = a["params"]["layer_1"]
    for name in loop_dense.NORMS:
        off = np.asarray(blk[name]["scale"], np.float64) - 1.0
        assert np.std(off) == pytest.approx(family.SPREAD, rel=0.3), name
        assert not np.array_equal(
            off, np.asarray(b["params"]["layer_1"][name]["scale"]) - 1.0)
    assert not np.array_equal(np.asarray(blk["attn_norm"]["scale"]),
                              np.asarray(blk["mlp_norm"]["scale"]))
    off = np.asarray(a["params"]["final_norm"]["scale"], np.float64) - 1.0
    assert np.std(off) == pytest.approx(family.SPREAD, rel=0.3)
    bias = float(a["params"]["exit_gate"]["bias"][0])
    assert 0.0 < abs(bias) < 0.5
    np.testing.assert_array_equal(
        np.asarray(blk["attn"]["wq"]["kernel"]),
        np.asarray(plain["params"]["layer_1"]["attn"]["wq"]["kernel"]))
    assert float(jnp.std(blk["mlp"]["w_down"]["kernel"])) == pytest.approx(
        96 ** -0.5, rel=0.05)


# -- the configuration and the counts, by hand --------------------------------------

def test_the_configuration_keeps_every_published_size():
    data = bench_run.load_cell(ROOT, CELL)
    config, cell = data["config"], data["cell"]
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f
                    if '"name": "Ouro-2.6B"' in line]
    for row in rows:                      # the catalog, where it is there
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    for key, want in {
            "hidden_size": 2048, "num_hidden_layers": 48,
            "num_attention_heads": 16, "num_key_value_heads": 16,
            "head_dim": 128, "intermediate_size": 5632,
            "vocab_size": 49152, "rms_norm_eps": 1e-6,
            "rope_theta": 1000000, "rope_scaling": None,
            "total_ut_steps": 4, "early_exit_threshold": 1,
            "tie_word_embeddings": False, "use_sliding_window": False,
            "model_type": "ouro"}.items():
        assert config[key] == want, key
    assert config["reduced"] == [] and "published" not in config
    assert cell["chips"] == 1 and cell["traffic"] == "offline_math_lengths"
    t = data["traffic"]
    assert (t["prompt_lens"], t["prompt_weights"]) == ([64, 128], [0.5, 0.5])
    assert (t["output_lens"], t["output_weights"]) == ([64, 128], [0.5, 0.5])
    assert t["arrival"] == "at_zero" and t["order"] == "fixed"
    assert t["trace_rounds"] == 40
    assert "prefix_share" not in t and "session_share" not in t
    s = config["serving"]
    assert s["max_len"] == max(t["prompt_lens"]) + max(t["output_lens"])
    # Three waves of the slots at least, in a 30 s window.
    assert loadgen.num_requests(t, 30.0) >= 3 * s["slots"]
    entry = {c["name"]: c for c in data["bench"]["configs"]}["ouro_2_6b"]
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    # Every choice config.json does not fix is written down.
    for key in ("equations", "norm_placement", "no_biases",
                "final_norm_between_passes", "exit_gate", "rope_pairing",
                "softmax_scale", "own_cache_a_pass",
                "seeded_norms_and_bias"):
        assert key in config["assumed"], key
    assert config["departures"] and config["deployment"]
    assert set(config["limits"]) == {"served_logit_gap_max"}
    assert "served_logit_gap_max" in config["limits_why"]
    cfg = family.program_config(config)
    assert cfg.passes == 4 and cfg.exit_threshold == 1.0
    assert cfg.layer_spec().planes == 192


def test_byte_counts_against_param_shapes():
    from horovod_tpu.serving import loop_dense
    config = bench_run.load_cell(ROOT, CELL)["config"]
    # A cached token: 192 planes of 2 x (2,048 keys + 2,048 values) bytes.
    assert family.kv_bytes_per_token(config) == 1_572_864 == 192 * 8192
    assert family.layer_weight_bytes(config) == 4_933_287_936
    assert family.weight_bytes_per_round(config) == \
        4 * 4_933_287_936 + 201_326_592
    cfg = family.program_config(config)
    assert cfg.page_width == 4096
    shapes = loop_dense.param_shapes(cfg, jnp.bfloat16)
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert total == 2_667_974_657                      # the published "2.6B"
    assert family.weight_bytes(config) == 2 * total == 5_335_949_314
    layer = sum(int(np.prod(s.shape)) for s in
                jax.tree.leaves(shapes["params"]["layer_0"]))
    assert layer == 51_388_416
    assert total == 48 * layer + 201_326_592 + 2048 + 2048 + 1
    s = config["serving"]
    pages = s["slots"] * s["max_len"] // s["page_size"] + 1
    pool = pages * s["page_size"] * family.kv_bytes_per_token(config)
    assert pages == 321 and pool == 8_078_229_504
    # Weights and pool leave room for a prefill's rows and XLA.
    assert 13.3e9 < family.weight_bytes(config) + pool < 13.5e9


def test_the_cell_lists_its_metrics_and_each_has_a_reader():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    traced = validate.expected_metrics(bench, CELL, True)
    assert set(validate.expected_metrics(bench, CELL, False)) == {
        "serve_tokens_per_s", "setup_s"}
    assert set(traced) == {
        "serve_tokens_per_s", "setup_s", "batch_occupancy_pct",
        "decode_step_ms.offline", "device_idle_pct.offline",
        "round_idle_ms.prepare", "round_idle_ms.fetch",
        "round_idle_ms.bookkeep", "round_idle_ms.between",
        "decode_attn_roofline", "loop_step_roofline"}
    # A share that 40 traced rounds of this traffic need not hold: the
    # requests all start at t = 0 and are 64 or 128 tokens long, so slots
    # free in bands 64 rounds apart and the trace can fall between two.
    assert "prefill_share_pct.offline" not in traced
    for name in set(traced) - {"serve_tokens_per_s", "setup_s"}:
        assert callable(bench_run.reader_for(name).read)
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [(m["name"], m["unit"], m["source"], m["layer"], m["moves"])
            for m in new] == [("loop_step_roofline", "%", "device_trace",
                               "decode step", "serve_tokens_per_s")]
    assert bench["per_layer"][-1]["name"] == "loop_step_roofline"
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "ouro_2_6b"
    # One more cell on one chip; the four-chip cell is still the only one.
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "bert_large_dp4"]
    assert len(bench["workloads"]) == 7


# -- the new reader on a synthetic trace ----------------------------------------------

def _trace(rounds, round_ns, other=()):
    """A device plane whose modules line holds ``rounds`` events of the
    looped decode program, ``round_ns`` each, a prefill between them."""
    modules, t = [], 1000
    for i in range(rounds):
        modules.append(xplane.Event(
            f"jit_loop_dense_step({i})", t, t + round_ns))
        t += round_ns + 500
        if i == 0:
            modules.append(xplane.Event("jit__prefill(7)", t, t + 9000))
            t += 9500
    modules += list(other)
    ops = [xplane.Event("%fusion.1 = f32[8] fusion()", m.start_ns,
                        m.end_ns) for m in modules]
    return xplane.Trace(devices=[xplane.DevicePlane(0, ops, modules)],
                        host=[])


def _reader_ctx(trace, counters, fam=family):
    return types.SimpleNamespace(
        trace=trace, counters=counters, family=fam,
        config=bench_run.load_cell(ROOT, CELL)["config"],
        peaks=peaks.peaks_for("TPU v5 lite"), metric=None,
        log=lambda msg: None)


def test_loop_step_roofline_on_a_synthetic_trace():
    read = bench_run.reader_for("loop_step_roofline").read
    # 40 rounds of 60 ms over 2,900 live tokens a round.
    live = 40 * 2900
    got = read(_reader_ctx(_trace(40, 60_000_000),
                           {"traced_live_tokens": live}))
    least_bytes = 40 * (4 * 4_933_287_936 + 201_326_592) + live * 1_572_864
    want = 100.0 * (least_bytes / 819e9) / (40 * 0.060)
    assert got == pytest.approx(want, rel=1e-12)
    assert 49.0 < got < 51.0
    # The weights' four streams alone: 24.3 ms of a 60 ms round.
    bare = read(_reader_ctx(_trace(40, 60_000_000),
                            {"traced_live_tokens": 1}))
    assert bare == pytest.approx(100.0 * 24.34 / 60.0, rel=2e-3)
    # A program at the floor reads 100 and no more.
    at_floor = read(_reader_ctx(
        _trace(2, int(round(least_bytes / 40 / 819e9 * 1e9))),
        {"traced_live_tokens": 2 * 2900}))
    assert at_floor == pytest.approx(100.0, rel=1e-6)


def test_loop_step_roofline_reads_nothing_where_there_is_nothing():
    """On a program without a looped step (the parent, another family's
    trace), with no live token counted, or with no event of the decode
    program in the trace, the reader returns None and does not raise."""
    from benchmarks.families import llama_dense, zaya_cca_moe
    read = bench_run.reader_for("loop_step_roofline").read
    trace = _trace(3, 1_000_000)
    for fam in (llama_dense, zaya_cca_moe):
        assert read(_reader_ctx(trace, {"traced_live_tokens": 100},
                                fam)) is None
    assert read(_reader_ctx(trace, {})) is None
    assert read(_reader_ctx(trace, {"traced_live_tokens": 0})) is None
    empty = xplane.Trace(devices=[xplane.DevicePlane(
        0, [xplane.Event("%fusion.1 = f32[8] fusion()", 0, 10)],
        [xplane.Event("jit_spmd(1)", 0, 10)])], host=[])
    assert read(_reader_ctx(empty, {"traced_live_tokens": 100})) is None


def test_the_accepted_readers_find_the_looped_program_by_the_familys_names():
    """``decode_step_ms`` and ``prefill_share_pct`` go by the family's
    module names; ``decode_attn_roofline`` by the Mosaic calls inside the
    decode program against the family's bytes a token."""
    trace = _trace(4, 50_000_000)
    dev = trace.devices[0]
    dev.ops.append(xplane.Event(
        '%hvd_cca_decode.3 = f32[20,16,128] custom-call(), '
        'custom_call_target="tpu_custom_call"', 2000, 2000 + 20_000_000))
    dev.ops.sort(key=lambda e: e.start_ns)
    ctx = _reader_ctx(trace, {"traced_live_tokens": 4 * 2000})
    assert bench_run.reader_for("decode_step_ms").read(ctx) == 50.0
    share = bench_run.reader_for("prefill_share_pct").read(ctx)
    assert 0 < share < 1
    attn = bench_run.reader_for("decode_attn_roofline").read(ctx)
    assert attn == pytest.approx(
        100.0 * (4 * 2000 * 1_572_864 / 819e9) / 0.020, rel=1e-9)
