"""The ``exaone_swa_moe`` family and the cell ``k_exaone_236b_mixed_offline``
at a size a test run can hold: the ``serve`` kind rehearsed on the CPU
over a tiny model of window and full attention layers that holds a share
of its experts, the fp8 control failing ``served_logit_gap_max`` where
the sound program passes, the family's counts by hand, the
configuration's file against the published widths, the cell found by
name from data alone, and the readers it brings or joins on a synthetic trace.  No
number here is a device metric."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_synthetic import threads_for
from benchmarks import run as bench_run
from benchmarks.families import exaone_swa_moe as family
from benchmarks.kinds import serve
from benchmarks.lib import checks, hostspans, loadgen, peaks, validate
from benchmarks.lib import weights, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "k_exaone_236b_mixed_offline"

TINY_EXAONE = {
    "kind": "serve", "family": "exaone_swa_moe", "vocab_size": 128,
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "sliding_window": 8,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "rms_norm_eps": 1e-5, "max_position_embeddings": 128,
    "published": {"vocab_size": 256, "num_experts": 16},
    "share": {"first_expert": 8, "experts_held": 4},
    "compute_dtype": "float32",
    "serving": {"slots": 4, "page_size": 4, "max_len": 64},
    "limits": {"served_logit_gap_max": 1e-3, "routing_margin_min": 0.0,
               "routing_branches_max": 1}}
TINY_MIXED = {"arrival": "at_zero", "order": "fixed",
              "prompt_lens": [8, 16, 40], "prompt_weights": [0.5, 0.3, 0.2],
              "output_lens": [4, 8, 20], "output_weights": [0.4, 0.4, 0.2],
              "num_requests": 12, "trace_from_round": 2, "trace_rounds": 4}


def _ctx(config, traffic, seed=2 ** 31 + 7, seconds=0.5, control=""):
    data = {"cell": {"name": "tiny"}, "config": config, "traffic": traffic}
    logs = []
    ctx = bench_run.make_context(data, seed, seconds, "",
                                 jax.devices()[:1], family, logs.append)
    ctx.with_control = control
    return ctx, logs


# -- the rehearsal: the serve kind over the new family ---------------------------

def test_serve_kind_tiny_on_the_new_family():
    ctx, logs = _ctx(TINY_EXAONE, TINY_MIXED)
    out = serve.run(ctx)
    assert out["attempted"] == 12 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    assert checks.all_ok(out["checks"]), [c.line() for c in out["checks"]]
    by_name = {c.name: c.value for c in out["checks"]}
    # Pages of BOTH groups of planes are back (Program.pool_drained).
    assert by_name["pool_pages_left_live"] == 0
    assert by_name["compilations_inside_window"] == 0


def test_serve_kind_catches_an_altered_token_of_the_new_family(monkeypatch):
    from horovod_tpu.serving import engine
    real = engine.greedy_sample
    monkeypatch.setattr(engine, "greedy_sample",
                        lambda logits: (real(logits) + 1) % 128)
    ctx, _ = _ctx(TINY_EXAONE, TINY_MIXED)
    out = serve.run(ctx)
    by_name = {c.name: c for c in out["checks"]}
    assert not by_name["served_logit_gap_max"].ok


def test_serve_kind_catches_a_window_plane_left_live(monkeypatch):
    """A window page that is not returned fails ``pool_pages_left_live``:
    the family's drain check counts both groups."""
    from horovod_tpu.serving import kvcache
    real = kvcache.PagedKVCache.free_slot

    def leaky(self, slot):
        held = int(self._wallocated[slot])
        real(self, slot)
        if held:
            self._wfree.pop()
            self._wallocated[slot] = 1
    monkeypatch.setattr(kvcache.PagedKVCache, "free_slot", leaky)
    ctx, _ = _ctx(TINY_EXAONE, dict(TINY_MIXED, num_requests=3))
    out = serve.run(ctx)
    by_name = {c.name: c for c in out["checks"]}
    assert not by_name["pool_pages_left_live"].ok


def _greedy(params, cfg, prompt, n, pad=48):
    from horovod_tpu.serving import swa_moe
    forward = jax.jit(lambda p, t: swa_moe.prefill_forward(
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


def test_fp8_control_fails_the_served_comparison_of_the_new_family():
    """The plain reference in the program's place, computed in fp8: its
    first token lies far below the float32 reference's best, where the
    sound float32 program's lies at it.  Two seeds, one above 2**31."""
    from horovod_tpu.serving import swa_moe
    cfg = family.program_config(TINY_EXAONE)
    shapes = swa_moe.param_shapes(cfg, jnp.float32)
    worst_sound, least_control = 0.0, np.inf
    for seed in (5, 2 ** 31 + 6):
        params = family.seeded_head_norms(family.fan_in_experts(
            weights.make_weights(seed, shapes, jnp.float32)), seed)
        rng = np.random.RandomState(seed % 1000)
        sample = []
        for n in (12, 30):
            prompt = rng.randint(0, 128, size=n)
            sample.append((prompt, _greedy(params, cfg, prompt, 6)))
        gaps = family.served_gaps(TINY_EXAONE, params, sample, 48,
                                  with_control=True)
        assert gaps["tokens_compared"] == gaps["tokens_sampled"] == 12
        worst_sound = max(worst_sound, gaps["served_logit_gap_max"])
        least_control = min(least_control, gaps["control_logit_gap_max"])
    assert worst_sound < 1e-3
    assert least_control > 2e-3 and least_control > 20 * worst_sound


def test_seeded_head_norms_stand_off_one():
    from horovod_tpu.serving import swa_moe
    cfg = family.program_config(TINY_EXAONE)
    shapes = swa_moe.param_shapes(cfg, jnp.float32)
    params = family.seeded_head_norms(
        weights.make_weights(3, shapes, jnp.float32), 3)
    again = family.seeded_head_norms(
        weights.make_weights(3, shapes, jnp.float32), 3)
    seen = []
    for li in range(4):
        attn = params["params"][f"layer_{li}"]["attn"]
        for key in ("q_norm", "k_norm"):
            scale = np.asarray(attn[key]["scale"])
            assert scale.shape == (16,)
            assert 0.02 < np.abs(scale - 1.0).mean() < 0.3
            np.testing.assert_array_equal(scale, np.asarray(
                again["params"][f"layer_{li}"]["attn"][key]["scale"]))
            seen.append(scale)
        assert np.asarray(params["params"][f"layer_{li}"][
            "attn_norm"]["scale"]).tolist() == [1.0] * 64
    assert len({s.tobytes() for s in seen}) == 8


# -- the configuration's file -----------------------------------------------------

def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide")
    with open(path) as f:
        return next(json.loads(line) for line in f
                    if '"K-EXAONE-236B-A23B"' in line)


def test_the_configuration_keeps_every_published_width():
    data = bench_run.load_cell(ROOT, CELL)
    config, entry = data["config"], next(
        c for c in data["bench"]["configs"]
        if c["name"] == "k_exaone_236b_a23b")
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "sliding_windows", "num_experts", "vocab_size",
        "num_nextn_predict_layers"]
    row = _catalog()
    assert entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    # The cut: depth to two whole periods after the dense layer, a share
    # of the experts and of the vocabulary; the published counts beside.
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["num_experts"] == 128
    assert config["published"]["vocab_size"] == 153600 == 8 * 19200
    assert config["published"]["num_nextn_predict_layers"] == 1
    assert config["layer_types"] == row["config"]["layer_types"][:8]
    assert config["mlp_layer_types"] == row["config"]["mlp_layer_types"][:8]
    assert config["sliding_windows"] == [128, 128, 128, 0] * 2
    assert config["share"] == {
        "chips_a_layer": 8, "this_chip": 0, "first_expert": 0,
        "experts_held": 16, "router_width": 128, "first_vocab_row": 0,
        "vocab_rows_held": 19200}
    # No width is cut, and the floors of the guide hold.
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"]) == (
        6144, 64, 8, 128, 18432, 2048, 8, 128)
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert config["num_hidden_layers"] - 1 >= 4
    for key in ("qk_norm", "rope_layers", "norm_placement",
                "e_score_correction_bias", "window_edge", "expert_weights",
                "routing_margin_min"):
        assert key in config["assumed"], key
    assert "eight" in config["deployment"].lower()
    assert any("multi-token-prediction" in d for d in config["departures"])
    assert set(config["limits"]) == {
        "served_logit_gap_max", "routing_margin_min", "routing_branches_max"}


def test_counts_by_hand_and_against_param_shapes():
    from horovod_tpu.serving import swa_moe
    config = bench_run.load_cell(ROOT, CELL)["config"]
    assert family.expert_bytes(config) == 75_497_472
    assert family.kv_row_bytes(config) == 4096
    assert (family.window_layers(config), family.full_layers(config),
            family.moe_layers(config)) == (6, 2, 7)
    cfg = family.program_config(config)
    assert cfg.attn_kinds == ("window",) * 3 + ("full",) + (
        "window",) * 3 + ("full",)
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert) == (
        128, 16, 0)
    assert (cfg.vocab_size, cfg.vocab_held) == (153600, 19200)
    shapes = swa_moe.param_shapes(cfg, jnp.bfloat16)
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert family.weight_bytes(config) == 2 * total == 11_958_699_776
    # ISSUE 39's arithmetic: the matrices alone are 11,958,484,992 bytes;
    # norms and the routers' biases are the rest.
    assert 2 * total - 11_958_484_992 == 2 * (
        8 * (2 * 6144 + 2 * 128) + 7 * 128 + 6144)
    s = config["serving"]
    pools = 2 * 2 * (
        2 * (s["slots"] * s["max_len"] // s["page_size"] + 1)
        + 6 * (s["slots"] * 9 + 1)) * s["page_size"] * 1024
    assert pools == 2_529_689_600
    assert 14.4e9 < family.weight_bytes(config) + pools < 14.6e9
    # One window layer over 8,192 tokens: the band, not the triangle.
    cost = family.swa_prefill_cost(config, 8192)
    pairs = 128 * 129 // 2 + (8192 - 128) * 128
    assert cost == {"flops": 4 * 64 * 128 * pairs,
                    "bytes": 2 * 8192 * 128 * 2 * 72}
    assert cost["flops"] * 30 < 4 * 64 * 128 * 8192 * 8193 // 2
    assert family.swa_prefill_cost(config, 100)["flops"] \
        == 4 * 64 * 128 * 100 * 101 // 2


def test_the_cell_lists_its_metrics_and_each_has_a_reader():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    traced = validate.expected_metrics(bench, CELL, True)
    assert set(validate.expected_metrics(bench, CELL, False)) == {
        "serve_tokens_per_s", "setup_s"}
    for name in ("swa_decode_roofline", "full_decode_roofline",
                 "swa_prefill_roofline", "moe_held_touched_pct",
                 "moe_gmm_roofline", "moe_gmm_ms_per_round",
                 "decode_step_ms.offline", "batch_occupancy_pct",
                 "device_idle_pct.offline", "round_idle_ms.prepare",
                 "round_idle_ms.fetch", "round_idle_ms.bookkeep",
                 "round_idle_ms.between", "round_period_ms.offline",
                 "prefill_stall_ms.offline", "prefill_share_pct.offline",
                 "loop_host_ms_per_round.offline"):
        assert name in traced, name
        assert callable(bench_run.reader_for(name).read)
    # Every Mosaic call of the round against bytes that follow the live
    # tokens; all the experts where a share is held.
    assert "decode_attn_roofline" not in traced
    assert "moe_experts_touched_pct" not in traced
    # (No list is pinned to this cell alone: a later cell may join one.)
    new = {m["name"]: m for m in bench["per_layer"]
           if CELL in m.get("workloads", ())}
    for name in ("swa_decode_roofline", "full_decode_roofline",
                 "swa_prefill_roofline", "moe_held_touched_pct"):
        assert new[name]["moves"] == "serve_tokens_per_s"
    # The full layers' walk is ZAYA's and Mistral's kernel, and its share
    # is the accepted reader's under a second name.
    from benchmarks.readers import cca_decode_roofline
    assert bench_run.reader_for("full_decode_roofline").read \
        is cca_decode_roofline.read
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == "k_exaone_236b_a23b"
    assert len(bench["workloads"]) >= 8 and len(bench["configs"]) >= 6


def test_the_traffic_is_short_and_long_in_one_queue():
    traffic = bench_run.load_cell(ROOT, CELL)["traffic"]
    assert (traffic["arrival"], traffic["order"]) == ("at_zero", "fixed")
    assert traffic["prompt_lens"] == [512, 2048, 8192]
    assert traffic["prompt_weights"] == [0.5, 0.3, 0.2]
    assert traffic["output_lens"] == [256, 512, 1024]
    assert traffic["output_weights"] == [0.4, 0.4, 0.2]
    a = loadgen.generate(traffic, 5, 30.0, 19200)
    b = loadgen.generate(traffic, 2 ** 31 + 9, 30.0, 19200)
    assert [(len(r.prompt), r.max_new_tokens) for r in a] \
        == [(len(r.prompt), r.max_new_tokens) for r in b]
    assert all(r.arrival_s == 0.0 for r in a)
    assert max(int(r.prompt.max()) for r in a) < 19200
    lens = [len(r.prompt) for r in a]
    assert 2400 < sum(lens) / len(lens) < 2620
    assert max(len(r.prompt) + r.max_new_tokens for r in a) == 9216
    assert not any((a[i].prompt != b[i].prompt).sum() == 0
                   for i in range(len(a)))
    first, n = serve.traced_window(traffic)
    assert n == 100 and first >= 0


# -- the cell, found by name from data alone -----------------------------------------

def test_the_cell_is_found_by_name_and_prints_nothing_without_a_tpu():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", (
            "import json, sys; sys.path.insert(0, '.')\n"
            "from benchmarks import run\n"
            f"d = run.load_cell('.', '{CELL}')\n"
            "import importlib\n"
            "fam = importlib.import_module('benchmarks.families.' "
            "+ d['config']['family'])\n"
            "print(json.dumps({'family': fam.__name__, "
            "'kind': d['config']['kind'], "
            "'traffic': d['cell']['traffic'], "
            "'module': fam.DECODE_MODULE}))\n")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"family": "benchmarks.families.exaone_swa_moe",
                   "kind": "serve", "traffic": "offline_mixed_lengths",
                   "module": r"^jit_swa_moe_step\("}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "TPU" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


# -- the three new readers, and the accepted walk reader, on a synthetic trace ------

ROUNDS, ROUND_NS = 10, 17_000_000
LIVE, WINDOWED, TOUCHED = 80_000, 32 * 128, 98


def _trace(swa_ns=60_000, full_ns=500_000, gmm_ns=800_000,
           prefill_ns=4_000_000, prompt=8192):
    """A device plane of ``ROUNDS`` decode programs, in each six
    ``hvd_swa_decode`` calls, two ``hvd_cca_decode`` and fourteen
    ``hvd_moe_gmm``; after the first round one prefill program with six
    ``hvd_flash_swa_fwd`` calls over ``prompt`` tokens and two
    ``hvd_flash_fwd``."""
    modules, ops, t = [], [], 1000

    def call(name, shape, at, ns):
        ops.append(xplane.Event(
            f"%{name} = {shape} custom-call(), "
            'custom_call_target="tpu_custom_call"', at, at + ns))
        return at + ns + 10

    for i in range(ROUNDS):
        modules.append(xplane.Event(f"jit_swa_moe_step({i})", t,
                                    t + ROUND_NS))
        at = t + 100
        for j in range(6):
            at = call(f"hvd_swa_decode.{j}", "f32[32,64,128]{2,1,0}", at,
                      swa_ns)
        for j in range(2):
            at = call(f"hvd_cca_decode.{j}", "f32[32,64,128]{2,1,0}", at,
                      full_ns)
        for j in range(14):
            at = call(f"hvd_moe_gmm.{j}", "bf16[304,2048]{1,0}", at, gmm_ns)
        t += ROUND_NS + 500
        if i == 0:
            modules.append(xplane.Event("jit__prefill(7)", t,
                                        t + 40_000_000))
            at = t + 100
            for j in range(6):
                at = call(f"hvd_flash_swa_fwd.{j}",
                          f"bf16[1,64,{prompt},128]{{3,2,1,0}}", at,
                          prefill_ns)
            for j in range(2):
                at = call(f"hvd_flash_fwd.{j}",
                          f"bf16[1,64,{prompt},128]{{3,2,1,0}}", at,
                          17_000_000)
            t += 40_000_500
    ops.sort(key=lambda e: e.start_ns)
    return xplane.Trace(devices=[xplane.DevicePlane(0, ops, modules)],
                        host=[])


def _reader_ctx(trace, fam=family, spans=True, tells=True):
    threads = threads_for(trace, family.DECODE_MODULE, LIVE, 32)
    if spans:
        for s in threads[0]:
            if s.name == "decode.round":
                s.stats.update(window_tokens=WINDOWED, window_pages=288)
    if tells:
        threads[0] += [hostspans.Span(
            "decode.bookkeep", s.end_ns - 20, s.end_ns - 10,
            {"round": s.stats["round"], "experts_touched": TOUCHED})
            for s in list(threads[0]) if s.name == "decode.sample_fetch"]
        threads[0].sort(key=lambda s: (s.start_ns, -s.end_ns))
    return types.SimpleNamespace(
        trace=trace, threads=threads, counters={}, family=fam,
        config=bench_run.load_cell(ROOT, CELL)["config"],
        peaks=peaks.peaks_for("TPU v5 lite"), metric=None,
        cell={"name": CELL}, log=lambda msg: None)


def _read(metric, ctx):
    return bench_run.reader_for(metric).read(ctx)


def test_new_readers_on_a_synthetic_trace():
    ctx = _reader_ctx(_trace())
    # Six window layers read 32 slots x 128 tokens x 4,096 bytes a round.
    swa = _read("swa_decode_roofline", ctx)
    assert swa == pytest.approx(
        100.0 * (6 * WINDOWED * 4096 / 819e9) / (6 * 60e-6), rel=1e-9)
    assert 34.0 < swa < 34.5
    # Two full layers read every live token's 4,096 bytes: the accepted
    # reader over the family's ``kv_bytes_per_token``.
    assert family.kv_bytes_per_token(ctx.config) == 2 * 4096
    full = _read("full_decode_roofline", ctx)
    assert full == _read("cca_decode_roofline", ctx)
    assert full == pytest.approx(
        100.0 * (2 * LIVE * 4096 / 819e9) / (2 * 500e-6), rel=1e-9)
    assert 79.0 < full < 81.0
    # One 8,192-token prefill: six banded calls of 4 ms against the rows'
    # bytes (0.37 ms: more than the band's 0.17 ms of products).
    cost = family.swa_prefill_cost(ctx.config, 8192)
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12
    pre = _read("swa_prefill_roofline", ctx)
    assert pre == pytest.approx(
        100.0 * (cost["bytes"] / 819e9) / 4e-3, rel=1e-9)
    assert 9.0 < pre < 9.5
    # 98 of the 7 x 16 held experts a round.
    assert _read("moe_held_touched_pct", ctx) == pytest.approx(87.5)
    # The accepted readers take the family's names too.
    gmm = _read("moe_gmm_roofline", ctx)
    assert gmm == pytest.approx(
        100.0 * (TOUCHED * 75_497_472 / 819e9) / (14 * 800e-6), rel=1e-9)
    assert _read("moe_gmm_ms_per_round", ctx) == pytest.approx(11.2)
    assert _read("decode_step_ms", ctx) == pytest.approx(17.0)
    for value in (swa, full, pre, gmm):
        assert 0 < value <= 100.0


@pytest.mark.parametrize("metric,kw,floor_ns", [
    ("swa_decode_roofline", "swa_ns", WINDOWED * 4096 / 819e9 * 1e9),
    ("full_decode_roofline", "full_ns", LIVE * 4096 / 819e9 * 1e9),
    ("swa_prefill_roofline", "prefill_ns", 2 * 8192 * 128 * 2 * 72 / 819e9
     * 1e9)])
def test_a_kernel_at_its_floor_reads_100_and_one_below_it_reads_over(
        metric, kw, floor_ns):
    """A share is not capped: calls faster than the bytes allow read OVER
    100 (the driver refuses such a line: the count is then too high or
    the time leaves out part of the work), calls at the floor read 100."""
    at = _read(metric, _reader_ctx(_trace(**{kw: int(round(floor_ns))})))
    assert at == pytest.approx(100.0, rel=1e-3)
    over = _read(metric, _reader_ctx(_trace(**{kw: int(floor_ns / 2)})))
    assert over > 195.0


def test_new_readers_read_nothing_where_there_is_nothing():
    """On a program that lacks what this PR adds (the parent: another
    family's names, no ``window_tokens`` on its rounds, no call of the
    banded kernels), the new readers return None and do not raise."""
    from benchmarks.families import joyai_mla_moe, llama_dense, zaya_cca_moe
    trace = _trace()
    for fam in (llama_dense, joyai_mla_moe, zaya_cca_moe):
        ctx = _reader_ctx(trace, fam)
        ctx.config = bench_run.load_cell(ROOT, "mistral_7b_offline")["config"]
        for metric in ("swa_decode_roofline", "swa_prefill_roofline",
                       "moe_held_touched_pct"):
            assert _read(metric, ctx) is None, (fam.__name__, metric)
    # The family's own names over a program that files no window tokens
    # and no touched experts, and a trace without the kernels.
    bare = _reader_ctx(trace, spans=False, tells=False)
    assert _read("swa_decode_roofline", bare) is None
    assert _read("moe_held_touched_pct", bare) is None
    empty = xplane.Trace(devices=[xplane.DevicePlane(
        0, [xplane.Event("%fusion.1 = f32[8] fusion()", 1000, 1010)],
        [xplane.Event("jit_swa_moe_step(1)", 1000, 1010),
         xplane.Event("jit__prefill(2)", 2000, 2010)])], host=[])
    ctx = _reader_ctx(empty)
    for metric in ("swa_decode_roofline", "full_decode_roofline",
                   "swa_prefill_roofline"):
        assert _read(metric, ctx) is None
