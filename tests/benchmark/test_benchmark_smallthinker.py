"""The ``smallthinker_swa_moe`` family and the cell
``smallthinker_21b_window_cross_offline`` at a size a test run can hold:
the ``serve`` kind rehearsed on the CPU over a tiny ``G L L L`` model of
seven query heads a key/value head whose router reads the layer's input,
the fp8 control failing ``served_logit_gap_max`` where the sound program
passes, the family's counts by hand, the configuration's file against the
catalog's row, the cell found by name from data alone, and the two readers
it brings on a synthetic trace.  No number here is a device metric."""

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
from benchmarks.families import smallthinker_swa_moe as family
from benchmarks.kinds import serve
from benchmarks.lib import checks, loadgen, peaks, validate
from benchmarks.lib import weights, xplane
from benchmarks.readers import attn_to_experts_gap_ms

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "smallthinker_21b_window_cross_offline"
CONFIG = "smallthinker_21b_a3b"

TINY_SMALL = {
    "kind": "serve", "family": "smallthinker_swa_moe", "vocab_size": 128,
    "hidden_size": 64, "moe_ffn_hidden_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 16,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "sliding_window_size": 8, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 128,
    "tie_word_embeddings": False,
    "share": {"chips_a_layer": 1, "experts_held": 8, "router_width": 8},
    "compute_dtype": "float32",
    "serving": {"slots": 4, "page_size": 4, "max_len": 64},
    "limits": {"served_logit_gap_max": 1e-3, "routing_margin_min": 0.0,
               "routing_branches_max": 1}}
# Under, across and past a window of 8: 4 + 3, 6 + 8, 40 + 20.
TINY_CROSSING = {"arrival": "at_zero", "order": "fixed",
                 "prompt_lens": [4, 6, 40], "prompt_weights": [0.4, 0.4, 0.2],
                 "output_lens": [3, 8, 20], "output_weights": [0.4, 0.4, 0.2],
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
    ctx, logs = _ctx(TINY_SMALL, TINY_CROSSING)
    out = serve.run(ctx)
    assert out["attempted"] == 12 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    assert checks.all_ok(out["checks"]), [c.line() for c in out["checks"]]
    by_name = {c.name: c.value for c in out["checks"]}
    assert by_name["pool_pages_left_live"] == 0
    assert by_name["compilations_inside_window"] == 0


def test_serve_kind_catches_an_altered_token_of_the_new_family(monkeypatch):
    from horovod_tpu.serving import engine
    real = engine.greedy_sample
    monkeypatch.setattr(engine, "greedy_sample",
                        lambda logits: (real(logits) + 1) % 128)
    ctx, _ = _ctx(TINY_SMALL, TINY_CROSSING)
    out = serve.run(ctx)
    by_name = {c.name: c for c in out["checks"]}
    assert not by_name["served_logit_gap_max"].ok


def _seeded(seed):
    from horovod_tpu.serving import swa_moe
    cfg = family.program_config(TINY_SMALL)
    return cfg, family.fan_in_experts(weights.make_weights(
        seed, swa_moe.param_shapes(cfg, jnp.float32), jnp.float32))


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
    worst_sound, least_control = 0.0, np.inf
    for seed in (5, 2 ** 31 + 6):
        cfg, params = _seeded(seed)
        rng = np.random.RandomState(seed % 1000)
        sample = []
        for n in (12, 30):
            prompt = rng.randint(0, 128, size=n)
            sample.append((prompt, _greedy(params, cfg, prompt, 6)))
        gaps = family.served_gaps(TINY_SMALL, params, sample, 48,
                                  with_control=True)
        assert gaps["tokens_compared"] == gaps["tokens_sampled"] == 12
        worst_sound = max(worst_sound, gaps["served_logit_gap_max"])
        least_control = min(least_control, gaps["control_logit_gap_max"])
    assert worst_sound < 1e-3
    assert least_control > 2e-3 and least_control > 20 * worst_sound


def test_the_reference_is_the_program_at_a_tiny_size_and_not_its_neighbours():
    """The family's reference against the program's prefill, every row;
    and against itself with one equation changed (the layouts split: a
    rotated full layer; the other block's router, by a norm it lacks)."""
    from horovod_tpu.serving import swa_moe
    cfg, params = _seeded(3)
    prompt = (np.arange(24) * 5 + 1) % 128
    got = swa_moe.prefill_forward(params, cfg, jnp.asarray(prompt)[None],
                                  last_only=False)[0][0]
    want = family.Reference(TINY_SMALL, params, 24).logits(prompt, 0, 24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    rotated = dict(TINY_SMALL, rope_layout=[1, 1, 1, 1])
    off = family.Reference(rotated, params, 24).logits(prompt, 0, 24)
    assert float(jnp.max(jnp.abs(off - want))) > 1e-2
    # The program computes what the lists say or refuses.
    with pytest.raises(ValueError, match="rotates its window layers"):
        family.program_config(rotated)
    with pytest.raises(ValueError, match="softmax weights"):
        family.program_config(dict(TINY_SMALL, norm_topk_prob=False))
    # K-EXAONE's variant over the same tree's shapes has other leaves.
    assert family.program_config(TINY_SMALL).route_from == "layer_input"


# -- the configuration's file -----------------------------------------------------

def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide")
    with open(path) as f:
        return next(json.loads(line) for line in f
                    if '"SmallThinker-21BA3B-Instruct"' in line)


def test_the_configuration_keeps_every_published_width():
    data = bench_run.load_cell(ROOT, CELL)
    config, entry = data["config"], next(
        c for c in data["bench"]["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    row = _catalog()
    assert entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    # The cut: depth alone, to two whole periods G L L L.
    assert config["published"]["num_hidden_layers"] == 52
    assert config["num_hidden_layers"] == 8
    assert config["rope_layout"] == row["config"]["rope_layout"][:8] \
        == [0, 1, 1, 1] * 2
    assert config["sliding_window_layout"] \
        == row["config"]["sliding_window_layout"][:8] == [0, 1, 1, 1] * 2
    assert config["share"] == {"chips_a_layer": 1, "experts_held": 64,
                               "router_width": 64}
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_num_primary_experts"], config["moe_ffn_hidden_size"],
            config["moe_num_active_primary_experts"],
            config["sliding_window_size"], config["vocab_size"],
            config["rope_theta"]) == (
        2560, 28, 4, 128, 64, 768, 6, 4096, 151936, 1500000)
    assert config["serving"] == {"slots": 64, "page_size": 16,
                                 "max_len": 9216}
    for key in ("early_router", "secondary_experts", "window_edge",
                "router_weights", "expert_weights", "gate",
                "weights_over_the_chosen", "routing_margin_min"):
        assert key in config["assumed"], key
    assert "alternative" in config["assumed"]["early_router"]
    assert "pipeline" in config["deployment"]
    assert set(config["limits"]) == {
        "served_logit_gap_max", "routing_margin_min", "routing_branches_max"}


def test_counts_by_hand_and_against_param_shapes():
    from horovod_tpu.serving import swa_moe
    config = bench_run.load_cell(ROOT, CELL)["config"]
    assert family.expert_bytes(config) == 3 * 2560 * 768 * 2 == 11_796_480
    assert family.kv_row_bytes(config) == 2048
    assert family.kv_bytes_per_token(config) == 2 * 2048
    assert (family.window_layers(config), family.full_layers(config),
            family.moe_layers(config)) == (6, 2, 8)
    cfg = family.program_config(config)
    assert cfg.attn_kinds == ("full", "window", "window", "window") * 2
    assert cfg.ffn_kinds == ("moe",) * 8
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.num_experts,
            cfg.experts_held, cfg.experts_per_token, cfg.vocab_held) == (
        28, 4, 64, 64, 6, 151936)
    assert (cfg.qk_norm, cfg.router, cfg.route_from, cfg.gate_act,
            cfg.num_shared_experts) == (False, "topk_softmax", "layer_input",
                                        "relu", 0)
    shapes = swa_moe.param_shapes(cfg, jnp.bfloat16)
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    # ISSUE 42's table: 398,627,840 parameters a layer, 777,914,880 in
    # embedding, head and final norm.
    assert total == 8 * 398_627_840 + 777_914_880 == 3_966_937_600
    assert family.weight_bytes(config) == 2 * total == 7_933_875_200
    # The cache: a ring of 257 pages a slot in six planes of two pools.
    assert family.window_pages(config) == 64 * 257 == 16_448
    assert family.cache_bytes(config) == 2_415_984_640 + 3_234_004_992 \
        == 5_649_989_632
    spec = cfg.layer_spec()
    assert (spec.window, spec.window_planes, spec.planes) == (4096, 6, 2)
    # One window layer over 8,192 tokens: a band of 4,096, not the
    # triangle; under the window the triangle.
    cost = family.swa_prefill_cost(config, 8192)
    pairs = 4096 * 4097 // 2 + (8192 - 4096) * 4096
    assert cost == {"flops": 4 * 28 * 128 * pairs,
                    "bytes": 2 * 8192 * 128 * 2 * 32}
    assert family.swa_prefill_cost(config, 1024)["flops"] \
        == 4 * 28 * 128 * 1024 * 1025 // 2
    assert family.swa_prefill_cost(config, 3584)["flops"] \
        == 4 * 28 * 128 * 3584 * 3585 // 2


def test_the_cell_lists_its_metrics_and_each_has_a_reader():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    traced = validate.expected_metrics(bench, CELL, True)
    assert set(validate.expected_metrics(bench, CELL, False)) == {
        "serve_tokens_per_s", "setup_s"}
    listed = ("swa_decode_roofline", "full_decode_roofline",
              "swa_prefill_roofline", "moe_held_touched_pct",
              "moe_gmm_roofline", "moe_gmm_ms_per_round",
              "decode_step_ms.offline", "batch_occupancy_pct",
              "device_idle_pct.offline", "round_idle_ms.prepare",
              "round_idle_ms.fetch", "round_idle_ms.bookkeep",
              "round_idle_ms.between", "round_period_ms.offline",
              "prefill_stall_ms.offline", "prefill_share_pct.offline",
              "loop_host_ms_per_round.offline", "attn_to_experts_gap_ms",
              "window_ring_held_pct")
    assert set(traced) == set(listed) | {"serve_tokens_per_s", "setup_s"}
    for name in listed:
        assert callable(bench_run.reader_for(name).read)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, source in (
            ("attn_to_experts_gap_ms", "decode step", "device_trace"),
            ("window_ring_held_pct", "serving engine", "program_span")):
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["source"], m["moves"], m["better"]) == (
            layer, source, "serve_tokens_per_s", "lower")
    # The two new entries stand last, the configuration and the cell too.
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "attn_to_experts_gap_ms", "window_ring_held_pct"]
    assert bench["configs"][-1]["name"] == CONFIG
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, "offline_window_crossing_lengths", 1)
    assert len(cell["why"]) <= 200
    for word in ("64 slots", "257", "64 of 64", "8 of 52"):
        assert word in cell["why"], word


def test_the_traffic_crosses_the_window():
    traffic = bench_run.load_cell(ROOT, CELL)["traffic"]
    assert (traffic["arrival"], traffic["order"]) == ("at_zero", "fixed")
    assert traffic["prompt_lens"] == [1024, 3584, 8192]
    assert traffic["prompt_weights"] == [0.4, 0.4, 0.2]
    assert traffic["output_lens"] == [256, 512, 1024]
    assert traffic["output_weights"] == [0.4, 0.4, 0.2]
    assert "prefix_share" not in traffic and "session_share" not in traffic
    a = loadgen.generate(traffic, 5, 30.0, 151936)
    b = loadgen.generate(traffic, 2 ** 31 + 9, 30.0, 151936)
    assert [(len(r.prompt), r.max_new_tokens) for r in a] \
        == [(len(r.prompt), r.max_new_tokens) for r in b]
    assert all(r.arrival_s == 0.0 for r in a)
    assert max(int(r.prompt.max()) for r in a) < 151936
    assert not any((a[i].prompt != b[i].prompt).sum() == 0
                   for i in range(len(a)))
    lens = [len(r.prompt) for r in a]
    assert 3300 < sum(lens) / len(lens) < 3650          # mean 3,482
    assert max(len(r.prompt) + r.max_new_tokens for r in a) == 9216
    # The three regimes: under the window for good, across it while
    # decoding, past it from the prompt on.
    ends = {(len(r.prompt), len(r.prompt) + r.max_new_tokens) for r in a}
    assert any(end < 4096 for _, end in ends)
    assert any(start < 4096 < end for start, end in ends)
    assert any(start > 4096 for start, _ in ends)
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
    assert out == {"family": "benchmarks.families.smallthinker_swa_moe",
                   "kind": "serve",
                   "traffic": "offline_window_crossing_lengths",
                   "module": r"^jit_swa_moe_step\("}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "TPU" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


# -- the two new readers on a synthetic trace ---------------------------------------

ROUNDS, ROUND_NS = 10, 15_000_000
GAP_NS, ROUTE_NS = 40_000, 25_000


def _trace(route_late=False):
    """A device plane of ``ROUNDS`` decode programs of eight layers: a
    page walk (``hvd_cca_decode`` in layers 0 and 4, ``hvd_swa_decode`` in
    the others), ``GAP_NS`` later two ``hvd_moe_gmm`` calls.
    ``route_late``: a fusion of ``ROUTE_NS`` more stands in the gap, as
    where XLA schedules the router after attention."""
    modules, ops, t = [], [], 1000

    def call(name, at, ns):
        ops.append(xplane.Event(
            f"%{name} = f32[64,28,128]{{2,1,0}} custom-call(), "
            'custom_call_target="tpu_custom_call"', at, at + ns))
        return at + ns

    for i in range(ROUNDS):
        modules.append(xplane.Event(f"jit_swa_moe_step({i})", t,
                                    t + ROUND_NS))
        at = t + 100
        for layer in range(8):
            walk = "hvd_swa_decode" if layer % 4 else "hvd_cca_decode"
            at = call(f"{walk}.{layer}", at + 50_000, 300_000)
            if route_late:
                ops.append(xplane.Event("%fusion.9 = f32[64,64] fusion()",
                                        at + 10, at + 10 + ROUTE_NS))
            at += GAP_NS + (ROUTE_NS if route_late else 0)
            at = call(f"hvd_moe_gmm.{2 * layer}", at, 500_000)
            at = call(f"hvd_moe_gmm.{2 * layer + 1}", at + 10, 250_000)
        t += ROUND_NS + 500
    ops.sort(key=lambda e: e.start_ns)
    return xplane.Trace(devices=[xplane.DevicePlane(0, ops, modules)],
                        host=[])


def _reader_ctx(trace, fam=family, held=None):
    threads = threads_for(trace, family.DECODE_MODULE, 200_000, 64)
    if held is not None:
        for i, s in enumerate(
                [s for s in threads[0] if s.name == "decode.round"]):
            s.stats.update(window_pages_held=held + i)
    return types.SimpleNamespace(
        trace=trace, threads=threads, counters={}, family=fam,
        config=bench_run.load_cell(ROOT, CELL)["config"],
        peaks=peaks.peaks_for("TPU v5 lite"), metric=None,
        cell={"name": CELL}, log=lambda msg: None)


def _read(metric, ctx):
    return bench_run.reader_for(metric).read(ctx)


def test_the_gap_reader_sums_a_round_s_eight_gaps():
    # Eight layers, 40 us from each walk's end to its experts' start.
    assert _read("attn_to_experts_gap_ms", _reader_ctx(_trace())) \
        == pytest.approx(8 * GAP_NS / 1e6)
    # Work scheduled into the gap makes it longer by that work.
    assert _read("attn_to_experts_gap_ms",
                 _reader_ctx(_trace(route_late=True))) \
        == pytest.approx(8 * (GAP_NS + ROUTE_NS) / 1e6)


def test_gaps_pair_each_walk_with_the_next_grouped_matmul_only():
    import re
    E = xplane.Event
    ops = [E("%hvd_cca_decode.1 = f32[]", 0, 100),
           E("%fusion.3 = f32[]", 110, 120),
           E("%hvd_moe_gmm.1 = bf16[]", 150, 200),
           E("%hvd_moe_gmm.2 = bf16[]", 210, 260),     # its second: no gap
           E("%hvd_swa_decode.1 = f32[]", 300, 400),
           E("%hvd_swa_decode.2 = f32[]", 410, 500),   # the later walk counts
           E("%hvd_moe_gmm.3 = bf16[]", 530, 600)]
    walks = re.compile(family.SWA_DECODE_KERNEL + "|"
                       + family.CCA_DECODE_KERNEL)
    assert attn_to_experts_gap_ms.gaps_ns(
        ops, walks, re.compile(family.MOE_GMM_KERNEL)) == [50, 30]


def test_the_ring_reader_is_the_mean_share_of_the_group_held():
    ctx = _reader_ctx(_trace(), held=8000)
    # Rounds hold 8,000 .. 8,009 of the group's 16,448 pages.
    assert _read("window_ring_held_pct", ctx) == pytest.approx(
        100.0 * 8004.5 / 16448)
    assert 48.0 < _read("window_ring_held_pct", ctx) < 49.0
    full = _reader_ctx(_trace(), held=16448 - 9)
    assert _read("window_ring_held_pct", full) == pytest.approx(
        100.0 * (16448 - 4.5) / 16448)


def test_the_new_readers_read_nothing_where_there_is_nothing():
    """On a program that lacks what this PR adds (the parent: no
    ``window_pages_held`` on its rounds), for a family that names no such
    kernels or no window group, and on a trace without the calls, the new
    readers return None and do not raise."""
    from benchmarks.families import (exaone_swa_moe, joyai_mla_moe,
                                     llama_dense)
    trace = _trace()
    assert _read("window_ring_held_pct", _reader_ctx(trace)) is None
    for fam in (llama_dense, joyai_mla_moe, exaone_swa_moe):
        ctx = _reader_ctx(trace, fam, held=100)
        assert _read("window_ring_held_pct", ctx) is None, fam.__name__
    assert _read("attn_to_experts_gap_ms",
                 _reader_ctx(trace, llama_dense)) is None
    empty = xplane.Trace(devices=[xplane.DevicePlane(
        0, [xplane.Event("%fusion.1 = f32[8] fusion()", 1000, 1010)],
        [xplane.Event("jit_swa_moe_step(1)", 1000, 1010)])], host=[])
    assert _read("attn_to_experts_gap_ms", _reader_ctx(empty)) is None
    assert _read("window_ring_held_pct", _reader_ctx(empty)) is None
