"""The ``eva_dense`` family and the cell ``evabyte_6_5b_code_files_offline``
at a size a test run can hold: the ``serve`` kind rehearsed on the CPU
over a tiny model with EVA attention, the fp8 control failing
``served_logit_gap_max`` where the sound program passes, the family's
reference against a second plain form of the same equations (a loop over
queries), its counts by hand, the configuration's file against the
catalog row's values written out, the cell found by name from data alone,
and the two readers it brings on a synthetic trace.  No number here is a
device metric, and nothing here asserts where in a list of
``BENCHMARK.json`` an entry stands beyond "behind what was there": a
later cell may join."""

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
from benchmarks.families import eva_dense as family
from benchmarks.kinds import serve
from benchmarks.lib import checks, loadgen, peaks, validate
from benchmarks.lib import weights, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
from serving_families import TINY_EVA  # noqa: E402

CELL = "evabyte_6_5b_code_files_offline"
CONFIG = "evabyte_6_5b"

# Prompts that end inside a chunk (16) and inside a window (64), some of
# them past one; outputs that cross a window's edge.
TINY_FILES = {"arrival": "at_zero", "order": "fixed",
              "prompt_lens": [37, 75, 115], "prompt_weights": [0.4, 0.4, 0.2],
              "output_lens": [5, 30, 60], "output_weights": [0.4, 0.4, 0.2],
              "num_requests": 10, "trace_from_round": 2, "trace_rounds": 4}


def _ctx(config, traffic, seed=2 ** 31 + 7, seconds=0.5, control=""):
    data = {"cell": {"name": "tiny"}, "config": config, "traffic": traffic}
    logs = []
    ctx = bench_run.make_context(data, seed, seconds, "",
                                 jax.devices()[:1], family, logs.append)
    ctx.with_control = control
    return ctx, logs


# -- the rehearsal: the serve kind over the new family ---------------------------

def test_serve_kind_tiny_on_the_new_family():
    ctx, logs = _ctx(TINY_EVA, TINY_FILES)
    out = serve.run(ctx)
    assert out["attempted"] == 10 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    assert checks.all_ok(out["checks"]), [c.line() for c in out["checks"]]
    by_name = {c.name: c.value for c in out["checks"]}
    assert by_name["pool_pages_left_live"] == 0
    assert by_name["compilations_inside_window"] == 0
    assert any("4 requests" in line for line in logs)


def test_serve_kind_catches_an_altered_token_of_the_new_family(monkeypatch):
    from horovod_tpu.serving import engine
    real = engine.greedy_sample
    monkeypatch.setattr(engine, "greedy_sample",
                        lambda logits: (real(logits) + 1) % 320)
    ctx, _ = _ctx(TINY_EVA, TINY_FILES)
    out = serve.run(ctx)
    by_name = {c.name: c for c in out["checks"]}
    assert not by_name["served_logit_gap_max"].ok


def _seeded(seed, config=TINY_EVA):
    from horovod_tpu.serving import eva_dense
    cfg = family.program_config(config)
    return cfg, family.seeded_assumptions(weights.make_weights(
        seed, eva_dense.param_shapes(cfg, jnp.float32), jnp.float32), seed)


def _greedy(params, cfg, prompt, n, pad=160):
    from horovod_tpu.serving import eva_dense
    forward = jax.jit(lambda p, t: eva_dense.prefill_forward(
        p, cfg, t, last_only=False)[0])
    served = []
    for _ in range(n):
        ctx = np.zeros((pad,), np.int32)
        ctx[:len(prompt) + len(served)] = np.concatenate(
            [prompt, np.asarray(served, int)])
        logits = forward(params, jnp.asarray(ctx)[None])
        served.append(int(jnp.argmax(
            logits[0, len(prompt) + len(served) - 1, :320])))
    return served


def test_fp8_control_fails_the_served_comparison_of_the_new_family():
    """The plain reference in the program's place, computed in fp8: its
    first token lies far below the float32 reference's best, where the
    sound float32 program's lies at it.  Two seeds, one above 2**31;
    contexts past one and two windows' edges."""
    worst_sound, least_control = 0.0, np.inf
    for seed in (5, 2 ** 31 + 6):
        cfg, params = _seeded(seed)
        rng = np.random.RandomState(seed % 1000)
        sample = []
        for n in (40, 130, 70):
            prompt = rng.randint(0, 320, size=n)
            sample.append((prompt, _greedy(params, cfg, prompt, 10)))
        gaps = family.served_gaps(TINY_EVA, params, sample, 160,
                                  with_control=True)
        assert gaps["tokens_compared"] == 30
        worst_sound = max(worst_sound, gaps["served_logit_gap_max"])
        least_control = min(least_control, gaps["control_logit_gap_max"])
    assert worst_sound < 1e-4
    assert least_control > 1e-3 and least_control > 20 * worst_sound


def test_the_seeded_vectors_are_the_published_initialisation():
    _, params = _seeded(2 ** 31 + 11)
    _, again = _seeded(2 ** 31 + 11)
    _, other = _seeded(12)
    p = params["params"]
    assert not np.any(np.asarray(p["final_norm"]["scale"]))
    for li in range(2):
        blk = p[f"layer_{li}"]
        # A unit-offset norm starts at 1 + 0.
        assert not np.any(np.asarray(blk["attn_norm"]["scale"]))
        assert not np.any(np.asarray(blk["mlp_norm"]["scale"]))
        for key in ("adaptive_mu_k", "adaptive_phi"):
            z = np.asarray(blk["attn"][key], np.float64) * np.sqrt(32)
            assert z.shape == (4, 32)
            # A normal cut at one deviation: a fifth and more AT the cut.
            assert np.abs(z).max() <= 1.0 + 1e-6
            assert 0.15 < np.mean(np.abs(z) > 1.0 - 1e-6) < 0.5
            assert 0.4 < z.std() < 0.8
            np.testing.assert_array_equal(
                blk["attn"][key], again["params"][f"layer_{li}"]["attn"][key])
            assert not np.array_equal(
                blk["attn"][key], other["params"][f"layer_{li}"]["attn"][key])
        assert not np.array_equal(blk["attn"]["adaptive_mu_k"],
                                  blk["attn"]["adaptive_phi"])
    assert not np.array_equal(p["layer_0"]["attn"]["adaptive_phi"],
                              p["layer_1"]["attn"]["adaptive_phi"])


# -- the reference, against a second plain form of the same equations ----------

def _by_queries(x, blk, config):
    """EVA attention of one layer as a LOOP OVER QUERIES in numpy float64:
    query ``i`` gathers the exact keys of its own window up to itself and
    pools, then and there, every chunk of the windows before."""
    heads, dh = config["num_attention_heads"], family.head_dim(config)
    win, chunk = config["window_size"], config["chunk_size"]
    theta = float(config["rope_theta"])
    attn = jax.tree.map(lambda z: np.asarray(z, np.float64), blk["attn"])
    t = x.shape[0]
    u = x / np.sqrt(np.mean(x * x, -1, keepdims=True)
                    + config["rms_norm_eps"]) * (
        1.0 + np.asarray(blk["attn_norm"]["scale"], np.float64))

    def rope(z):
        z = z.reshape(t, heads, dh)
        freqs = theta ** (-np.arange(0, dh, 2) / dh)
        ang = np.arange(t)[:, None, None] * freqs
        a, b = z[..., :dh // 2], z[..., dh // 2:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], -1)

    q, k = rope(u @ attn["wq"]["kernel"]), rope(u @ attn["wk"]["kernel"])
    v = (u @ attn["wv"]["kernel"]).reshape(t, heads, dh)

    def softmax(z):
        e = np.exp(z - z.max())
        return e / e.sum()

    out = np.zeros((t, heads, dh))
    for i in range(t):
        w = i // win
        for h in range(heads):
            keys, vals = [], []
            for c in range(w * win // chunk):
                kc = k[c * chunk:(c + 1) * chunk, h]
                vc = v[c * chunk:(c + 1) * chunk, h]
                keys.append(softmax(kc @ attn["adaptive_mu_k"][h]) @ kc)
                vals.append(softmax(kc @ attn["adaptive_phi"][h]) @ vc)
            keys += list(k[w * win:i + 1, h])
            vals += list(v[w * win:i + 1, h])
            p = softmax(np.stack(keys) @ q[i, h] / np.sqrt(dh))
            out[i, h] = p @ np.stack(vals)
    return out.reshape(t, heads * dh) @ attn["wo"]["kernel"]


def test_the_reference_is_the_loop_over_queries_and_the_program():
    """The family's masked whole-sequence form against the loop over
    queries (three windows, a ragged chunk), and against the program's
    prefill, every row of every head; a config whose flags name another
    model is refused."""
    from horovod_tpu.serving import eva_dense
    cfg, params = _seeded(3)
    t = 150
    x = np.random.RandomState(0).normal(size=(t, 128))
    blk = params["params"]["layer_1"]
    eps = float(TINY_EVA["rms_norm_eps"])
    got = family.ref_attention(
        family._norm(jnp.asarray(x, jnp.float32),
                     blk["attn_norm"]["scale"], eps),
        blk["attn"], TINY_EVA, query_block=64)
    want = _by_queries(x, blk, TINY_EVA)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    prompt = (np.arange(t) * 7 + 3) % 320
    served = eva_dense.prefill_forward(
        params, cfg, jnp.asarray(prompt)[None], last_only=False)[0][0]
    ref = family.Reference(TINY_EVA, params, t).logits(prompt, 0, t)
    assert ref.shape == (t, 8 * 320)
    np.testing.assert_allclose(np.asarray(served), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # Another chunk: another model.
    off = family.Reference(dict(TINY_EVA, chunk_size=8), params, t).logits(
        prompt, 0, t)
    assert float(jnp.max(jnp.abs(off[70:] - ref[70:]))) > 1e-3
    np.testing.assert_allclose(np.asarray(off[:64]), np.asarray(ref[:64]),
                               rtol=1e-5, atol=1e-5)
    for key, value in (("attention_class", "full"),
                       ("norm_add_unit_offset", False),
                       ("attention_bias", True), ("num_chunks", 8),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="the program computes"):
            family.program_config(dict(TINY_EVA, **{key: value}))


# -- the configuration's file -----------------------------------------------------

# The catalog row's ``config`` (architectures.jsonl, "EvaByte"), written
# out: every key the file must hold under its own name.
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}
SOURCE = "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"


def test_the_written_out_row_is_the_catalog_s():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide")
    with open(path) as f:
        row = next(json.loads(line) for line in f if '"EvaByte"' in line)
    assert row["config"] == PUBLISHED and row["source_url"] == SOURCE


def test_the_configuration_keeps_every_published_key_but_the_depth():
    data = bench_run.load_cell(ROOT, CELL)
    config, entry = data["config"], next(
        c for c in data["bench"]["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmarks/configs/evabyte_6_5b.json"
    assert entry["source"] == config["source"] == SOURCE
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["num_hidden_layers"] == 8
    assert config["share"] == {"chips_a_layer": 1}
    assert config["serving"] == {"slots": 24, "page_size": 16,
                                 "max_len": 14336}
    assert config["serving"]["page_size"] == config["chunk_size"]
    assert config["serving"]["max_len"] == 7 * config["window_size"]
    for key in ("head_dim", "pooling_scale", "pooling_values",
                "seeded_vectors", "rope", "next_byte_head", "chunking",
                "layers_alike", "no_bias", "served_logit_gap_max"):
        assert key in config["assumed"], key
    for key in ("pooling_scale", "pooling_values", "rope", "next_byte_head",
                "chunking"):
        assert "alternative" in config["assumed"][key], key
    assert "FOUR-stage pipeline" in config["deployment"]
    assert any("8 of the 32 layers" in d for d in config["departures"])
    assert set(config["limits"]) == {"served_logit_gap_max"}
    assert (config["kind"], config["family"], config["compute_dtype"]) == (
        "serve", "eva_dense", "bfloat16")


def test_counts_by_hand_and_against_param_shapes():
    from horovod_tpu.serving import eva_dense
    config = bench_run.load_cell(ROOT, CELL)["config"]
    # ISSUE 51's arithmetic, a layer: four projections, the SwiGLU's
    # three, two norms, the two pooling vectors a head.
    products = 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert products == 202_375_168
    assert family.layer_params(config) == products + 2 * 4096 \
        + 2 * 32 * 128 == 202_391_552
    rest = 320 * 4096 + 4096 * 2560 + 4096
    cfg = family.program_config(config)
    shapes = eva_dense.param_shapes(cfg, jnp.bfloat16)
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert total == 8 * 202_391_552 + rest
    assert family.weight_bytes(config) == 2 * total == 3_261_865_984
    # All 32 layers: 12.95 GB, no room for a cache.
    assert 32 * 2 * 202_391_552 == 12_953_059_328
    assert family.head_dim(config) == 128 == cfg.head_dim
    # A cached row: 4,096 columns in each of two pools, a layer; a byte of
    # context in the ring, a chunk of 16 in the growing pages.
    assert family.kv_bytes_per_row(config) == 2 * 4096 * 2 == 16_384
    assert family.ring_pages(config) == 129
    assert family.pool_pages(config) == 24 * (56 + 129) + 1 == 4441
    ring, grown = 129 * 16 * 16_384, 56 * 16 * 16_384
    assert (ring, grown) == (int(32.25 * 2 ** 20), 14 * 2 ** 20)
    assert family.slot_bytes_per_layer(config) == ring + grown \
        == int(46.25 * 2 ** 20)
    # Full attention at 14,336: 224 MiB a slot a layer.
    assert 14336 * 16_384 == 224 * 2 ** 20
    assert family.cache_bytes(config) == 8 * 4441 * 16 * 16_384 \
        == 9_313_452_032
    static = family.weight_bytes(config) + family.cache_bytes(config)
    assert static == 12_575_318_016 and static > 0.25 * 16e9
    spec = cfg.layer_spec()
    assert (spec.planes, spec.window_planes, spec.window, spec.row_tokens,
            spec.window_aligned, spec.slot_state) == (
        8, 8, 2048, 16, True, None)
    from horovod_tpu.serving.kvcache import CacheConfig
    cc = CacheConfig(num_layers=8, slots=24, page_size=16, max_len=14336,
                     dtype="bfloat16", page=spec.page, window_layers=8,
                     window=2048, row_tokens=16)
    assert cc.layout()["kv_shape"] == [8, 4441, 16, 4096]
    assert (cc.pages_per_slot, cc.window_pages_per_slot) == (56, 129)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.window, cfg.chunk,
            cfg.pred_heads, cfg.vocab_size, cfg.rope_theta, cfg.rms_eps) == (
        32, 32, 2048, 16, 8, 320, 1e5, 1e-5)


# -- the cell, its lists and its traffic ---------------------------------------------

LISTED = ("eva_decode_roofline", "eva_decode_ms_per_round",
          "decode_step_ms.offline", "batch_occupancy_pct",
          "device_idle_pct.offline", "round_idle_ms.prepare",
          "round_idle_ms.fetch", "round_idle_ms.bookkeep",
          "round_idle_ms.between", "round_period_ms.offline",
          "prefill_stall_ms.offline", "prefill_share_pct.offline",
          "loop_host_ms_per_round.offline")


def test_the_cell_lists_its_metrics_and_each_has_a_reader():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    traced = validate.expected_metrics(bench, CELL, True)
    assert set(validate.expected_metrics(bench, CELL, False)) == {
        "serve_tokens_per_s", "setup_s"}
    assert set(traced) == set(LISTED) | {"serve_tokens_per_s", "setup_s"}
    # The walks' readers of the other cells count rows a token; this
    # cell's rows are not tokens.
    for name in ("decode_attn_roofline", "full_decode_roofline",
                 "swa_decode_roofline"):
        assert name not in traced
    for name in LISTED:
        assert callable(bench_run.reader_for(name).read)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, better, unit in (("eva_decode_roofline", "higher", "%"),
                               ("eva_decode_ms_per_round", "lower", "ms")):
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["source"], m["moves"], m["better"],
                m["unit"]) == ("Pallas kernels", "device_trace",
                               "serve_tokens_per_s", better, unit)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "offline_byte_file_lengths", 1)
    assert len(cell["why"]) <= 200
    for word in ("24 slots", "60% cross a window's edge", "8 of 32"):
        assert word in cell["why"], word
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_nothing_accepted_moved():
    """Against the parent's file as PR 50 left it, by name and in order:
    every list begins with what it held, and the lists the cell joined
    hold it last."""
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [c["name"] for c in bench["configs"]][:8] == [
        "bert_large", "mistral_7b_v03", "joyai_llm_flash", "zaya1_8b",
        "ouro_2_6b", "k_exaone_236b_a23b", "smallthinker_21b_a3b",
        "falcon_h1_34b"]
    assert [w["name"] for w in bench["workloads"]][:10] == [
        "bert_large_dp1", "mistral_7b_offline", "mistral_7b_chat_steady",
        "bert_large_dp4", "joyai_llm_flash_offline_docs",
        "zaya1_8b_reasoning_offline", "ouro_2_6b_math_offline",
        "k_exaone_236b_mixed_offline",
        "smallthinker_21b_window_cross_offline",
        "falcon_h1_34b_short_chat_offline"]
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) >= 45 and names[41:43] == [
        "ssm_decode_roofline", "ssm_decode_ms_per_round"]
    for key, new in (("configs", CONFIG), ("workloads", CELL),
                     ("per_layer", "eva_decode_roofline")):
        order = [e["name"] for e in bench[key]]
        assert order.index(new) > order.index(
            {"configs": "falcon_h1_34b",
             "workloads": "falcon_h1_34b_short_chat_offline",
             "per_layer": "ssm_decode_ms_per_round"}[key])
    joined = [m for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ()) and len(m["workloads"]) > 1]
    assert len(joined) == 12
    for m in joined:
        at = m["workloads"].index(CELL)
        assert m["workloads"][at - 1] == "falcon_h1_34b_short_chat_offline"
    assert bench["run_seconds"] == 30 and bench["command"] == [
        "python3", "benchmarks/run.py"]
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("train_tokens_per_s_per_chip", 0.01), ("serve_tokens_per_s", 0.04),
        ("tpot_p95_ms", 0.1), ("setup_s", 0.1)]


def test_the_traffic_is_whole_files_of_bytes_at_t_zero():
    traffic = bench_run.load_cell(ROOT, CELL)["traffic"]
    assert (traffic["arrival"], traffic["order"]) == ("at_zero", "fixed")
    assert traffic["prompt_lens"] == [3500, 7500, 11500]
    assert traffic["prompt_weights"] == [0.4, 0.4, 0.2]
    assert traffic["output_lens"] == [512, 1024, 2048]
    assert traffic["output_weights"] == [0.4, 0.4, 0.2]
    assert "prefix_share" not in traffic and "session_share" not in traffic
    assert "1.2 x" in traffic["why"]
    a = loadgen.generate(traffic, 5, 30.0, 320)
    b = loadgen.generate(traffic, 2 ** 31 + 9, 30.0, 320)
    assert [(len(r.prompt), r.max_new_tokens) for r in a] \
        == [(len(r.prompt), r.max_new_tokens) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(r.arrival_s == 0.0 for r in a)
    assert len(a) == round(30 * traffic["requests_per_second_of_window"])
    assert max(int(r.prompt.max()) for r in a) == 319
    assert max(len(r.prompt) + r.max_new_tokens for r in a) == 13548 <= 14336
    lens = np.asarray([(len(r.prompt), r.max_new_tokens) for r in a])
    assert abs(lens[:, 0].mean() - 6700) < 150
    assert abs(lens[:, 1].mean() - 1024) < 40
    # No prompt ends on a chunk's edge or a window's: rows wait in the
    # ring and are pooled inside the timed path.
    assert all(n % 16 and n % 2048 for n in traffic["prompt_lens"])
    assert [n % 16 for n in traffic["prompt_lens"]] == [12, 12, 12]
    # Which pairs cross a window's edge while they decode.
    crossing = {(p, o) for p in traffic["prompt_lens"]
                for o in traffic["output_lens"]
                if (p + o - 1) // 2048 > p // 2048}
    assert {(3500, 1024), (7500, 1024), (11500, 1024)} <= crossing
    assert (3500, 512) not in crossing and (7500, 512) not in crossing
    first, n = serve.traced_window(traffic)
    assert n == 100 and first >= 0
    # Every prompt goes through its prefill program alone: four of the
    # shortest are 14,000 rows, over the 2,048 a group may have.
    from horovod_tpu.serving.engine import group_size
    assert [group_size(t, 2048) for t in (3500, 7500, 11500)] == [1, 1, 1]


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
            "'module': fam.DECODE_MODULE, "
            "'kernel': fam.EVA_DECODE_KERNEL}))\n")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"family": "benchmarks.families.eva_dense",
                   "kind": "serve",
                   "traffic": "offline_byte_file_lengths",
                   "module": r"^jit_eva_dense_step\(",
                   "kernel": r"^%hvd_eva_decode[.\d]* = "}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "TPU" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


# -- the two new readers on a synthetic trace ---------------------------------------

ROUNDS, ROUND_NS, SLOTS = 10, 12_000_000, 24
CALL_NS = 800_000
ATTENDED = SLOTS * 1600               # rows a round attends in ONE layer


def _trace(calls=8, module="jit_eva_dense_step"):
    """A device plane of ``ROUNDS`` decode programs of eight layers, one
    walk each, ``CALL_NS`` long; a call of the same name OUTSIDE any
    round's program, which no round owns."""
    modules, ops, t = [], [], 1000

    def call(name, at, ns):
        ops.append(xplane.Event(
            f"%{name} = f32[24,32,128]{{2,1,0}} custom-call(), "
            'custom_call_target="tpu_custom_call"', at, at + ns))
        return at + ns

    for i in range(ROUNDS):
        modules.append(xplane.Event(f"{module}({i})", t, t + ROUND_NS))
        at = t + 100
        for layer in range(calls):
            at = call(f"hvd_eva_decode.{layer}", at + 50_000, CALL_NS)
        t += ROUND_NS + 500
    call("hvd_eva_decode.99", t + 10, CALL_NS)
    ops.sort(key=lambda e: e.start_ns)
    return xplane.Trace(devices=[xplane.DevicePlane(0, ops, modules)],
                        host=[])


def _reader_ctx(trace, fam=family, attended=ATTENDED):
    threads = threads_for(trace, family.DECODE_MODULE, 24 * 7000, SLOTS)
    if attended is not None:
        for s in threads[0]:
            if s.name == "decode.round":
                s.stats.update(attended_rows=attended,
                               pooled_rows=attended // 4, chunks_pooled=2,
                               window_crossings=0)
    return types.SimpleNamespace(
        trace=trace, threads=threads, counters={}, family=fam,
        config=bench_run.load_cell(ROOT, CELL)["config"],
        peaks=peaks.peaks_for("TPU v5 lite"), metric=None,
        cell={"name": CELL}, log=lambda msg: None)


def _read(metric, ctx):
    return bench_run.reader_for(metric).read(ctx)


def test_the_roofline_reader_counts_every_attended_row_once_a_layer():
    ctx = _reader_ctx(_trace())
    # 24 x 1,600 rows x 8 layers x 16,384 bytes at 819 GB/s: 6.15 ms of
    # the 6.4 ms the eight calls took.
    least_ms = ATTENDED * 8 * 16_384 / 819e9 * 1e3
    assert _read("eva_decode_ms_per_round", ctx) == pytest.approx(6.4)
    assert _read("eva_decode_roofline", ctx) \
        == pytest.approx(100 * least_ms / 6.4)
    assert 95 < _read("eva_decode_roofline", ctx) < 97
    # Half the rows over the same time: half the share.  The rows are the
    # program's own count, not the round's live tokens (four times more
    # here, which would read over 100%).
    half = _reader_ctx(_trace(), attended=ATTENDED // 2)
    assert _read("eva_decode_roofline", half) \
        == pytest.approx(50 * least_ms / 6.4)


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    # A program from before PR 51: its rounds name no attended_rows.
    old = _reader_ctx(_trace(), attended=None)
    assert _read("eva_decode_roofline", old) is None
    assert _read("eva_decode_ms_per_round", old) == pytest.approx(6.4)
    # A family that names no such kernel.
    bare = types.SimpleNamespace(DECODE_MODULE=family.DECODE_MODULE)
    ctx = _reader_ctx(_trace(), fam=bare)
    assert _read("eva_decode_roofline", ctx) is None
    assert _read("eva_decode_ms_per_round", ctx) is None
    # No call inside any round's program.
    none = _reader_ctx(_trace(calls=0))
    assert _read("eva_decode_roofline", none) is None
    assert _read("eva_decode_ms_per_round", none) is None
    # No decode program of the family's name: no whole round.
    other = _reader_ctx(_trace(module="jit_ssm_hybrid_step"))
    assert _read("eva_decode_roofline", other) is None
    assert _read("eva_decode_ms_per_round", other) is None
