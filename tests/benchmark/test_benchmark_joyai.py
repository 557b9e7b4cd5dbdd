"""The ``joyai_mla_moe`` family and the cell ``joyai_llm_flash_offline_docs``
at a size a test run can hold: the ``serve`` kind rehearsed on the CPU
over a tiny latent-attention / routed-expert model, the fp8 control put
in the program's place failing ``served_logit_gap_max`` where the sound
program passes, the family's byte counts by hand, the configuration's file
against the published widths, and the new readers on a small trace
recorded on the chip.  No number here is a device metric."""

import gzip
import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import joyai_mla_moe as family
from benchmarks.kinds import serve
from benchmarks.lib import checks, peaks, validate, weights, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "joyai_llm_flash_offline_docs"

TINY_JOYAI = {
    "kind": "serve", "family": "joyai_mla_moe", "vocab_size": 256,
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 16,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 128,
    "compute_dtype": "float32",
    "serving": {"slots": 4, "page_size": 8, "max_len": 64},
    "limits": {"served_logit_gap_max": 1e-3, "routing_margin_min": 0.0,
               "routing_branches_max": 1}}
TINY_DOCS = {"arrival": "at_zero", "order": "fixed",
             "prompt_lens": [8, 16, 32], "output_lens": [4, 8],
             "num_requests": 12, "trace_rounds": 4}


def _ctx(config, traffic, seed=2 ** 31 + 7, seconds=0.5, control=""):
    data = {"cell": {"name": "tiny"}, "config": config, "traffic": traffic}
    logs = []
    ctx = bench_run.make_context(data, seed, seconds, "",
                                 jax.devices()[:1], family, logs.append)
    ctx.with_control = control
    return ctx, logs


def test_serve_kind_tiny_on_the_new_family():
    ctx, logs = _ctx(TINY_JOYAI, TINY_DOCS)
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
                        lambda logits: (real(logits) + 1) % 256)
    ctx, _ = _ctx(TINY_JOYAI, TINY_DOCS)
    out = serve.run(ctx)
    by_name = {c.name: c for c in out["checks"]}
    assert not by_name["served_logit_gap_max"].ok


def _greedy(params, cfg, prompt, n, pad=32):
    """``n`` greedy tokens after ``prompt`` from the program's prefill, one
    compiled length (padding on the right changes no earlier row)."""
    from horovod_tpu.serving import mla_moe
    forward = jax.jit(lambda p, t: mla_moe.prefill_forward(
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
    from horovod_tpu.serving import mla_moe
    cfg = family.program_config(TINY_JOYAI)
    shapes = mla_moe.param_shapes(cfg, jnp.float32)
    worst_sound, least_control = 0.0, np.inf
    for seed in (5, 2 ** 31 + 6):
        params = family.fan_in_experts(
            weights.make_weights(seed, shapes, jnp.float32))
        rng = np.random.RandomState(seed % 1000)
        sample = []
        for n in (12, 20):
            prompt = rng.randint(0, 256, size=n)
            sample.append((prompt, _greedy(params, cfg, prompt, 6)))
        gaps = family.served_gaps(TINY_JOYAI, params, sample, 32,
                                  with_control=True)
        assert gaps["tokens_compared"] == gaps["tokens_sampled"] == 12
        worst_sound = max(worst_sound, gaps["served_logit_gap_max"])
        least_control = min(least_control, gaps["control_logit_gap_max"])
    assert worst_sound < 1e-3
    assert least_control > 0.05 and least_control > 50 * worst_sound


def test_routings_within_by_hand():
    """Top 2 of 6.  Row 0 is decided (the 2nd leads the 3rd by 0.2); row
    1's 2nd and 3rd lie 0.004 apart and its 4th 0.3 below: two routings;
    row 2 holds three within 0.006: three routings; a tie counts as a
    near tie once ``tau`` is above zero; more than ``most`` routings, or
    near ties to the end of what is shown, leave a row out."""
    vals = np.asarray([[0.9, 0.7, 0.5, 0.3, 0.2, 0.1],
                       [0.9, 0.604, 0.6, 0.3, 0.2, 0.1],
                       [0.9, 0.606, 0.603, 0.6, 0.2, 0.1],
                       [0.7, 0.7, 0.7, 0.3, 0.2, 0.1]])
    idx = np.tile(np.arange(10, 16), (4, 1))
    row, experts, cost, over = family.routings_within(vals, idx, 2, 0.01, 8)
    assert not over.any()
    assert row.tolist() == [0, 1, 1, 2, 2, 2, 3, 3, 3]
    sets = [tuple(sorted(e)) for e in experts.tolist()]
    assert sets[:3] == [(10, 11), (10, 11), (10, 12)]
    assert sorted(sets[3:6]) == [(10, 11), (10, 12), (10, 13)]
    assert sorted(sets[6:]) == [(10, 11), (10, 12), (11, 12)]
    np.testing.assert_allclose(cost[:3], [0, 0, 0.004], atol=1e-12)
    np.testing.assert_allclose(sorted(cost[3:6]), [0, 0.003, 0.006],
                               atol=1e-12)
    # The reference's own choice comes first among a row's, at cost 0.
    assert [sets[i] for i in (0, 1, 3, 6)] == [(10, 11)] * 4
    # At tau = 0 every row has its own routing and no other.
    row, experts, cost, over = family.routings_within(vals, idx, 2, 0.0, 1)
    assert row.tolist() == [0, 1, 2, 3] and not cost.any()
    assert experts.tolist() == [[10, 11]] * 4 and not over.any()
    # most = 2: rows 2 and 3 (three routings each) are left out.
    row, _, _, over = family.routings_within(vals, idx, 2, 0.01, 2)
    assert over.tolist() == [False, False, True, True]
    assert row.tolist() == [0, 1, 1]
    # tau so wide that near ties reach the end of what is shown.
    _, _, _, over = family.routings_within(vals, idx, 2, 0.65, 1000)
    assert over.all()


def _greedy_bf16(params, cfg, prompt, n, pad=48):
    from horovod_tpu.serving import mla_moe
    forward = jax.jit(lambda p, t: mla_moe.prefill_forward(
        p, cfg, t, last_only=False, dtype=jnp.bfloat16)[0])
    served = []
    for _ in range(n):
        ctx = np.zeros((pad,), np.int32)
        ctx[:len(prompt) + len(served)] = np.concatenate(
            [prompt, np.asarray(served, int)])
        logits = forward(params, jnp.asarray(ctx)[None])
        served.append(int(jnp.argmax(
            logits[0, len(prompt) + len(served) - 1])))
    return served


def test_a_choice_within_rounding_is_followed_and_the_control_is_not():
    """The program in bfloat16 chooses another expert than the float32
    reference in a row whose scores nearly tie: against the reference's
    own routing alone its token lies 0.71 below the best logit; followed
    through the routings within 0.01 of ``score + bias`` it lies 0.016
    below, which is bfloat16's rounding.  The fp8 control stays at 1.1
    under the same rule: it is not a near tie that moves it."""
    from horovod_tpu.serving import mla_moe
    cfg = family.program_config(TINY_JOYAI)
    params = family.fan_in_experts(weights.make_weights(
        11, mla_moe.param_shapes(cfg, jnp.float32), jnp.float32))
    prompt = np.random.RandomState(11).randint(0, 256, size=10)
    served = _greedy_bf16(params, cfg, prompt, 24)

    def gaps(tau, most):
        config = dict(TINY_JOYAI, limits={
            "served_logit_gap_max": 0.1, "routing_margin_min": tau,
            "routing_branches_max": most})
        return family.served_gaps(config, params, [(prompt, served)], 48,
                                  with_control=True)

    alone, followed = gaps(0.0, 1), gaps(0.01, 16)
    assert alone["tokens_compared"] == followed["tokens_compared"] == 24
    assert alone["served_logit_gap_max"] > 0.5
    assert followed["served_logit_gap_max"] < 0.05
    assert followed["control_logit_gap_max"] > 0.5
    # A row with more routings than the most allowed is not compared; with
    # no row compared the number is infinite: not correct.
    some = gaps(0.06, 4)
    assert 0 < some["tokens_compared"] < 24
    assert some["tokens_sampled"] == 24
    none = gaps(0.6, 1)
    assert none["tokens_compared"] == 0
    assert none["served_logit_gap_max"] == float("inf")


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5])
def test_a_missing_or_a_wrong_expert_is_not_within_rounding(seed,
                                                            monkeypatch):
    """What the margin forgives is a near tie, not a fault: the bfloat16
    program reads 0.016 and 0 under the cell's rule (every routing within
    0.01 followed), the same program with each row's last choice left out
    reads 0.79 and 0.84 and with that choice sent to the next expert 1.39
    and 1.64."""
    from horovod_tpu.ops import moe
    from horovod_tpu.serving import mla_moe
    cfg = family.program_config(TINY_JOYAI)
    params = family.fan_in_experts(weights.make_weights(
        seed, mla_moe.param_shapes(cfg, jnp.float32), jnp.float32))
    prompt = np.random.RandomState(seed % 1000).randint(0, 256, size=10)
    config = dict(TINY_JOYAI, limits={
        "served_logit_gap_max": 0.1, "routing_margin_min": 0.01,
        "routing_branches_max": 16})
    real = moe.route

    def left_out(*args, **kw):
        r = real(*args, **kw)
        return moe.Routing(r.experts, r.weights.at[:, -1].set(0.0))

    def next_expert(*args, **kw):
        r = real(*args, **kw)
        return moe.Routing(r.experts.at[:, -1].set(
            (r.experts[:, -1] + 1) % 16), r.weights)

    read = {}
    for name, route in (("sound", real), ("left_out", left_out),
                        ("next_expert", next_expert)):
        monkeypatch.setattr(moe, "route", route)
        served = _greedy_bf16(params, cfg, prompt, 24)
        monkeypatch.setattr(moe, "route", real)
        got = family.served_gaps(config, params, [(prompt, served)], 48)
        assert got["tokens_compared"] == 24
        read[name] = got["served_logit_gap_max"]
    assert read["sound"] < 0.05
    assert read["left_out"] > 0.3 and read["next_expert"] > 0.3


def test_row_gaps_without_a_margin_are_the_plain_comparison():
    """``tau`` = 0: one routing a row, the reference's own, and the gap
    is the plain ``best - logit[token]`` of ``Reference.logits``, computed
    a second way (rows that stand in for the context's rows)."""
    from horovod_tpu.serving import mla_moe
    cfg = family.program_config(TINY_JOYAI)
    params = family.fan_in_experts(weights.make_weights(
        2 ** 31 + 3, mla_moe.param_shapes(cfg, jnp.float32), jnp.float32))
    rng = np.random.RandomState(3)
    ctx, picks = rng.randint(0, 256, size=30), rng.randint(0, 256, (2, 12))
    ref = family.Reference(TINY_JOYAI, params, 32)
    plain = np.asarray(ref.logits(ctx, 17, 12), np.float64)
    gaps, leaves, (row, cost, _) = ref.row_gaps(ctx, 17, 12, picks, 0.0, 1)
    assert leaves.tolist() == [1] * 12 and row.tolist() == list(range(12))
    assert not cost.any()
    want = plain.max(axis=-1)[None] - plain[np.arange(12)[None], picks]
    np.testing.assert_allclose(gaps, want, rtol=0, atol=2e-5)
    # Followed through every routing within 0.05, a row's gap is the least
    # over them: never above the plain one.
    wide, leaves, _ = ref.row_gaps(ctx, 17, 12, picks, 0.05, 256)
    assert leaves.min() >= 1 and leaves.max() > 1
    assert np.all(wide <= gaps + 2e-5)


def test_seeded_experts_have_their_fan_in():
    """``lib/weights.py`` draws a stacked expert leaf at 1/sqrt(experts);
    the family brings it to 1/sqrt(fan_in), and leaves the rest alone."""
    from horovod_tpu.serving import mla_moe
    cfg = family.program_config(TINY_JOYAI)
    params = family.fan_in_experts(weights.make_weights(
        3, mla_moe.param_shapes(cfg, jnp.float32), jnp.float32))
    ex = params["params"]["layer_1"]["moe"]["experts"]
    assert float(jnp.std(ex["w_gate"])) == pytest.approx(64 ** -0.5, rel=0.05)
    assert float(jnp.std(ex["w_down"])) == pytest.approx(32 ** -0.5, rel=0.05)
    bias = params["params"]["layer_1"]["moe"]["router"][
        "e_score_correction_bias"]
    assert 0.1 < float(jnp.std(bias)) < 0.5          # 1/sqrt(16), seeded
    # The cell runs without a selection bias (even load: the family's
    # ``even_routing``); the expert leaves stay as they were.
    even = family.even_routing(params)
    assert not np.any(np.asarray(even["params"]["layer_2"]["moe"]["router"][
        "e_score_correction_bias"]))
    assert float(jnp.std(even["params"]["layer_1"]["moe"]["experts"][
        "w_up"])) == pytest.approx(64 ** -0.5, rel=0.05)
    assert "moe" not in params["params"]["layer_0"]


# -- the configuration and the counts, by hand --------------------------------------

def test_the_configuration_keeps_every_published_width():
    data = bench_run.load_cell(ROOT, CELL)
    config, cell = data["config"], data["cell"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            if os.path.exists("/opt/skills/guides/model-configs/"
                              "architectures.jsonl") else open(os.devnull) \
            as f:
        rows = [json.loads(line) for line in f if "JoyAI-LLM-Flash" in line]
    for row in rows:                      # the catalog, where it is there
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
    for key, want in {
            "hidden_size": 2048, "q_lora_rank": 1536, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "moe_intermediate_size": 768,
            "intermediate_size": 7168, "n_routed_experts": 256,
            "num_experts_per_tok": 8, "vocab_size": 129280,
            "num_attention_heads": 32, "first_k_dense_replace": 1,
            "rope_theta": 32000000, "routed_scaling_factor": 2.5}.items():
        assert config[key] == want, key
    assert config["reduced"] == ["num_hidden_layers",
                                 "num_nextn_predict_layers"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_nextn_predict_layers": 1}
    assert (config["num_hidden_layers"],
            config["num_nextn_predict_layers"]) == (5, 0)
    assert cell["chips"] == 1 and cell["traffic"] == "offline_doc_lengths"
    s = config["serving"]
    assert s["max_len"] == max(data["traffic"]["prompt_lens"]) + max(
        data["traffic"]["output_lens"])
    entry = {c["name"]: c for c in data["bench"]["configs"]}[
        "joyai_llm_flash"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_byte_counts_by_hand():
    config = bench_run.load_cell(ROOT, CELL)["config"]
    # One expert: three 2048 x 768 matrices in bfloat16.
    assert family.expert_bytes(config) == 3 * 2048 * 768 * 2 == 9_437_184
    # A cached token: (512 + 64) values x 2 bytes x 5 layers.
    assert family.latent_bytes_per_token(config) == 1152 * 5
    attn = (2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512
            + 512 * 32 * 256 + 32 * 128 * 2048)
    assert attn == 26_345_472 + 1536 + 512      # ISSUE's count + two norms
    routed = (attn + 2 * 2048 + 2048 * 256 + 256 + 256 * 4_718_592
              + 4_718_592)
    dense = attn + 2 * 2048 + 3 * 2048 * 7168
    total = dense + 4 * routed + 2 * 129280 * 2048 + 2048
    assert family.weight_bytes(config) == 2 * total
    assert 11.10e9 < family.weight_bytes(config) < 11.14e9
    assert family.moe_layers(config) == 4
    # The shape tree the weights are made from holds the same count.
    from horovod_tpu.serving import mla_moe
    shapes = mla_moe.param_shapes(family.program_config(config),
                                  jnp.bfloat16)
    assert sum(int(np.prod(s.shape)) for s in
               jax.tree.leaves(shapes)) == total


def test_the_cell_lists_its_metrics_and_each_has_a_reader():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    traced = validate.expected_metrics(bench, CELL, True)
    assert set(validate.expected_metrics(bench, CELL, False)) == {
        "serve_tokens_per_s", "setup_s"}
    for name in ("mla_decode_roofline", "moe_gmm_roofline",
                 "moe_gmm_ms_per_round", "moe_experts_touched_pct",
                 "prefill_share_pct.offline", "decode_step_ms.offline",
                 "batch_occupancy_pct", "device_idle_pct.offline",
                 "round_idle_ms.prepare", "round_idle_ms.fetch",
                 "round_idle_ms.bookkeep", "round_idle_ms.between"):
        assert name in traced, name
        assert callable(bench_run.reader_for(name).read)
    assert "decode_attn_roofline" not in traced       # Mistral's kernel
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["moves"] for m in new} == {"serve_tokens_per_s"}


# -- the new readers on a recorded trace --------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    stem = os.path.join(HERE, "data", CELL + ".spans")
    if not os.path.exists(stem + ".xplane.pb.gz"):
        pytest.skip("no recorded trace of the cell")
    dst = tmp_path_factory.mktemp("joyai") / (CELL + ".xplane.pb")
    with gzip.open(stem + ".xplane.pb.gz", "rb") as f, \
            open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(stem + ".counters.json") as f:
        counters = json.load(f)
    return str(dst), xplane.load_trace(str(dst)), counters


@pytest.mark.parametrize("metric", [
    "mla_decode_roofline", "moe_gmm_roofline", "moe_gmm_ms_per_round",
    "moe_experts_touched_pct", "decode_step_ms.offline",
    "device_idle_pct.offline", "round_idle_ms.fetch"])
def test_new_readers_on_the_recorded_trace(metric, recorded):
    path, trace, counters = recorded
    data = bench_run.load_cell(ROOT, CELL)
    busy_s, window_s = xplane.busy_and_window_s(trace)
    ctx = types.SimpleNamespace(
        trace=trace, counters=counters, config=data["config"],
        traffic=data["traffic"], cell=data["cell"], chips=1, family=family,
        peaks=peaks.peaks_for("TPU v5 lite"), busy_s=busy_s,
        window_s=window_s, log=lambda msg: None, xplane_path=path,
        metric=next(m for m in data["bench"]["per_layer"]
                    if m["name"] == metric))
    value = bench_run.reader_for(metric).read(ctx)
    assert value is not None and 0 < value < 1e6
    if metric.endswith("_roofline") or metric.endswith("_pct"):
        assert value <= 100.0


def test_recorded_rounds_name_their_kernels_and_touched_experts(recorded):
    from benchmarks.lib import hostspans
    from benchmarks.readers import moe_experts_touched_pct
    path, trace, counters = recorded
    dev = trace.devices[0]
    ops = xplane.ops_within(dev, family.DECODE_MODULE)
    rounds, _ = xplane.name_sums(dev.modules, family.DECODE_MODULE)
    n_mla, _ = xplane.name_sums(ops, family.MLA_DECODE_KERNEL)
    n_gmm, _ = xplane.name_sums(ops, family.MOE_GMM_KERNEL)
    assert rounds >= 1
    assert n_mla == 5 * rounds and n_gmm == 2 * 4 * rounds
    # No pool-shaped copy inside a round: the pool is updated in place.
    assert not [e for e in ops if " copy(" in e.name
                and "bf16[5,34817,16,640]" in e.name.split(" copy(")[0]]
    touched = moe_experts_touched_pct.touched(
        types.SimpleNamespace(xplane_path=path, cell={"name": CELL}))
    assert len(touched) == rounds
    assert all(8 <= t <= 4 * 256 for t in touched)
    assert hostspans.named(hostspans.load(path), "decode.bookkeep")


def test_new_readers_read_nothing_from_a_program_without_the_kernels():
    """On a program that lacks what this PR adds (the parent, or another
    family) the new readers return None and do not raise."""
    from benchmarks.families import llama_dense
    src = os.path.join(HERE, "data", "mistral_7b_offline.spans.xplane.pb.gz")
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        dst = os.path.join(tmp, "m.xplane.pb")
        with gzip.open(src, "rb") as f, open(dst, "wb") as g:
            shutil.copyfileobj(f, g)
        trace = xplane.load_trace(dst)
        for fam in (llama_dense, family):
            ctx = types.SimpleNamespace(
                trace=trace, counters={"traced_live_tokens": 1000},
                config=bench_run.load_cell(ROOT, CELL)["config"],
                family=fam, peaks=peaks.peaks_for("TPU v5 lite"),
                xplane_path=dst, cell={"name": "x"}, metric=None,
                log=lambda msg: None)
            for name in ("mla_decode_roofline", "moe_gmm_roofline",
                         "moe_gmm_ms_per_round",
                         "moe_experts_touched_pct"):
                assert bench_run.reader_for(name).read(ctx) is None
