"""The ``zaya_cca_moe`` family and the cell ``zaya1_8b_reasoning_offline``
at a size a test run can hold: the ``serve`` kind rehearsed on the CPU
over a tiny model of the block, the fp8 control put in the program's
place failing ``served_logit_gap_max`` where the sound program passes,
what the comparison follows and what it leaves out, the seeded draw of
the vectors ``config.json`` does not fix, the family's byte counts
against ``param_shapes``, the configuration's file against the published
widths, and the new readers on a small trace recorded on the chip.  No
number here is a device metric."""

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
from benchmarks.families import zaya_cca_moe as family
from benchmarks.kinds import serve
from benchmarks.lib import checks, peaks, validate, weights, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "zaya1_8b_reasoning_offline"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY_ZAYA = {
    "kind": "serve", "family": "zaya_cca_moe", "vocab_size": 256,
    "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 1, "router_hidden_size": 16,
    "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"rope_theta": 10000.0}},
    "rms_norm_eps": 1e-5, "max_position_embeddings": 128,
    "compute_dtype": "float32",
    "serving": {"slots": 4, "page_size": 8, "max_len": 64},
    "limits": {"served_logit_gap_max": 1e-3, "routing_margin_min": 0.0,
               "routing_branches_max": 1}}
TINY_REASONING = {"arrival": "at_zero", "order": "fixed",
                  "prompt_lens": [8, 16], "output_lens": [16, 24, 32],
                  "num_requests": 10, "trace_rounds": 4}


def _ctx(config, traffic, seed=2 ** 31 + 7, seconds=0.5, control=""):
    data = {"cell": {"name": "tiny"}, "config": config, "traffic": traffic}
    logs = []
    ctx = bench_run.make_context(data, seed, seconds, "",
                                 jax.devices()[:1], family, logs.append)
    ctx.with_control = control
    return ctx, logs


def _seeded(seed):
    from horovod_tpu.serving import cca_moe
    cfg = family.program_config(TINY_ZAYA)
    shapes = cca_moe.param_shapes(cfg, jnp.float32)
    return cfg, family.seeded_assumptions(
        weights.make_weights(seed, shapes, jnp.float32), seed)


def test_serve_kind_tiny_on_the_new_family():
    ctx, logs = _ctx(TINY_ZAYA, TINY_REASONING)
    out = serve.run(ctx)
    assert out["attempted"] == 10 and out["failed"] == 0
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
    ctx, _ = _ctx(TINY_ZAYA, TINY_REASONING)
    out = serve.run(ctx)
    by_name = {c.name: c for c in out["checks"]}
    assert not by_name["served_logit_gap_max"].ok


def _greedy(params, cfg, prompt, n, pad=32):
    """``n`` greedy tokens after ``prompt`` from the program's prefill, one
    compiled length (everything is causal: padding on the right changes
    no earlier row)."""
    from horovod_tpu.serving import cca_moe
    forward = jax.jit(lambda p, t: cca_moe.prefill_forward(
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
        for n in (12, 20):
            prompt = rng.randint(0, 256, size=n)
            sample.append((prompt, _greedy(params, cfg, prompt, 6)))
        gaps = family.served_gaps(TINY_ZAYA, params, sample, 32,
                                  with_control=True)
        assert gaps["tokens_compared"] == gaps["tokens_sampled"] == 12
        worst_sound = max(worst_sound, gaps["served_logit_gap_max"])
        least_control = min(least_control, gaps["control_logit_gap_max"])
    assert worst_sound < 1e-4
    assert least_control > 0.01 and least_control > 100 * worst_sound


def test_row_gaps_without_a_margin_are_the_plain_comparison():
    """``tau`` = 0: one routing a row, the reference's own, no row left
    out for its neighbours, and the gap is the plain ``best -
    logit[token]`` of ``Reference.logits`` computed a second way (rows
    that stand in for the context's rows: their convolutions read the
    context's row before them, their router stream is their own)."""
    _, params = _seeded(2 ** 31 + 3)
    rng = np.random.RandomState(3)
    ctx, picks = rng.randint(0, 256, size=30), rng.randint(0, 256, (2, 12))
    ref = family.Reference(TINY_ZAYA, params, 32)
    plain = np.asarray(ref.logits(ctx, 17, 12), np.float64)
    gaps, leaves = ref.row_gaps(ctx, 17, 12, picks, 0.0, 1)
    assert leaves.tolist() == [1] * 12
    want = plain.max(axis=-1)[None] - plain[np.arange(12)[None], picks]
    np.testing.assert_allclose(gaps, want, rtol=0, atol=2e-5)
    # The first row of a context (position 0: nothing before it).
    gaps0, leaves0 = ref.row_gaps(ctx, 0, 3, picks[:, :3], 0.0, 1)
    plain0 = np.asarray(ref.logits(ctx, 0, 3), np.float64)
    np.testing.assert_allclose(
        gaps0, plain0.max(axis=-1)[None]
        - plain0[np.arange(3)[None], picks[:, :3]], rtol=0, atol=2e-5)


def test_a_margin_follows_other_experts_and_leaves_out_rows_after_a_tie():
    """Within a margin of 0.005 (softmax scores of 8 experts lie near
    0.125; a row's least lead over three layers is 0.002-0.04) a third of
    the rows have a second routing somewhere: a row followed
    through several is judged by the best of them, never worse than
    plainly; and a row whose one or two predecessors have such a tie is
    not compared at all, for its slot state may be another expert's."""
    _, params = _seeded(11)
    rng = np.random.RandomState(4)
    ctx, picks = rng.randint(0, 256, size=30), rng.randint(0, 256, (1, 12))
    ref = family.Reference(TINY_ZAYA, params, 32)
    plain, _ = ref.row_gaps(ctx, 17, 12, picks, 0.0, 1)
    _, _, _, leads = ref._forward(ctx, keep=True)
    near = np.min(np.stack(leads), axis=0) < 0.005
    wide, leaves = ref.row_gaps(ctx, 17, 12, picks, 0.005, 256)
    for i in range(12):
        after_a_tie = near[17 + i - 1] or near[17 + i - 2]
        assert (leaves[i] == 0) == bool(after_a_tie), i
        if leaves[i]:
            assert wide[0, i] <= plain[0, i] + 2e-5
            assert (leaves[i] > 1) == bool(near[17 + i]), i
    assert 0 < np.sum(leaves > 0) < 12 and leaves.max() > 1
    # More routings than the most allowed: the row is not compared.
    few, kept = ref.row_gaps(ctx, 17, 12, picks, 0.005, 1)
    assert np.all(kept[leaves > 1] == 0) and np.all(kept[leaves == 1] == 1)


def test_the_seeded_draw_puts_every_assumed_vector_off_its_identity():
    """``lib/weights.py`` knows kernels, scales and biases; the family
    brings the stacked experts and the convolution's matrices to their
    fan-in and draws each vector ``config.json`` does not fix 0.1 off its
    identity value, another draw a seed; the balancing bias stays zero."""
    _, a = _seeded(3)
    _, b = _seeded(4)
    blk = a["params"]["layer_1"]
    ex = blk["moe"]["experts"]
    assert float(jnp.std(ex["w_gate"])) == pytest.approx(64 ** -0.5, rel=0.05)
    assert float(jnp.std(ex["w_down"])) == pytest.approx(32 ** -0.5, rel=0.05)
    assert float(jnp.std(blk["attn"]["conv1"]["w"])) == pytest.approx(
        32 ** -0.5, rel=0.05)                       # two taps of 16
    assert float(jnp.std(blk["attn"]["conv0"]["w"])) == pytest.approx(
        2 ** -0.5, rel=0.15)
    assert not np.any(np.asarray(blk["moe"]["router"]["bias"]))
    for owner, key in ((blk["attn"], "tau"), (blk, "attn_alpha"),
                       (blk, "moe_alpha"), (blk["attn"]["conv0"], "b0"),
                       (blk["attn"]["conv1"], "b1"),
                       (blk["moe"]["router"], "gamma")):
        leaf = np.asarray(owner[key], np.float64)
        off = leaf - family.ASSUMED_VECTORS[key]
        assert np.all(np.abs(off) < 0.5) and np.any(np.abs(off) > 0.01), key
        if leaf.size >= 32:
            assert np.std(off) == pytest.approx(family.SPREAD, rel=0.3), key
    assert not np.array_equal(np.asarray(blk["attn_alpha"]),
                              np.asarray(b["params"]["layer_1"]["attn_alpha"]))
    assert not np.array_equal(np.asarray(blk["attn_alpha"]),
                              np.asarray(a["params"]["layer_2"]["attn_alpha"]))


# -- the configuration and the counts, by hand --------------------------------------

def test_the_configuration_keeps_every_published_width():
    data = bench_run.load_cell(ROOT, CELL)
    config, cell = data["config"], data["cell"]
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f
                    if '"name": "ZAYA1-8B"' in line]
    for row in rows:                      # the catalog, where it is there
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
    for key, want in {
            "hidden_size": 2048, "num_attention_heads": 8,
            "num_key_value_heads": 2, "head_dim": 128,
            "moe_intermediate_size": 2048, "num_experts": 16,
            "num_experts_per_tok": 1, "router_hidden_size": 256,
            "vocab_size": 262272, "cca_time0": 2, "cca_time1": 2,
            "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-5,
            "tie_word_embeddings": True, "sliding_window": None}.items():
        assert config[key] == want, key
    assert config["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40}
    assert 20 <= config["num_hidden_layers"] <= 40
    assert cell["chips"] == 1
    assert cell["traffic"] == "offline_reasoning_lengths"
    t = data["traffic"]
    assert (t["prompt_lens"], t["prompt_weights"]) == (
        [128, 256, 512], [0.4, 0.4, 0.2])
    assert (t["output_lens"], t["output_weights"]) == (
        [512, 768, 1024], [0.4, 0.3, 0.3])
    assert t["arrival"] == "at_zero" and t["order"] == "fixed"
    assert config["serving"]["max_len"] == max(t["prompt_lens"]) + max(
        t["output_lens"])
    entry = {c["name"]: c for c in data["bench"]["configs"]}["zaya1_8b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # Every choice config.json does not fix is written down.
    for key in ("qk_mean", "tau", "rope_pairing", "softmax_scale",
                "conv_biases", "value_shift", "router_depth_average",
                "router_mlp", "residual_scaling", "balancing_bias",
                "expert_weights", "routing_margin_min"):
        assert key in config["assumed"], key


def test_byte_counts_against_param_shapes():
    from horovod_tpu.serving import cca_moe
    config = bench_run.load_cell(ROOT, CELL)["config"]
    layers = config["num_hidden_layers"]
    # One expert: three 2048 x 2048 matrices in bfloat16.
    assert family.expert_bytes(config) == 25_165_824
    # A cached token: 2 x (256 keys + 256 values) bytes a layer.
    assert family.kv_bytes_per_token(config) == 1024 * layers
    # A slot: u and a (1,280 each) and W_v2 h (128), bfloat16, a layer.
    assert family.slot_state_bytes(config) == 5376 * layers
    cfg = family.program_config(config)
    assert cfg.slot_state_width * 2 == 5376 and cfg.page_width == 512
    shapes = cca_moe.param_shapes(cfg, jnp.bfloat16)
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert family.weight_bytes(config) == 2 * total
    layer = sum(int(np.prod(s.shape)) for s in
                jax.tree.leaves(shapes["params"]["layer_0"]))
    assert 207.5e6 < layer < 207.9e6                 # ISSUE: about 207.6 M
    assert total == layers * layer + 262272 * 2048 + 2048
    experts = 16 * 3 * 2048 * 2048
    assert layers * experts * 2 / family.weight_bytes(config) > 0.87
    s = config["serving"]
    pool = layers * (s["slots"] * s["max_len"] // s["page_size"] + 1) \
        * s["page_size"] * 1024
    # Weights, pool and slot state leave room for the logits and XLA.
    assert 14.5e9 < family.weight_bytes(config) + pool \
        + s["slots"] * family.slot_state_bytes(config) < 15.0e9


def test_the_cell_lists_its_metrics_and_each_has_a_reader():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    traced = validate.expected_metrics(bench, CELL, True)
    assert set(validate.expected_metrics(bench, CELL, False)) == {
        "serve_tokens_per_s", "setup_s"}
    for name in ("cca_decode_roofline", "moe_peak_expert_share_pct",
                 "moe_gmm_roofline", "moe_gmm_ms_per_round",
                 "decode_step_ms.offline",
                 "batch_occupancy_pct", "device_idle_pct.offline",
                 "round_idle_ms.prepare", "round_idle_ms.fetch",
                 "round_idle_ms.bookkeep", "round_idle_ms.between"):
        assert name in traced, name
        assert callable(bench_run.reader_for(name).read)
    # Another family's kernel; a reader that asks for a key this source
    # names otherwise; and a share that 100 traced rounds of this traffic
    # need not hold (outputs of 512-1,024 tokens: 2.5 s can pass with no
    # request finishing, so with no prefill at all).
    assert "mla_decode_roofline" not in traced
    assert "moe_experts_touched_pct" not in traced
    assert "prefill_share_pct.offline" not in traced
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in new} == {"cca_decode_roofline",
                                        "moe_peak_expert_share_pct"}
    assert {m["moves"] for m in new} == {"serve_tokens_per_s"}
    # One more cell on one chip; the four-chip cell is still the only one.
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "bert_large_dp4"]


# -- the new readers on a recorded trace --------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    stem = os.path.join(HERE, "data", CELL + ".spans")
    if not os.path.exists(stem + ".xplane.pb.gz"):
        pytest.skip("no recorded trace of the cell")
    dst = tmp_path_factory.mktemp("zaya") / (CELL + ".xplane.pb")
    with gzip.open(stem + ".xplane.pb.gz", "rb") as f, \
            open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(stem + ".counters.json") as f:
        counters = json.load(f)
    return str(dst), xplane.load_trace(str(dst)), counters


def _reader_ctx(recorded, metric, fam=family):
    path, trace, counters = recorded
    data = bench_run.load_cell(ROOT, CELL)
    busy_s, window_s = xplane.busy_and_window_s(trace)
    return types.SimpleNamespace(
        trace=trace, counters=counters, config=data["config"],
        traffic=data["traffic"], cell=data["cell"], chips=1, family=fam,
        peaks=peaks.peaks_for("TPU v5 lite"), busy_s=busy_s,
        window_s=window_s, log=lambda msg: None, xplane_path=path,
        metric=next(m for m in data["bench"]["per_layer"]
                    if m["name"] == metric))


@pytest.mark.parametrize("metric", [
    "cca_decode_roofline", "moe_peak_expert_share_pct", "moe_gmm_roofline",
    "moe_gmm_ms_per_round", "decode_step_ms.offline",
    "device_idle_pct.offline", "round_idle_ms.fetch"])
def test_new_readers_on_the_recorded_trace(metric, recorded):
    value = bench_run.reader_for(metric).read(_reader_ctx(recorded, metric))
    assert value is not None and 0 < value < 1e6
    if metric.endswith("_roofline") or metric.endswith("_pct"):
        assert value <= 100.0


def test_recorded_rounds_name_their_kernels_and_their_fullest_expert(
        recorded):
    from benchmarks.lib import hostspans
    from benchmarks.readers import moe_experts_touched_pct, \
        moe_peak_expert_share_pct
    path, trace, counters = recorded
    layers = bench_run.load_cell(ROOT, CELL)["config"]["num_hidden_layers"]
    dev = trace.devices[0]
    ops = xplane.ops_within(dev, family.DECODE_MODULE)
    rounds, _ = xplane.name_sums(dev.modules, family.DECODE_MODULE)
    n_cca, _ = xplane.name_sums(ops, family.CCA_DECODE_KERNEL)
    n_gmm, _ = xplane.name_sums(ops, family.MOE_GMM_KERNEL)
    assert rounds >= 1
    assert n_cca == layers * rounds and n_gmm == 2 * layers * rounds
    # No pool-shaped copy inside a round: the pool is updated in place.
    assert not [e for e in ops if " copy(" in e.name
                and f"bf16[{layers},9217,16,512]"
                in e.name.split(" copy(")[0]]
    ctx = types.SimpleNamespace(xplane_path=path, cell={"name": CELL})
    touched = moe_experts_touched_pct.touched(ctx)
    shares = moe_peak_expert_share_pct.shares(ctx)
    assert len(touched) == len(shares) == rounds
    assert all(layers <= t <= layers * 16 for t in touched)
    assert all(1 / 16 <= s <= 1 for s in shares)
    assert hostspans.named(hostspans.load(path), "decode.bookkeep")


def test_new_readers_read_nothing_from_a_program_without_what_they_read(
        tmp_path):
    """On a program that lacks what this PR adds (the parent, or another
    family: a trace with no ``hvd_cca_decode`` call and no
    ``peak_expert_rows`` on its bookkeep spans) the new readers return
    None and do not raise."""
    from benchmarks.families import joyai_mla_moe, llama_dense
    config = bench_run.load_cell(ROOT, CELL)["config"]
    for name, fams in (
            ("mistral_7b_offline", (llama_dense, family)),
            ("joyai_llm_flash_offline_docs", (joyai_mla_moe, family))):
        src = os.path.join(HERE, "data", name + ".spans.xplane.pb.gz")
        dst = str(tmp_path / (name + ".xplane.pb"))
        with gzip.open(src, "rb") as f, open(dst, "wb") as g:
            shutil.copyfileobj(f, g)
        trace = xplane.load_trace(dst)
        for fam in fams:
            ctx = types.SimpleNamespace(
                trace=trace, counters={"traced_live_tokens": 1000},
                config=config, family=fam,
                peaks=peaks.peaks_for("TPU v5 lite"), xplane_path=dst,
                cell={"name": "x"}, metric=None, log=lambda msg: None)
            for metric in ("cca_decode_roofline",
                           "moe_peak_expert_share_pct"):
                assert bench_run.reader_for(metric).read(ctx) is None
