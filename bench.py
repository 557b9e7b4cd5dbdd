"""Headline benchmark: ResNet-50 data-parallel training throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Mirrors the reference's synthetic benchmark recipe (``tf_cnn_benchmarks`` /
``*_synthetic_benchmark.py``, SURVEY.md section 6): synthetic ImageNet-shaped
data resident on device, fwd+bwd+update per step through the full framework
path (DistributedOptimizer fused allreduce, bf16 compute, space-to-depth
stem -- mathematically identical to the 7x7/2 stem, see
``models/resnet.py::s2d_conv_init_kernel``).

``vs_baseline`` compares against 2,542 img/s/chip, recorded under THIS
config (batch 256/chip, space-to-depth stem) on an earlier runtime
(July-August 2026, not reproduced on today's libtpu) -- same-config
comparison so the ratio is pure regression signal, not config drift.
BASELINE.json.published is empty (the driver recorded no reference
numbers), so our own prior measurement is the regression baseline until
the S1 benchmark re-measures it.  The stderr diagnostics carry the
per-window numbers and stddev, and the JSON line names the config and
the device it ran on.

The default (RN50) mode measures a TPU: it refuses any other backend
unless ``BENCH_TINY=1`` (the CPU-runnable plumbing config, never a
speed).

Timing note: ``jax.block_until_ready`` is the documented fence.  On
today's runtime (jax 0.9.0, libtpu 0.0.34) ``chip_smoke.py`` times one
RN50 window both ways and they agree (0.09829 vs 0.09828 s/step; smoke,
v5e, 2026-09-26).  The windows here end in a host fetch of the final
scalar loss, which fences just as well: loss_N depends on params_{N-1}
and therefore on every prior step.
"""

import json
import os
import sys
import threading
import time

WATCHDOG_S = int(os.environ.get("BENCH_WATCHDOG_S", "900"))
BATCH = int(os.environ.get("BENCH_BATCH", "256"))
STEPS = int(os.environ.get("BENCH_STEPS", "40"))       # per window
WINDOWS = int(os.environ.get("BENCH_WINDOWS", "3"))
# img/s/chip recorded at batch 256 with the space-to-depth stem -- the SAME
# config this script runs -- on an earlier runtime (July-August 2026, not
# reproduced); vs_baseline is a same-config regression ratio against it.
BASELINE = 2542.27
BASELINE_CONFIG = "batch256_s2d_bf16"
# HOROVOD_ZERO=1 (or HVD_TPU_ZERO=1) benches the ZeRO-1 sharded-optimizer
# path instead: bare SGD + zero_init state, reduce-scatter grads,
# allgathered params.  Different config string -> vs_baseline emits null
# (not comparable to the replicated baseline).
ZERO = any(os.environ.get(v, "").strip().lower() in ("1", "true", "yes", "on")
           for v in ("HVD_TPU_ZERO", "HOROVOD_ZERO"))
# BENCH_SCANLOOP=1 (or HOROVOD_STEPS_PER_EXEC>1) benches the steps-per-
# execution scan runner (make_flax_train_loop): k steps per dispatch, one
# device->host fence per window element, reported alongside the host-
# dispatch-gap fraction (timeline.DispatchGapMonitor).  Different config
# string -> vs_baseline null.
def _env_on(*names):
    return any(os.environ.get(v, "").strip().lower()
               in ("1", "true", "yes", "on") for v in names)


SCAN_K = int(os.environ.get("HVD_TPU_STEPS_PER_EXEC",
                            os.environ.get("HOROVOD_STEPS_PER_EXEC", "0"))
             or 0)
SCANLOOP = _env_on("BENCH_SCANLOOP") or SCAN_K > 1
if SCANLOOP and SCAN_K < 1:
    SCAN_K = 4
# BENCH_OVERLAP=1 (or HOROVOD_MICROBATCHES>1) benches the backward-overlap
# microbatched exchange (make_flax_train_step(microbatches=k)): per-bucket
# reduce-scatter of microbatch i scheduled against backward compute of
# microbatch i+1, reported alongside the exchange-overlap fraction
# (timeline.OverlapMonitor).  Different config string -> vs_baseline null.
MICRO_K = int(os.environ.get("HVD_TPU_MICROBATCHES",
                             os.environ.get("HOROVOD_MICROBATCHES", "0"))
              or 0)
OVERLAP = _env_on("BENCH_OVERLAP") or MICRO_K > 1
if OVERLAP and MICRO_K < 1:
    MICRO_K = 4
# HOROVOD_COMPRESSION=powersgd:<rank>|topk:<fraction> benches the
# error-feedback compressed gradient exchange (collectives/compression.py):
# the DistributedOptimizer threads residual state through the step and the
# result carries wire bytes vs the uncompressed planner payload.  Composes
# with HOROVOD_ZERO=1 (compressed param-delta allgather) and
# HOROVOD_MICROBATCHES>1 (one exchange per step).  Different config string
# -> vs_baseline null.
COMPRESSION = (os.environ.get("HVD_TPU_COMPRESSION")
               or os.environ.get("HOROVOD_COMPRESSION") or "").strip()
# BENCH_TINY=1 swaps RN50 for a one-stage 8-filter ResNet on 32x32 inputs:
# a plumbing smoke config (CPU-runnable), never comparable to the baseline.
TINY = _env_on("BENCH_TINY")
# BENCH_EAGER=1 benches the eager control plane instead of training
# throughput: runs examples/eager_latency_probe.py under the launcher
# (BENCH_EAGER_NP procs, default 2, forced CPU) and re-emits its JSON
# line (sync vs deferred-unfused vs deferred-fused 8-op batch, grouped
# reference).  Latency metric, no throughput baseline -> vs_baseline null.
EAGER = _env_on("BENCH_EAGER")
EAGER_NP = int(os.environ.get("BENCH_EAGER_NP", "2"))
# BENCH_CHAOS=1 runs the elastic recovery drill instead of throughput: a
# deterministic HOROVOD_CHAOS comm fault kills half the world mid-run
# (8 -> 4 virtual CPU devices), the run recovers checkpointlessly via
# JaxState.resize (ZeRO shards re-laid out, EF residual mass carried) and
# reports steps-to-recover plus the 30-step convergence-proxy parity
# against the uninterrupted run.  Never throughput-comparable ->
# vs_baseline null.
CHAOS_BENCH = _env_on("BENCH_CHAOS")
CHAOS_SPEC = os.environ.get("BENCH_CHAOS_SPEC",
                            "seed=7;comm@step=11,rank=0")
# BENCH_SERVING=1 runs the continuous-batching inference drill instead of
# training throughput: the LLAMA_SERVE toy decoder served over an 8-way
# tensor-parallel virtual CPU mesh, a seeded open-loop Poisson load from
# serving/loadgen.py, reporting tokens/s plus p50/p99 TTFT and per-token
# latency and mean batch occupancy.  A CPU-mesh serving drill has no
# training-throughput peer -> vs_baseline null.
SERVING_BENCH = _env_on("BENCH_SERVING")
SERVING_REQUESTS = int(os.environ.get("BENCH_SERVING_REQUESTS", "24"))
SERVING_RATE = float(os.environ.get("BENCH_SERVING_RATE", "50"))
SERVING_SLOTS = int(os.environ.get("BENCH_SERVING_SLOTS", "8"))
# BENCH_SERVING_V2=1 runs the round-15 serving overhaul drill, two phases
# in one process.  Phase A (throughput): the BENCH_r11 workload with
# longer outputs, served with speculative decoding (self-draft
# ModelDrafter, k tokens verified in one fixed-shape width-k+1 step) and
# fp8 KV-cache compression on -- gated at >= 2x r11's 262.95 tokens/s
# with mean batch occupancy > 0.8.  Phase B (latency): the 512/2048/4096
# kilotoken mixture through chunked flash prefill vs an identical
# no-chunk run, gated on TTFT p99 at the 4k bucket (chunked must beat
# whole-prompt prefill, which blocks the decode loop for entire
# kilotoken forwards).  vs_baseline reports the phase-A speedup over
# r11; tests/test_bench_guard.py::scan_serving_v2_entries enforces the
# block shape and both gates on the committed BENCH_r15.json.
SERVING_V2_BENCH = _env_on("BENCH_SERVING_V2")
SERVING_V2_REQUESTS = int(os.environ.get("BENCH_SERVING_V2_REQUESTS", "32"))
SERVING_V2_RATE = float(os.environ.get("BENCH_SERVING_V2_RATE", "100"))
SERVING_V2_K = int(os.environ.get("BENCH_SERVING_V2_K", "4"))
SERVING_V2_CHUNK = int(os.environ.get("BENCH_SERVING_V2_CHUNK", "512"))
SERVING_V2_LONG_REQUESTS = int(
    os.environ.get("BENCH_SERVING_V2_LONG_REQUESTS", "12"))
# Round-11 recorded serving throughput (BENCH_r11.json) on the same
# 8-device virtual CPU mesh -- the denominator of the phase-A gate.
SERVING_R11_TOKENS_PER_S = 262.95
# BENCH_AUTOSCALE=1 runs the SLO-driven elastic serving drill: the same
# LLAMA_SERVE decoder behind the ServingControlPlane, with a kill@ +
# slow@ chaos spec fired virtually under the Poisson load.  The closed
# loop must shrink off the dead rank, auto-evict the slow one, and carry
# every in-flight request across both transitions (drain/re-prefill);
# the recorded SLO-violation seconds are gated against the budget by
# tests/test_bench_guard.py::scan_autoscale_entries.
AUTOSCALE_BENCH = _env_on("BENCH_AUTOSCALE")
AUTOSCALE_REQUESTS = int(os.environ.get("BENCH_AUTOSCALE_REQUESTS", "48"))
AUTOSCALE_RATE = float(os.environ.get("BENCH_AUTOSCALE_RATE", "40"))
AUTOSCALE_SPEC = os.environ.get(
    "BENCH_AUTOSCALE_SPEC",
    "kill@step=20,rank=7;slow@step=35,rank=2,secs=0.2")
AUTOSCALE_BUDGET_S = float(os.environ.get("BENCH_AUTOSCALE_BUDGET_S", "30"))
# BENCH_ROOFLINE=1 runs the single-chip kernel roofline drill instead of
# training: each HOROVOD_PALLAS family (flash-decoding, fused PowerSGD
# update, fused BN backward) timed kernel-on vs the XLA reference on the
# same shapes, with per-family flop/byte accounting against the v5e
# peaks.  On CPU the kernels run in the Pallas interpreter, so the
# on/off ratio measures PARITY PLUMBING (the dispatch really switches
# and agrees numerically), not speed -- the block says which backend
# produced it, and the speedup column is only meaningful on TPU.
ROOFLINE_BENCH = _env_on("BENCH_ROOFLINE")
ROOFLINE_ITERS = int(os.environ.get("BENCH_ROOFLINE_ITERS", "5"))
# BENCH_SDC=1 runs the silent-data-corruption defense drill: (1) a
# nan-poisoned input shard is screened by the in-step guard
# (HOROVOD_GUARD) and the optimizer update skipped, (2) a sustained
# 3-step anomaly trips the streak limit and the snapshot ledger rolls
# back past the poison window, replaying to <= 1.25x loss parity with
# the uninterrupted run, (3) a single flipped mantissa bit on one
# replica -- finite, invisible to the numeric screen -- is caught by the
# in-band checksum tripwire (HOROVOD_DESYNC_CHECK_STEPS) within one
# check interval, attributed to the victim rank, and quarantined by
# shrinking the world off that rank.  A CPU recovery drill has no
# throughput peer -> vs_baseline null; the committed entry is gated by
# tests/test_bench_guard.py::scan_sdc_entries.
SDC_BENCH = _env_on("BENCH_SDC")
SDC_STEPS = int(os.environ.get("BENCH_SDC_STEPS", "30"))
# BENCH_PREFIX=1 runs the round-17 prefix-shared KV cache drill: the
# LLAMA_SERVE 8-way mesh serves a kilotoken prefix-shared mixture (75%
# of requests share one of two fixed 1024-token system prefixes, a
# quarter open two-turn sessions, gold/bronze tenant mix) twice --
# cold (prefix cache off) and warm (radix cache on) at matched load --
# then replays matched uniform vs adversarial tenant mixes (same seed,
# so prompts and arrival times are byte-identical; only the tenant
# labels move) for the fairness gate.  Gates: prefill FLOPs avoided
# >= 0.4, warm TTFT p99 strictly under cold, warm end-to-end tokens/s
# (prompt + generated over wall clock -- the comparable number at
# kilotoken context) >= BENCH_r15's 975.11 headline, zero leaked pages
# with balanced refcounts after drop_all, and every tenant class
# inside its TTFT SLO budget under the adversarial mix at >= 90% of
# the uniform-mix throughput.  Committed entry gated by
# tests/test_bench_guard.py::scan_prefix_entries.
PREFIX_BENCH = _env_on("BENCH_PREFIX")
PREFIX_REQUESTS = int(os.environ.get("BENCH_PREFIX_REQUESTS", "28"))
PREFIX_RATE = float(os.environ.get("BENCH_PREFIX_RATE", "6"))
SERVING_R15_TOKENS_PER_S = 975.11
# BENCH_PLANIR=1 runs the round-19 exchange-plan IR drill: the plans a
# real step's consumers make (reverse-planned DP hier buckets, the
# ZeRO-1 arena, the SDC guard screen, a serving decode step, one MoE
# layer) are built host-side for a virtual 2x32 contended-DCN mesh
# (2 DCN slices x 32 ICI chips, world 64), then the whole-step leg
# list is issued A/B -- HOROVOD_EXCHANGE_SCHEDULE=bandwidth order vs
# pure program order -- through controller.fusion.simulate_issue's
# two-link contention model on the v5e ChipSpec.  Gates: (1) the two
# orders carry a BYTE-IDENTICAL wire payload (scheduling moves WHEN
# legs issue, never WHAT goes on the wire), (2) zero warm replans (a
# repeat step resolves every plan from the shared cache -- the
# plan-once claim), (3) the scheduled order's modeled dispatch-gap
# fraction strictly below program order's with makespan no worse.
# Purely a host-side model -> vs_baseline null; the committed entry is
# gated by tests/test_bench_guard.py::scan_planir_entries.
PLANIR_BENCH = _env_on("BENCH_PLANIR")
# BENCH_FLEET=1 runs the round-20 disaggregated serving fleet drill in
# three phases on the forced 8-way CPU host.  Parity: a 1-prefill +
# 1-decode fleet streaming f32 KV pages over the rendezvous plane must
# emit token streams BITWISE equal to a colocated engine on the same
# mesh spec, with every handoff actually travelling the wire.
# Throughput (the headline, matched 8 devices): the fleet -- prefill
# workers on one 4-device half, the decode engine on the other -- must
# beat the BEST single colocated engine (tp=8 and tp=4 both measured)
# on generated tokens/s, because offloading prompt math means the
# decode host never stalls a batch for a kilotoken prefill.  Chaos: the
# fleet_spec surge (arrival rate DOUBLES mid-run, 3:1 arrival skew)
# plus a prefill-host kill mid-handoff; the scaler must grow to 2
# decode engines under live traffic (migrating queued requests), the
# decode side must absorb the reaped KV objects via local-prefill
# fallback, SLO-violation seconds must stay under
# BENCH_FLEET_BUDGET_S, and BOTH decode engines must drain to zero
# leaked pages with balanced refcounts.  CPU-mesh serving drill -> the
# vs_baseline peer is the best colocated engine at matched device
# count; the committed entry is gated by
# tests/test_bench_guard.py::scan_fleet_entries.
FLEET_BENCH = _env_on("BENCH_FLEET")
FLEET_REQUESTS = int(os.environ.get("BENCH_FLEET_REQUESTS", "32"))
FLEET_RATE = float(os.environ.get("BENCH_FLEET_RATE", "40"))
FLEET_BUDGET_S = float(os.environ.get("BENCH_FLEET_BUDGET_S", "30"))


def _config() -> str:
    base = f"tinycnn_batch{BATCH}" if TINY else f"batch{BATCH}_s2d_bf16"
    comp = COMPRESSION.replace(":", "").replace(".", "p")
    return (base + ("_zero1" if ZERO else "")
            + (f"_scanloop{SCAN_K}" if SCANLOOP else "")
            + (f"_microbatch{MICRO_K}" if OVERLAP else "")
            + (f"_{comp}" if comp else ""))
FLOPS_PER_IMAGE = 12.3e9  # RN50 fwd+bwd estimate
# Per-chip peaks keyed by ``jax.devices()[0].device_kind``: (bf16 FLOP/s,
# HBM bytes/s).  Source: Google Cloud documentation, "TPU v5e".  A device
# that is not in the table is an error, not a default.
PEAKS = {"TPU v5 lite": (197e12, 819e9)}
V5E_BF16_PEAK, V5E_HBM = PEAKS["TPU v5 lite"]


def _bf16_peak(device_kind: str) -> float:
    if device_kind not in PEAKS:
        sys.exit(f"bench.py: no peak recorded for device_kind "
                 f"{device_kind!r}; add it to PEAKS with its source")
    return PEAKS[device_kind][0]


def _watchdog():
    time.sleep(WATCHDOG_S)
    print(json.dumps({"metric": "resnet50_images_per_sec_per_chip",
                      "value": 0.0, "unit": "images/s/chip",
                      "vs_baseline": 0.0,
                      "error": f"watchdog: no result in "
                               f"{WATCHDOG_S}s"}), flush=True)
    os._exit(2)


def _main_chaos():
    """BENCH_CHAOS=1: deterministic kill-half-the-world recovery drill."""
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(8, cpu=True)  # before jax touches the backend
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu import elastic
    from horovod_tpu.elastic import chaos
    from horovod_tpu.elastic.run_loop import _looks_like_comm_failure
    from horovod_tpu.timeline import metrics as tm

    comp = "topk:0.25"
    steps, commit_every = 30, 3
    rng = np.random.RandomState(0)
    w_true = rng.randn(16, 4).astype(np.float32)
    x = rng.randn(64, 16).astype(np.float32)
    data = (x, x @ w_true)
    params0 = {"w1": rng.randn(16, 32).astype(np.float32) * 0.3,
               "b1": np.zeros((32,), np.float32),
               "w2": rng.randn(32, 4).astype(np.float32) * 0.3,
               "b2": np.zeros((4,), np.float32)}

    def loss_fn(p, batch):
        bx, by = batch
        h = jnp.tanh(bx @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] + p["b2"] - by) ** 2)

    def build():
        p = hvd.replicate(params0)
        st = hvd.zero_init(optax.adam(0.05), p, compression=comp)
        step = hvd.make_train_step(loss_fn, optax.adam(0.05), zero_stage=1,
                                   zero_compression=comp)
        return p, st, step, hvd.shard_batch(data)

    hvd.init()

    # Uninterrupted reference run (world 8).
    p, st, step, batch = build()
    for _ in range(steps):
        p, st, loss = step(p, st, batch)
    base_loss = float(loss)

    # Chaos run: same problem, injected comm fault, 8 -> 4 recovery.
    hvd.shutdown()
    hvd.init()
    world_before = hvd.size()
    p, st, step, batch = build()
    state = elastic.JaxState(params=p, opt_state=st, batch=0)
    chaos.install(CHAOS_SPEC, rank=0, size=1)
    inj = chaos.injector()
    recovery = None
    batch_at_fault = None
    while state.batch < steps:
        try:
            inj.on_step(state.batch + 1)
            state.params, state.opt_state, loss = step(
                state.params, state.opt_state, batch)
            state.batch += 1
            if state.batch % commit_every == 0:
                state.commit()
        except chaos.ChaosCommError as e:
            if not _looks_like_comm_failure(e) or recovery is not None:
                raise
            batch_at_fault = state.batch
            state.restore()
            hvd.shutdown()
            hvd.init(devices=jax.devices()[:4])
            recovery = state.resize(world_before, 4)
            tm.registry().counter(
                "horovod_elastic_ranks_lost",
                "Ranks lost across elastic recoveries").inc(
                    world_before - 4)
            step = hvd.make_train_step(loss_fn, optax.adam(0.05),
                                       zero_stage=1, zero_compression=comp)
            batch = hvd.shard_batch(data)

    if recovery is None:
        print(json.dumps({"metric": "elastic_chaos_recovery", "value": 0.0,
                          "unit": "loss_ratio", "vs_baseline": None,
                          "error": f"chaos fault never fired "
                                   f"({CHAOS_SPEC!r})"}), flush=True)
        os._exit(2)
    ratio = float(loss) / base_loss
    result = {
        "metric": "elastic_chaos_recovery",
        "value": round(ratio, 4),
        "unit": "loss_ratio",
        "vs_baseline": None,  # a CPU recovery drill has no throughput peer
        "config": _config() + "_chaos",
        "baseline_config": _config() + "_chaos",
        "chaos": {
            "spec": CHAOS_SPEC,
            "steps_to_recover": batch_at_fault - state_batch_after_restore(
                batch_at_fault, commit_every),
            "parity_ratio": round(ratio, 4),
            "ranks_lost": world_before - 4,
            "world_before": world_before,
            "world_after": 4,
            "ef_residual_recovered_bytes": int(tm.registry().counter(
                "horovod_ef_residual_recovered_bytes").value),
            "recovery_report": {k: v for k, v in recovery.items()},
        },
    }
    print(json.dumps(result), flush=True)
    os._exit(0)


def _main_sdc():
    """BENCH_SDC=1: silent-data-corruption defense drill.

    Three acts on one 8-device virtual CPU mesh, all against the same
    tanh-MLP problem under a lockstep DistributedOptimizer (grad
    allreduce -- the host snapshot IS the collective state):

    1. clean baseline: SDC_STEPS guarded steps, proving the screen fires
       zero false activations;
    2. sustained nan anomaly -> ledger rollback: a poisoned input shard
       from step 11 is skipped in-step (params bitwise untouched) until
       the 3-step streak raises SustainedAnomalyError; the ledger rolls
       back PAST the poison window and the healed replay must land
       within 1.25x loss parity of the uninterrupted run;
    3. bitflip -> tripwire quarantine: one flipped mantissa bit on one
       rank's replica stays finite (the numeric screen cannot see it);
       the in-band checksum tripwire catches it within one check
       interval, attributes the victim by majority vote, and the world
       shrinks off that rank with state intact.
    """
    os.environ.setdefault("HOROVOD_GUARD", "1")
    os.environ.setdefault("HOROVOD_GUARD_STREAK", "3")
    os.environ.setdefault("HOROVOD_SNAPSHOT_STEPS", "2")
    os.environ.setdefault("HOROVOD_DESYNC_CHECK_STEPS", "2")
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(8, cpu=True)  # before jax touches the backend
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu import elastic
    from horovod_tpu.core import desync, guard
    from horovod_tpu.core.exceptions import (CorruptRankError,
                                             SustainedAnomalyError)
    from horovod_tpu.elastic import chaos
    from horovod_tpu.timeline import metrics as tm

    steps, commit_every = SDC_STEPS, 3
    poison_from = 11
    rng = np.random.RandomState(0)
    w_true = rng.randn(16, 4).astype(np.float32)
    x = rng.randn(64, 16).astype(np.float32)
    data = (x, x @ w_true)
    params0 = {"w1": rng.randn(16, 32).astype(np.float32) * 0.3,
               "b1": np.zeros((32,), np.float32),
               "w2": rng.randn(32, 4).astype(np.float32) * 0.3,
               "b2": np.zeros((4,), np.float32)}

    def loss_fn(p, batch):
        bx, by = batch
        h = jnp.tanh(bx @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] + p["b2"] - by) ** 2)

    def build():
        opt = hvd.DistributedOptimizer(optax.adam(0.05))
        p = hvd.replicate(params0)
        st = opt.init(p)
        step = hvd.make_train_step(loss_fn, opt)
        return p, st, step, hvd.shard_batch(data)

    reg = tm.registry()
    hvd.init()
    guard.reset()
    world = hvd.size()

    # Act 1: uninterrupted guarded reference -- zero false activations.
    p, st, step, batch = build()
    for _ in range(steps):
        p, st, loss = step(p, st, batch)
    base_loss = float(loss)
    clean_skips = int(reg.counter("horovod_guard_skipped_total").value)

    # Act 2: sustained nan anomaly -> streak trip -> ledger rollback.
    chaos.reset()
    hvd.shutdown()
    hvd.init()
    guard.reset()
    p, st, step, batch = build()
    poisoned = hvd.shard_batch(chaos.poison_batch(
        tuple(jnp.asarray(a) for a in data)))
    state = elastic.JaxState(params=p, opt_state=st, batch=0)
    wedged = True
    rollback_report = None
    while state.batch < steps:
        nxt = state.batch + 1
        try:
            use = poisoned if (wedged and nxt >= poison_from) else batch
            state.params, state.opt_state, loss = step(
                state.params, state.opt_state, use)
            state.batch = nxt
            if state.batch % commit_every == 0:
                state.commit()
        except SustainedAnomalyError:
            if rollback_report is not None:
                raise
            wedged = False  # the rolled-back replay reads a healed shard
            rollback_report = state.rollback(
                before_commit=(poison_from - 1) // commit_every)
            if rollback_report is None:
                break
    skipped = int(reg.counter("horovod_guard_skipped_total").value
                  ) - clean_skips
    ratio = float(loss) / base_loss

    # Act 3: bitflip on one replica -> tripwire attribution + quarantine.
    victim = world - 1
    state2 = elastic.JaxState(params=hvd.replicate(params0), batch=0)
    state2.commit()  # commit 1: off-cadence, the flip rides undetected
    state2.params = desync.corrupt_replica(state2.params, victim)
    attributed = None
    commits_to_detect = 0
    try:
        commits_to_detect = 1
        state2.commit()  # commit 2: tripwire samples -- one interval later
    except CorruptRankError as e:
        attributed = list(e.ranks)
    world_after = world
    if attributed == [victim]:
        survivors = [d for i, d in enumerate(jax.devices()) if i != victim]
        survivors = survivors[:len(survivors) // 2 * 2 or 1]
        hvd.shutdown()
        hvd.init(devices=survivors)
        state2.restore()  # pre-corruption commit: quarantine keeps state
        world_after = hvd.size()

    ok = (rollback_report is not None and 0 < ratio <= 1.25
          and clean_skips == 0 and skipped >= 1 and attributed == [victim])
    result = {
        "metric": "sdc_defense_recovery",
        "value": round(ratio, 4),
        "unit": "loss_ratio",
        "vs_baseline": None,  # a CPU recovery drill has no throughput peer
        "config": _config() + "_sdc",
        "baseline_config": _config() + "_sdc",
        "sdc": {
            "steps": steps,
            "guard": {
                "clean_skips": clean_skips,
                "poison_from_step": poison_from,
                "skipped": skipped,
                "streak_limit": int(os.environ["HOROVOD_GUARD_STREAK"]),
            },
            "rollback": {
                "report": rollback_report,
                "resumed_batch": (rollback_report["commit"] * commit_every
                                  if rollback_report else None),
                "parity_ratio": round(ratio, 4),
                "snapshot_steps": int(os.environ["HOROVOD_SNAPSHOT_STEPS"]),
            },
            "tripwire": {
                "victim_rank": victim,
                "attributed": attributed,
                "check_interval_commits": int(
                    os.environ["HOROVOD_DESYNC_CHECK_STEPS"]),
                "detected_within_commits": commits_to_detect,
                "world_before": world,
                "world_after": world_after,
                "checks": int(reg.counter(
                    "horovod_guard_tripwire_checks_total").value),
                "trips": int(reg.counter(
                    "horovod_guard_tripwire_trips_total").value),
            },
            "counters": {
                "horovod_guard_steps_total": int(reg.counter(
                    "horovod_guard_steps_total").value),
                "horovod_guard_skipped_total": int(reg.counter(
                    "horovod_guard_skipped_total").value),
                "horovod_guard_rollbacks_total": int(reg.counter(
                    "horovod_guard_rollbacks_total").value),
            },
        },
    }
    if not ok:
        result["error"] = "sdc drill failed a gate (see sdc block)"
    print(json.dumps(result), flush=True)
    os._exit(0 if ok else 2)


def _main_serving():
    """BENCH_SERVING=1: continuous-batching serving throughput drill."""
    import dataclasses
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(8, cpu=True)  # before jax touches the backend
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from horovod_tpu import serving
    from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM

    cfg = LLAMA_SERVE
    model = LlamaLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("tp",))
    eng = serving.ServingEngine(cfg, params, mesh=mesh,
                                slots=SERVING_SLOTS, page_size=8,
                                max_len=64)
    spec = serving.LoadSpec(num_requests=SERVING_REQUESTS,
                            rate_rps=SERVING_RATE,
                            prompt_lens=(4, 8, 16), output_lens=(4, 8),
                            vocab_size=cfg.vocab_size, seed=11)
    # Warm-up pass compiles the decode step and every prompt-length
    # prefill variant outside the timed run (same length mix, tiny N).
    eng.serve(serving.generate(
        dataclasses.replace(spec, num_requests=6, seed=1)))
    report = eng.serve(serving.generate(spec))

    config = f"llama_serve_w8_slots{SERVING_SLOTS}"
    result = {
        "metric": "serving_tokens_per_sec",
        "value": round(report.tokens_per_s, 2),
        "unit": "tokens/s",
        "vs_baseline": None,  # CPU-mesh serving drill: no throughput peer
        "config": config,
        "baseline_config": config,
        "serving": {
            "world": 8,
            "slots": SERVING_SLOTS,
            "requests": report.num_requests,
            "completed": report.completed,
            "rejected": report.rejected,
            "prompt_tokens": report.prompt_tokens,
            "new_tokens": report.new_tokens,
            "decode_steps": report.decode_steps,
            "tokens_per_s": round(report.tokens_per_s, 2),
            "ttft_p50_ms": round(report.ttft_p50_s * 1e3, 3),
            "ttft_p99_ms": round(report.ttft_p99_s * 1e3, 3),
            "token_latency_p50_ms": round(
                report.token_latency_p50_s * 1e3, 3),
            "token_latency_p99_ms": round(
                report.token_latency_p99_s * 1e3, 3),
            "batch_occupancy": round(report.mean_occupancy, 4),
            "load": {"rate_rps": SERVING_RATE,
                     "num_requests": SERVING_REQUESTS,
                     "prompt_lens": list(spec.prompt_lens),
                     "output_lens": list(spec.output_lens),
                     "seed": spec.seed},
        },
    }
    print(json.dumps(result), flush=True)
    os._exit(0)


def _main_serving_v2():
    """BENCH_SERVING_V2=1: round-15 serving throughput overhaul drill."""
    import dataclasses
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(8, cpu=True)  # before jax touches the backend
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from horovod_tpu import serving
    from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM

    cfg = LLAMA_SERVE
    model = LlamaLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("tp",))
    slots = SERVING_SLOTS

    # --- phase A: speculative throughput on the r11 workload shape -------
    # Self-draft: the drafter runs the target model on its own 1-device
    # mesh, so drafts disagree with the sharded verify argmax only where
    # layout changes the float rounding -- acceptance stays near 1 and
    # each width-(k+1) dispatch emits ~k+1 tokens where r11 paid one
    # 8-device dispatch per token.  fp8 KV compression rides along to
    # show the gather-path blend at full throughput.
    drafter = serving.ModelDrafter(cfg, params, slots=slots, page_size=8,
                                   max_len=64, dtype=jnp.float32)
    eng_a = serving.ServingEngine(cfg, params, mesh=mesh, slots=slots,
                                  page_size=8, max_len=64,
                                  spec_decode=True, spec_k=SERVING_V2_K,
                                  drafter=drafter, kv_compress=True)
    spec_a = serving.LoadSpec(num_requests=SERVING_V2_REQUESTS,
                              rate_rps=SERVING_V2_RATE,
                              prompt_lens=(4, 8, 16),
                              output_lens=(16, 24),
                              vocab_size=cfg.vocab_size, seed=11)
    # Warm-up compiles prefill variants, the verify step, and the
    # drafter's own decode step outside the timed run.
    eng_a.serve(serving.generate(
        dataclasses.replace(spec_a, num_requests=6, seed=1)))
    rep_a = eng_a.serve(serving.generate(spec_a))
    print(f"# phase A: {rep_a.tokens_per_s:.1f} tokens/s, "
          f"acceptance {rep_a.acceptance_rate:.3f}, "
          f"occupancy {rep_a.mean_occupancy:.3f}", file=sys.stderr)

    # --- phase B: kilotoken TTFT, chunked vs whole-prompt prefill --------
    def _long_run(chunk):
        eng = serving.ServingEngine(cfg, params, mesh=mesh, slots=slots,
                                    page_size=8, max_len=4608,
                                    prefill_chunk=chunk)
        # Warm-up covers every prompt length in the mixture so neither
        # run pays prefill compiles inside its timed TTFT window.
        warm = serving.long_prompt_spec(
            num_requests=6, rate_rps=1000.0,
            prompt_weights=(0.34, 0.33, 0.33),
            vocab_size=cfg.vocab_size, seed=1)
        eng.serve(serving.generate(warm))
        reqs = serving.generate(serving.long_prompt_spec(
            num_requests=SERVING_V2_LONG_REQUESTS,
            vocab_size=cfg.vocab_size, seed=11))
        rep = eng.serve(reqs)
        ttft_4k = sorted(r.ttft_s for r in reqs
                         if r.prompt_len == 4096 and r.ttft_s is not None)
        assert ttft_4k, "mixture produced no 4k-token prompts"
        return rep, ttft_4k

    rep_c, t4k_c = _long_run(SERVING_V2_CHUNK)
    rep_n, t4k_n = _long_run(0)
    p = lambda v, q: round(float(np.percentile(np.asarray(v), q)) * 1e3, 3)
    print(f"# phase B: 4k TTFT p99 chunked {p(t4k_c, 99)} ms vs "
          f"whole-prompt {p(t4k_n, 99)} ms", file=sys.stderr)

    def _long_block(rep, t4k):
        return {"completed": rep.completed,
                "requests": rep.num_requests,
                "tokens_per_s": round(rep.tokens_per_s, 2),
                "ttft_p50_ms": round(rep.ttft_p50_s * 1e3, 3),
                "ttft_p99_ms": round(rep.ttft_p99_s * 1e3, 3),
                "ttft_4k_p50_ms": p(t4k, 50),
                "ttft_4k_p99_ms": p(t4k, 99),
                "prompts_4k": len(t4k)}

    config = f"llama_serve_v2_w8_slots{slots}_spec{SERVING_V2_K}_fp8kv"
    result = {
        "metric": "serving_v2_tokens_per_sec",
        "value": round(rep_a.tokens_per_s, 2),
        "unit": "tokens/s",
        # Same mesh/model/slots as r11; the serving stack is the variable.
        "vs_baseline": round(rep_a.tokens_per_s / SERVING_R11_TOKENS_PER_S,
                             2),
        "config": config,
        "baseline_config": "llama_serve_w8_slots8",
        "serving_v2": {
            "world": 8,
            "slots": slots,
            "spec_k": SERVING_V2_K,
            "drafter": "model_self_draft",
            "kv_compress": True,
            "throughput": {
                "requests": rep_a.num_requests,
                "completed": rep_a.completed,
                "rejected": rep_a.rejected,
                "new_tokens": rep_a.new_tokens,
                "decode_steps": rep_a.decode_steps,
                "spec_rounds": rep_a.spec_rounds,
                "proposed_tokens": rep_a.proposed_tokens,
                "accepted_tokens": rep_a.accepted_tokens,
                "acceptance_rate": round(rep_a.acceptance_rate, 4),
                "tokens_per_s": round(rep_a.tokens_per_s, 2),
                "batch_occupancy": round(rep_a.mean_occupancy, 4),
                "baseline_tokens_per_s": SERVING_R11_TOKENS_PER_S,
                "load": {"rate_rps": SERVING_V2_RATE,
                         "num_requests": SERVING_V2_REQUESTS,
                         "prompt_lens": list(spec_a.prompt_lens),
                         "output_lens": list(spec_a.output_lens),
                         "seed": spec_a.seed}},
            "long_prompt": {
                "prefill_chunk": SERVING_V2_CHUNK,
                "num_requests": SERVING_V2_LONG_REQUESTS,
                "prompt_lens": [512, 2048, 4096],
                "chunked": _long_block(rep_c, t4k_c),
                "nochunk": _long_block(rep_n, t4k_n)},
        },
    }
    print(json.dumps(result), flush=True)
    os._exit(0)


def _main_prefix():
    """BENCH_PREFIX=1: round-17 prefix-shared KV cache drill."""
    import dataclasses
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(8, cpu=True)  # before jax touches the backend
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from horovod_tpu import serving
    from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM

    cfg = LLAMA_SERVE
    model = LlamaLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("tp",))
    slots = SERVING_SLOTS
    # SLO class budgets for the fairness gate: gold gets 4x the stride
    # weight and a tight TTFT budget; bronze is best-effort but capped
    # at 3/4 of the slots so a bronze flood cannot starve gold.
    classes = {
        "gold": serving.TenantClass("gold", weight=4.0, ttft_slo_s=3.0),
        "bronze": serving.TenantClass("bronze", weight=1.0,
                                      ttft_slo_s=10.0, max_share=0.75)}

    def _engine(prefix_on):
        return serving.ServingEngine(
            cfg, params, mesh=mesh, slots=slots, page_size=16,
            max_len=2048, prefix_cache=prefix_on,
            session_ttl_steps=2048, tenants=classes)

    # The measured mixture: 1024-token shared prefixes over 64-token
    # unique tails, so one radix hit skips ~16x the tail's prefill.
    spec = serving.prefix_spec(
        num_requests=PREFIX_REQUESTS, rate_rps=PREFIX_RATE,
        prompt_lens=(64,), output_lens=(16, 24),
        prefix_share=0.75, num_prefixes=2, prefix_lens=(1024,),
        session_share=0.25, session_turns=2,
        tenants=(("gold", 1.0), ("bronze", 1.0)),
        vocab_size=cfg.vocab_size, seed=11)
    # Warm-up mixture: same shape, tiny N, high rate -- covers every
    # prefill length {64, 128, 1088, 1152} and every chunked-tail
    # (tail, past) variant outside the timed runs.
    warm_spec = dataclasses.replace(spec, num_requests=12, rate_rps=1000.0,
                                    session_share=0.5, seed=1)

    def _run(eng, s):
        reqs = serving.generate(s)
        rep = eng.serve(reqs)
        total = (rep.prompt_tokens + rep.new_tokens) / rep.wall_s
        return rep, reqs, total

    # --- phase A: cold-cache baseline at matched load --------------------
    eng_cold = _engine(False)
    eng_cold.serve(serving.generate(warm_spec))
    rep_c, _, total_c = _run(eng_cold, spec)
    print(f"# cold: {total_c:.1f} tokens/s end-to-end, "
          f"TTFT p99 {rep_c.ttft_p99_s * 1e3:.1f} ms", file=sys.stderr)

    # --- phase B: warm radix cache, same stream --------------------------
    eng = _engine(True)
    eng.serve(serving.generate(warm_spec))
    eng._prefix.drop_all()  # hits in the timed run must be earned there
    rep_w, _, total_w = _run(eng, spec)
    print(f"# warm: {total_w:.1f} tokens/s end-to-end, "
          f"TTFT p99 {rep_w.ttft_p99_s * 1e3:.1f} ms, "
          f"hit rate {rep_w.prefix_hit_rate:.3f}, "
          f"flops avoided {rep_w.prefill_flops_avoided:.3f}",
          file=sys.stderr)

    # --- drain: every shared page must come home -------------------------
    eng._prefix.drop_all()
    leaked = int(eng.cache.live_pages)
    balanced = bool(eng.cache.refcounts_balanced())

    # --- phase C: fairness under an adversarial tenant mix ---------------
    # Same seed for both mixes: the tenant label is the only rng draw
    # whose OUTCOME changes with the weights, so prompts and arrival
    # times stay byte-identical -- matched load by construction.
    def _fair(mix, seed):
        eng._prefix.drop_all()
        s = dataclasses.replace(spec, tenants=mix, seed=seed)
        rep, reqs, total = _run(eng, s)
        p99 = {}
        for name in ("gold", "bronze"):
            ts = [r.ttft_s for r in reqs
                  if r.tenant == name and r.ttft_s is not None]
            p99[name] = float(np.percentile(np.asarray(ts), 99)) \
                if ts else 0.0
        return rep, total, p99

    rep_u, total_u, p99_u = _fair((("gold", 1.0), ("bronze", 1.0)), 13)
    rep_a, total_a, p99_a = _fair((("gold", 1.0), ("bronze", 9.0)), 13)
    ratio = total_a / total_u if total_u else 0.0
    print(f"# fairness: uniform {total_u:.1f} vs adversarial "
          f"{total_a:.1f} tokens/s (ratio {ratio:.3f}); adversarial "
          f"TTFT p99 gold {p99_a['gold'] * 1e3:.1f} ms / bronze "
          f"{p99_a['bronze'] * 1e3:.1f} ms", file=sys.stderr)

    slo = {c.name: c.ttft_slo_s for c in classes.values()}
    ok = (rep_w.prefill_flops_avoided >= 0.4
          and rep_w.ttft_p99_s < rep_c.ttft_p99_s
          and total_w >= SERVING_R15_TOKENS_PER_S
          and total_w >= total_c
          and leaked == 0 and balanced
          and all(p99_a[n] <= slo[n] for n in slo)
          and ratio >= 0.9)

    config = f"llama_serve_w8_slots{slots}_prefix"
    result = {
        "metric": "serving_prefix_tokens_per_sec",
        "value": round(total_w, 2),
        "unit": "tokens/s",
        "vs_baseline": None,  # CPU-mesh serving drill: no throughput peer
        "config": config,
        "baseline_config": f"llama_serve_w8_slots{slots}_coldcache",
        "prefix": {
            "world": 8,
            "slots": slots,
            "page_size": 16,
            "hit": {"queries": rep_w.prefix_queries,
                    "hits": rep_w.prefix_hits,
                    "hit_rate": round(rep_w.prefix_hit_rate, 4)},
            "prefill": {
                "tokens_cached": rep_w.prefill_tokens_cached,
                "tokens_computed": (rep_w.prompt_tokens
                                    - rep_w.prefill_tokens_cached),
                "flops_avoided": round(rep_w.prefill_flops_avoided, 4)},
            "ttft": {"cold_p50_ms": round(rep_c.ttft_p50_s * 1e3, 3),
                     "cold_p99_ms": round(rep_c.ttft_p99_s * 1e3, 3),
                     "warm_p50_ms": round(rep_w.ttft_p50_s * 1e3, 3),
                     "warm_p99_ms": round(rep_w.ttft_p99_s * 1e3, 3)},
            # End-to-end token throughput (prompt + generated per wall
            # second): the number the avoided prefill moves at
            # kilotoken context, and the one compared against the
            # BENCH_r15 headline.
            "throughput": {
                "cold_tokens_per_s": round(total_c, 2),
                "warm_tokens_per_s": round(total_w, 2),
                "warm_decode_tokens_per_s": round(rep_w.tokens_per_s, 2),
                "baseline_r15_tokens_per_s": SERVING_R15_TOKENS_PER_S,
                "vs_r15": round(total_w / SERVING_R15_TOKENS_PER_S, 2)},
            "sessions": {"resumes": rep_w.session_resumes},
            "drain": {"leaked_pages": leaked,
                      "refcounts_balanced": balanced},
            "fairness": {
                "classes": {
                    n: {"ttft_p99_s": round(p99_a[n], 4),
                        "slo_s": slo[n],
                        "met": bool(p99_a[n] <= slo[n])}
                    for n in slo},
                "uniform_tokens_per_s": round(total_u, 2),
                "adversarial_tokens_per_s": round(total_a, 2),
                "throughput_ratio": round(ratio, 4)},
            "load": {"rate_rps": PREFIX_RATE,
                     "num_requests": PREFIX_REQUESTS,
                     "prefix_share": spec.prefix_share,
                     "num_prefixes": spec.num_prefixes,
                     "prefix_lens": list(spec.prefix_lens),
                     "prompt_lens": list(spec.prompt_lens),
                     "output_lens": list(spec.output_lens),
                     "session_share": spec.session_share,
                     "session_turns": spec.session_turns,
                     "seed": spec.seed},
        },
    }
    if not ok:
        result["error"] = "prefix drill failed a gate (see prefix block)"
    print(json.dumps(result), flush=True)
    os._exit(0 if ok else 2)


def _main_autoscale():
    """BENCH_AUTOSCALE=1: closed-loop elastic serving chaos drill."""
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(8, cpu=True)  # before jax touches the backend
    import jax
    import jax.numpy as jnp
    from horovod_tpu import serving
    from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM

    cfg = LLAMA_SERVE
    model = LlamaLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    policy_cfg = serving.PolicyConfig(
        interval_s=0.05, ttft_slo_s=2.0, queue_high=20,
        occupancy_low=0.15, hysteresis=2, cooldown_s=0.3,
        evict_lateness_s=0.05, drain_steps=8)
    plane = serving.ServingControlPlane(
        cfg, params, devices=jax.devices()[:8], initial_tp=8,
        policy_config=policy_cfg, chaos_spec=AUTOSCALE_SPEC,
        slots=SERVING_SLOTS, page_size=8, max_len=64)
    spec = serving.LoadSpec(num_requests=AUTOSCALE_REQUESTS,
                            rate_rps=AUTOSCALE_RATE,
                            prompt_lens=(4, 8, 16), output_lens=(8, 16, 24),
                            vocab_size=cfg.vocab_size, seed=11)
    rep = plane.serve(serving.generate(spec))

    config = (f"llama_serve_ctl_w8_slots{SERVING_SLOTS}_"
              + AUTOSCALE_SPEC.replace("@", "").replace("=", "")
                .replace(",", "_").replace(";", "_").replace(".", "p"))
    result = {
        "metric": "autoscale_slo_violation_seconds",
        "value": round(rep.slo_violation_s, 3),
        "unit": "s",
        "vs_baseline": None,  # closed-loop drill: no throughput peer
        "config": config,
        "baseline_config": f"llama_serve_w8_slots{SERVING_SLOTS}",
        "autoscale": {
            "world": 8,
            "initial_tp": rep.mesh_size_initial,
            "final_tp": rep.mesh_size_final,
            "chaos_spec": AUTOSCALE_SPEC,
            "decisions": rep.decision_counts,
            "resizes": rep.resizes,
            "evicted_ranks": rep.evicted_ranks,
            "dead_ranks": rep.dead_ranks,
            "drained_completed": rep.drained_completed,
            "drained_reprefilled": rep.drained_reprefilled,
            "drain_leaked_pages": rep.drain_leaked_pages,
            "lost_requests": rep.lost_requests,
            "slo_violation_s": round(rep.slo_violation_s, 3),
            "slo_budget_s": AUTOSCALE_BUDGET_S,
            "requests": rep.serving.num_requests,
            "completed": rep.serving.completed,
            "rejected": rep.serving.rejected,
            "new_tokens": rep.serving.new_tokens,
            "decode_steps": rep.serving.decode_steps,
            "tokens_per_s": round(rep.serving.tokens_per_s, 2),
            "policy": {
                "interval_s": policy_cfg.interval_s,
                "ttft_slo_s": policy_cfg.ttft_slo_s,
                "queue_high": policy_cfg.queue_high,
                "occupancy_low": policy_cfg.occupancy_low,
                "hysteresis": policy_cfg.hysteresis,
                "cooldown_s": policy_cfg.cooldown_s,
                "evict_lateness_s": policy_cfg.evict_lateness_s,
                "drain_steps": policy_cfg.drain_steps,
            },
            "load": {"rate_rps": AUTOSCALE_RATE,
                     "num_requests": AUTOSCALE_REQUESTS,
                     "prompt_lens": list(spec.prompt_lens),
                     "output_lens": list(spec.output_lens),
                     "seed": spec.seed},
        },
    }
    print(json.dumps(result), flush=True)
    os._exit(0)


def _main_planir():
    """BENCH_PLANIR=1: exchange-plan IR + overlap-aware scheduler A/B."""
    import dataclasses

    from horovod_tpu.controller import fusion as _fusion
    from horovod_tpu.utils.scaling import V5E

    n_dcn, n_ici = 2, 32
    world = n_dcn * n_ici
    # Reverse-planned DP buckets (backward readies the LAST layer's
    # bucket first): f32 element counts of a transformer-ish tail.
    bucket_elems = [25_000_000, 8_000_000, 2_000_000, 512_000]
    zero_elems = [4_000_000, 1_000_000]

    def step_legs():
        """Plan every consumer's legs for one step; returns the program-
        order leg list with process-wide bucket ids (chains)."""
        legs, bucket = [], 0
        for size in reversed(bucket_elems):
            plan = _fusion.plan_exchange(
                "hier", size=size, dtype="float32", n_dcn=n_dcn,
                n_ici=n_ici, compression="ici:none,dcn:fp16")
            legs += [dataclasses.replace(l, bucket=bucket)
                     for l in plan.legs]
            bucket += 1
        zbufs = []
        for size in zero_elems:
            padded = size + (-size) % world
            zbufs.append(("float32", size, padded, padded // world))
        zplan = _fusion.plan_exchange(
            "zero", buffers=tuple(zbufs), world=world, compression=None,
            axes_shape=None, axes=(), use_rs=True)
        legs += [dataclasses.replace(l, bucket=bucket + l.bucket)
                 for l in zplan.legs]
        bucket += len(zero_elems)
        splan = _fusion.plan_exchange(
            "serving", kind="serving_decode", layers=4, slots=8, width=1,
            d_model=1024, dtype="bfloat16", axis="tp")
        legs += [dataclasses.replace(l, bucket=bucket + l.bucket)
                 for l in splan.legs]
        bucket += 4
        mplan = _fusion.plan_exchange(
            "moe", n_experts=16, capacity=128, d_model=1024,
            compression="bf16", axis="ep")
        legs += [dataclasses.replace(l, bucket=bucket)
                 for l in mplan.legs]
        bucket += 1
        legs += [dataclasses.replace(
            _fusion.plan_exchange("guard").legs[0], bucket=bucket)]
        return legs

    # Replan accounting: a cold step plans every exchange once; a warm
    # (repeat) step must resolve ALL of them from the shared cache.
    _fusion.clear_plan_cache()
    program = step_legs()
    cold = _fusion.plan_cache_stats()
    warm_legs = step_legs()
    warm = _fusion.plan_cache_stats()
    warm_replans = warm["misses"] - cold["misses"]
    warm_hits = warm["hits"] - cold["hits"]
    assert warm_legs == program

    scheduled = _fusion.schedule_legs(program, mode="bandwidth",
                                      chip=V5E)

    def payload(legs):
        return sorted((l.tag, int(l.bucket), l.collective, l.wire_dtype,
                       int(l.nbytes)) for l in legs)

    byte_identical = (payload(scheduled) == payload(program)
                      and sum(l.nbytes for l in scheduled)
                      == sum(l.nbytes for l in program))
    sim_prog = _fusion.simulate_issue(program, chip=V5E)
    sim_sched = _fusion.simulate_issue(scheduled, chip=V5E)
    speedup = sim_prog["makespan_s"] / max(sim_sched["makespan_s"],
                                           1e-12)
    gap_drop = (sim_prog["dispatch_gap_fraction"]
                - sim_sched["dispatch_gap_fraction"])
    phases = _fusion.overlap_phases(program, 4, mode="bandwidth",
                                    chip=V5E)

    ok = (byte_identical and warm_replans == 0 and warm_hits > 0
          and gap_drop > 0.0 and speedup >= 1.0
          and _fusion.schedule_legs(program, mode="program") == program)
    result = {
        "metric": "planir_scheduled_speedup",
        "value": round(speedup, 4),
        "unit": "x",
        "vs_baseline": None,  # host-side contention model, no wire peer
        "config": f"virtual_{n_dcn}x{n_ici}_sched_bandwidth",
        "baseline_config": f"virtual_{n_dcn}x{n_ici}_sched_program",
        "planir": {
            "world": world,
            "mesh": [n_dcn, n_ici],
            "chip": V5E.name,
            "legs": len(program),
            "bucket_elems": bucket_elems,
            "zero_elems": zero_elems,
            "consumers": ["hier-dp", "zero1", "serving-decode", "moe",
                          "guard"],
            "wire_bytes": int(sum(l.nbytes for l in program)),
            "byte_identical": bool(byte_identical),
            "plans_cold": int(cold["misses"]),
            "replans_warm": int(warm_replans),
            "hits_warm": int(warm_hits),
            "program": {
                "makespan_s": round(sim_prog["makespan_s"], 6),
                "dispatch_gap_fraction": round(
                    sim_prog["dispatch_gap_fraction"], 4),
                "busy_s": {k: round(v, 6)
                           for k, v in sim_prog["busy_s"].items()},
            },
            "scheduled": {
                "makespan_s": round(sim_sched["makespan_s"], 6),
                "dispatch_gap_fraction": round(
                    sim_sched["dispatch_gap_fraction"], 4),
                "busy_s": {k: round(v, 6)
                           for k, v in sim_sched["busy_s"].items()},
            },
            "speedup": round(speedup, 4),
            "gap_drop": round(gap_drop, 4),
            "overlap_phase_sizes": [len(p) for p in phases],
        },
    }
    if not ok:
        result["error"] = "planir drill failed a gate (see planir block)"
    print(json.dumps(result), flush=True)
    os._exit(0 if ok else 2)


def _main_fleet():
    """BENCH_FLEET=1: round-20 disaggregated serving fleet drill."""
    import dataclasses
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(8, cpu=True)  # before jax touches the backend
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from horovod_tpu import serving
    from horovod_tpu.models.transformer import LLAMA_SERVE, LlamaLM
    from horovod_tpu.run.http_kv import KVClient, RendezvousServer
    from horovod_tpu.run.secret import make_secret_key
    from horovod_tpu.serving.fleet import _SCOPE as _fleet_scope

    cfg = LLAMA_SERVE
    model = LlamaLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    devs = jax.devices()
    slots = SERVING_SLOTS

    def _engine(lo, hi, page_size=8, max_len=256):
        mesh = Mesh(np.asarray(devs[lo:hi]), ("tp",))
        return serving.ServingEngine(
            cfg, params, mesh=mesh, slots=slots, page_size=page_size,
            max_len=max_len, prefetch_depth=1, prefill_chunk=0)

    secret = make_secret_key()
    srv = RendezvousServer(secret, host="127.0.0.1")
    kv = KVClient("127.0.0.1", srv.port, secret)

    def _fleet(n_prefill, lo, hi, page_size=8, max_len=256,
               scaler_policy=None):
        return serving.ServingFleet(
            [serving.PrefillWorker(f"p{i}", cfg, params, kv,
                                   page_size=page_size, tier="f32")
             for i in range(n_prefill)],
            [serving.DecodeWorker(
                "decode0", _engine(lo, hi, page_size, max_len), kv)],
            kv, scaler_policy=scaler_policy,
            engine_factory=lambda: _engine(lo, hi, page_size, max_len))

    # --- phase P: bitwise parity, disaggregated vs colocated -------------
    # Same mesh spec both sides (tp=1): the f32 wire tier is bitwise
    # and per-slot decode logits are batch-independent, so the streams
    # must be bit-for-bit equal -- with every handoff on the wire.
    par_spec = serving.fleet_spec(
        num_requests=12, rate_rps=50.0, rate_double_at_s=0.0,
        engine_skew=(), vocab_size=cfg.vocab_size, seed=3)
    reqs_colo = serving.generate(par_spec)
    _engine(0, 1).serve(reqs_colo)
    reqs_par = serving.generate(par_spec)
    frep_par = _fleet(1, 0, 1).serve(reqs_par)
    bitwise = ({r.rid: list(r.tokens) for r in reqs_par}
               == {r.rid: list(r.tokens) for r in reqs_colo})
    parity_ok = (bitwise
                 and frep_par.completed == par_spec.num_requests
                 and frep_par.handoffs_streamed == frep_par.completed
                 and frep_par.handoffs_local == 0
                 and frep_par.kv_bytes_in == frep_par.kv_bytes_out
                 and all(v == 0 for v in frep_par.leaked_pages.values())
                 and frep_par.refcounts_balanced)
    print(f"# parity: bitwise={bitwise}, "
          f"{frep_par.handoffs_streamed} handoffs streamed, "
          f"{frep_par.kv_bytes_in} KV bytes", file=sys.stderr)

    # --- phase A: throughput at matched hardware (8 devices) -------------
    # Kilotoken prefix-shared prompts (the round-17 mixture): prefill
    # is the expensive regime, so colocated spends the decode host's
    # clock on every 1056-token prompt while the fleet moves that math
    # to the prefill half and only pays the (much cheaper) page import
    # on the decode host.  Both single-engine shapes are measured and
    # the fleet must beat the BEST of them.
    tp_spec = serving.fleet_spec(
        num_requests=FLEET_REQUESTS, rate_rps=FLEET_RATE,
        prompt_lens=(32,), output_lens=(12, 16),
        prefix_share=0.75, num_prefixes=2, prefix_lens=(1024,),
        rate_double_at_s=0.0, engine_skew=(),
        vocab_size=cfg.vocab_size, seed=7)
    # Warm-up covers both prefill shapes {32, 1056} on every engine
    # outside the timed runs.
    warm = dataclasses.replace(tp_spec, num_requests=10,
                               rate_rps=1000.0, prefix_share=0.5,
                               seed=1)

    colo = {}
    for name, lo, hi in (("tp8", 0, 8), ("tp4", 0, 4)):
        eng = _engine(lo, hi, page_size=16, max_len=2048)
        eng.serve(serving.generate(warm))
        colo[name] = eng.serve(serving.generate(tp_spec))
        print(f"# colocated {name}: {colo[name].tokens_per_s:.1f} "
              f"tokens/s, TTFT p99 {colo[name].ttft_p99_s * 1e3:.1f} ms",
              file=sys.stderr)
    best_name, best = max(colo.items(),
                          key=lambda kv_: kv_[1].tokens_per_s)

    fleet = _fleet(2, 4, 8, page_size=16, max_len=2048)
    # Deterministic compile warm-up for both prefill shapes on BOTH
    # workers (round-robin dispatch would otherwise leave a jit
    # compile inside the timed run's busy clock).
    for w in fleet.prefill_workers:
        for tlen in (32, 1056):
            rq = serving.Request(
                rid=900_000 + tlen,
                prompt=np.arange(tlen, dtype=np.int32) % cfg.vocab_size,
                max_new_tokens=1)
            tk = w.run(rq, jax.device_put(
                jnp.asarray(rq.prompt, jnp.int32)), 0.0)
            kv.delete_large(_fleet_scope, tk.key)
    fleet.serve(serving.generate(warm))
    frep = fleet.serve(serving.generate(tp_spec))
    print(f"# fleet (2 prefill + decode tp4): "
          f"{frep.tokens_per_s:.1f} tokens/s, TTFT p99 "
          f"{frep.ttft_p99_s * 1e3:.1f} ms, "
          f"{frep.kv_bytes_in} KV bytes streamed", file=sys.stderr)
    thr_ok = (frep.tokens_per_s > best.tokens_per_s
              and frep.completed == tp_spec.num_requests
              and frep.handoffs_local == 0
              and all(v == 0 for v in frep.leaked_pages.values())
              and frep.refcounts_balanced)

    # --- phase B: chaos -- surge + skew + prefill-host kill --------------
    # fleet_spec doubles the arrival rate mid-run and skews arrivals
    # 3:1; a prefill host dies at step 3 with handoffs in flight.  The
    # scaler must commission a second decode engine under live traffic
    # and the reaped KV objects must degrade to local prefills.
    chaos_spec = serving.fleet_spec(num_requests=48, rate_rps=80.0,
                                    vocab_size=cfg.vocab_size)
    fpol = serving.FleetPolicyConfig(
        interval_s=0.01, queue_high=4, ttft_slo_s=0.5,
        hysteresis=2, cooldown_s=0.5, max_engines=2)
    cfleet = _fleet(2, 4, 8,
                    scaler_policy=serving.FleetPolicy(fpol))
    crep = cfleet.serve(serving.generate(chaos_spec),
                        kill_prefill_at_step=3)
    print(f"# chaos: {crep.completed}/48 completed, engines "
          f"{crep.engines}, migrated {crep.migrated}, handoffs "
          f"streamed/local {crep.handoffs_streamed}/"
          f"{crep.handoffs_local}, SLO violation "
          f"{crep.slo_violation_s:.2f}s, leaked {crep.leaked_pages}",
          file=sys.stderr)
    chaos_ok = (crep.completed == chaos_spec.num_requests
                and crep.engines == 2
                and crep.migrated > 0
                and crep.handoffs_local >= 1
                and crep.handoffs_streamed >= 1
                and crep.slo_violation_s <= FLEET_BUDGET_S
                and all(v == 0 for v in crep.leaked_pages.values())
                and crep.refcounts_balanced)

    srv.stop()
    ok = parity_ok and thr_ok and chaos_ok
    print(f"# gates: parity={parity_ok} (completed "
          f"{frep_par.completed}, balanced "
          f"{frep_par.refcounts_balanced}), throughput={thr_ok} "
          f"(completed {frep.completed}, local {frep.handoffs_local}, "
          f"balanced {frep.refcounts_balanced}), chaos={chaos_ok}",
          file=sys.stderr)

    config = f"llama_serve_fleet_w8_2p_tp4decode_slots{slots}"
    result = {
        "metric": "fleet_tokens_per_s",
        "value": round(frep.tokens_per_s, 2),
        "unit": "tokens/s",
        "vs_baseline": round(frep.tokens_per_s / best.tokens_per_s, 2)
        if best.tokens_per_s else None,
        "config": config,
        "baseline_config": f"llama_serve_w8_slots{slots}_colocated_best",
        "fleet": {
            "world": 8,
            "slots": slots,
            "page_size": 16,
            "wire_tier": "f32",
            "parity": {
                "requests": par_spec.num_requests,
                "page_size": 8,
                "bitwise_equal": bool(bitwise),
                "handoffs_streamed": frep_par.handoffs_streamed,
                "handoffs_local": frep_par.handoffs_local,
                "kv_bytes": frep_par.kv_bytes_in,
                "leaked_pages": frep_par.leaked_pages,
            },
            "throughput": {
                "fleet_tokens_per_s": round(frep.tokens_per_s, 2),
                "colocated": {n: round(r.tokens_per_s, 2)
                              for n, r in colo.items()},
                "best_colocated": best_name,
                "best_colocated_tokens_per_s":
                    round(best.tokens_per_s, 2),
                "vs_best_colocated":
                    round(frep.tokens_per_s / best.tokens_per_s, 4),
                "fleet_ttft_p99_ms": round(frep.ttft_p99_s * 1e3, 3),
                "best_colocated_ttft_p99_ms":
                    round(best.ttft_p99_s * 1e3, 3),
                "handoffs_streamed": frep.handoffs_streamed,
                "kv_bytes_out": frep.kv_bytes_out,
                "kv_bytes_in": frep.kv_bytes_in,
                "leaked_pages": frep.leaked_pages,
            },
            "chaos": {
                "requests": chaos_spec.num_requests,
                "completed": crep.completed,
                "engines_start": 1,
                "engines_end": crep.engines,
                "migrated": crep.migrated,
                "handoffs_streamed": crep.handoffs_streamed,
                "handoffs_local": crep.handoffs_local,
                "slo_violation_s": round(crep.slo_violation_s, 3),
                "slo_budget_s": FLEET_BUDGET_S,
                "leaked_pages": crep.leaked_pages,
                "refcounts_balanced": crep.refcounts_balanced,
                "decisions": (cfleet.scaler.decisions
                              if cfleet.scaler else []),
                "policy": {
                    "interval_s": fpol.interval_s,
                    "queue_high": fpol.queue_high,
                    "ttft_slo_s": fpol.ttft_slo_s,
                    "hysteresis": fpol.hysteresis,
                    "cooldown_s": fpol.cooldown_s,
                    "max_engines": fpol.max_engines,
                },
            },
            "load": {"rate_rps": FLEET_RATE,
                     "num_requests": FLEET_REQUESTS,
                     "prompt_lens": list(tp_spec.prompt_lens),
                     "output_lens": list(tp_spec.output_lens),
                     "prefix_share": tp_spec.prefix_share,
                     "prefix_lens": list(tp_spec.prefix_lens),
                     "chaos_rate_rps": chaos_spec.rate_rps,
                     "chaos_rate_double_at_s":
                         chaos_spec.rate_double_at_s,
                     "chaos_engine_skew": list(chaos_spec.engine_skew),
                     "seed": tp_spec.seed},
        },
    }
    if not ok:
        result["error"] = "fleet drill failed a gate (see fleet block)"
    print(json.dumps(result), flush=True)
    os._exit(0 if ok else 2)


def _main_roofline():
    """BENCH_ROOFLINE=1: single-chip Pallas kernel roofline drill.

    Times each HOROVOD_PALLAS family against the XLA reference on its
    hot shape and accounts flops/bytes against the v5e single-chip peaks
    (197 bf16 TFLOP/s, 819 GB/s HBM).  Off-TPU the kernel leg runs the
    Pallas interpreter, so ``speedup`` is parity plumbing, not perf; the
    ``backend`` field keys which reading applies.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    os.environ.pop("HOROVOD_PALLAS", None)

    def timed(fn, *args):
        out = jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(ROOFLINE_ITERS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best, out

    def leg(family, shape_tag, flops, nbytes, on_fn, off_fn, args,
            atol):
        env = ("HOROVOD_PALLAS_DECODE" if family == "flash_decode"
               else "HOROVOD_PALLAS_FUSED_UPDATE"
               if family == "fused_update" else "HOROVOD_PALLAS_BN")
        os.environ[env] = "1"
        on_s, on_out = timed(jax.jit(on_fn), *args)
        os.environ[env] = "0"
        off_s, off_out = timed(jax.jit(off_fn), *args)
        del os.environ[env]
        ref = jnp.asarray(off_out, jnp.float32)
        err = float(jnp.max(jnp.abs(jnp.asarray(on_out, jnp.float32)
                                    - ref))
                    / jnp.maximum(1.0, jnp.max(jnp.abs(ref))))
        if not err <= atol:
            print(json.dumps({"metric": "pallas_roofline_speedup_geomean",
                              "value": 0.0, "unit": "x",
                              "vs_baseline": None,
                              "error": f"{family} parity {err} > {atol}"}),
                  flush=True)
            os._exit(2)
        return {
            "family": family, "shape": shape_tag,
            "on_ms": round(on_s * 1e3, 3),
            "off_ms": round(off_s * 1e3, 3),
            "speedup": round(off_s / on_s, 4),
            "flops": int(flops), "bytes": int(nbytes),
            "achieved_tflops": round(flops / on_s / 1e12, 4),
            "achieved_gbps": round(nbytes / on_s / 1e9, 3),
            "pct_peak_flops": round(flops / on_s / V5E_BF16_PEAK * 100,
                                    4),
            "pct_peak_hbm": round(nbytes / on_s / V5E_HBM * 100, 4),
            "max_rel_err": err,
        }

    kernels = []
    key = jax.random.PRNGKey(0)

    # -- flash-decoding: split-KV cache read, GQA 8q/2kv ------------------
    from horovod_tpu.ops.attention import decode_attention
    b, h, h_kv, s, d = 8, 8, 2, 1024, 64
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, h, 1, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, h_kv, s, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, h_kv, s, d), jnp.float32)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    kernels.append(leg(
        "flash_decode", f"b{b}_h{h}kv{h_kv}_s{s}_d{d}",
        flops=4 * b * h * s * d,
        nbytes=2 * b * h_kv * s * d * 4,
        on_fn=lambda q, k, v, l: decode_attention(q, k, v, lengths=l),
        off_fn=lambda q, k, v, l: decode_attention(q, k, v, lengths=l,
                                                   force_reference=True),
        args=(q, kc, vc, lengths), atol=1e-4))

    # -- fused optimizer+codec update: the three stages around the psums --
    from horovod_tpu.collectives.ops import (_orthonormalize_columns,
                                             _powersgd_seed_matrix)
    from horovod_tpu.ops import fused_update as _fused
    m = c = 512
    r = 4
    xk = jax.random.split(key, 2)
    x_mat = jax.random.normal(xk[0], (m, c), jnp.float32)
    res_mat = jax.random.normal(xk[1], (m, c), jnp.float32)
    q0 = _powersgd_seed_matrix(c, r)

    def fused_chain(x_mat, res_mat):
        acc, p = _fused.matricize_p(x_mat, res_mat, q0)
        po, ql = _fused.orthonormalize_q(acc, p)
        out, res2 = _fused.reconstruct_residual(acc, po, ql, ql)
        return out + res2

    def unfused_chain(x_mat, res_mat):
        acc = x_mat.astype(jnp.float32) + res_mat
        p = acc @ q0
        po = _orthonormalize_columns(p)
        ql = acc.T @ po
        out = po @ ql.T
        res2 = acc - po @ ql.T
        return out + res2

    kernels.append(leg(
        "fused_update", f"m{m}_c{c}_r{r}",
        flops=8 * m * c * r,
        nbytes=5 * m * c * 4,
        on_fn=fused_chain, off_fn=unfused_chain,
        args=(x_mat, res_mat), atol=1e-4))

    # -- fused BN backward: two-pass 7N floor -----------------------------
    from horovod_tpu.ops import bn as _bn
    n_, side, feat = 32, 16, 256
    bk = jax.random.split(key, 3)
    xb = jax.random.normal(bk[0], (n_, side, side, feat), jnp.float32)
    dyb = jax.random.normal(bk[1], (n_, side, side, feat), jnp.float32)
    scale = jax.random.normal(bk[2], (feat,), jnp.float32) + 1.0

    def bn_bwd(x, dy, scale):
        mean, var = _bn.batch_stats(x)
        dx, dg, db = _bn.fused_bn_backward(x, scale, mean, var, dy,
                                           eps=1e-5)
        return dx + dg + db

    # Distinct wrappers per leg: jax caches traces by function identity,
    # and the env flag is read at trace time.
    kernels.append(leg(
        "bn_bwd", f"n{n_}_hw{side}_c{feat}",
        flops=10 * xb.size,
        nbytes=7 * xb.size * 4,
        on_fn=lambda x, dy, s: bn_bwd(x, dy, s),
        off_fn=lambda x, dy, s: bn_bwd(x, dy, s),
        args=(xb, dyb, scale), atol=1e-4))

    speedups = [k["speedup"] for k in kernels]
    geomean = float(np.exp(np.mean(np.log(speedups))))
    config = f"pallas_roofline_{backend}_" + "_".join(
        k["family"] for k in kernels)
    result = {
        "metric": "pallas_roofline_speedup_geomean",
        "value": round(geomean, 4),
        "unit": "x",
        "vs_baseline": None,  # CPU interpreter drill: no perf peer
        "config": config,
        "baseline_config": config,
        "roofline": {
            "backend": backend,
            "interpreted": backend != "tpu",
            "peak_tflops": V5E_BF16_PEAK / 1e12,
            "peak_hbm_gbps": V5E_HBM / 1e9,
            "iters": ROOFLINE_ITERS,
            "kernels": kernels,
        },
    }
    print(json.dumps(result), flush=True)
    os._exit(0)


def state_batch_after_restore(batch_at_fault: int, commit_every: int) -> int:
    """The batch counter the restore rolled back to (last commit)."""
    return (batch_at_fault // commit_every) * commit_every


def _main_eager():
    """BENCH_EAGER=1: eager control-plane latency via the probe script."""
    import subprocess
    repo = os.path.dirname(os.path.abspath(__file__))
    probe = os.path.join(repo, "examples", "eager_latency_probe.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    n_procs = EAGER_NP
    cmd = [sys.executable, "-m", "horovod_tpu.run", "-np", str(n_procs),
           "--cpu", sys.executable, probe]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=max(WATCHDOG_S - 30, 60))
    # The launcher prefixes worker output ("[0]<stdout> {...}"); take the
    # last line containing the probe's JSON object.
    parsed = None
    for line in out.stdout.splitlines():
        brace = line.find("{")
        if brace < 0:
            continue
        try:
            cand = json.loads(line[brace:])
        except ValueError:
            continue
        if isinstance(cand, dict) and cand.get("metric") == \
                "eager_latency_probe":
            parsed = cand
    if out.returncode != 0 or parsed is None:
        print(out.stdout[-2000:] + out.stderr[-2000:], file=sys.stderr)
        print(json.dumps({"metric": "eager_latency_probe", "value": 0.0,
                          "unit": "ms/batch", "vs_baseline": None,
                          "error": f"probe failed (rc={out.returncode})"}),
              flush=True)
        os._exit(2)
    print(json.dumps(parsed), flush=True)
    os._exit(0)


TRAJECTORY_COLUMNS = ("round", "metric", "value", "unit", "vs_baseline",
                      "config")
_TRAJ_BEGIN = "<!-- BENCH_TRAJECTORY_BEGIN -->"
_TRAJ_END = "<!-- BENCH_TRAJECTORY_END -->"


def build_trajectory_rows(repo: str):
    """Fold every ``BENCH_r*.json`` into one row list (round-sorted).

    Each row carries exactly :data:`TRAJECTORY_COLUMNS`; files without a
    ``parsed`` result (a crashed round) still get a row, with a null
    value, so the trajectory never silently drops a round.
    """
    import glob
    import re
    rows = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        p = rec.get("parsed") or {}
        m = re.search(r"BENCH_r(\d+)", os.path.basename(path))
        rows.append({
            "round": int(rec.get("n", int(m.group(1)) if m else 0)),
            "metric": p.get("metric", "(no result)"),
            "value": p.get("value"),
            "unit": p.get("unit", ""),
            "vs_baseline": p.get("vs_baseline"),
            "config": p.get("config", "-"),
        })
    rows.sort(key=lambda r: r["round"])
    return rows


def render_trajectory_table(rows) -> str:
    """Markdown table over :data:`TRAJECTORY_COLUMNS`."""
    def cell(v):
        return "null" if v is None else str(v)
    lines = ["| " + " | ".join(TRAJECTORY_COLUMNS) + " |",
             "|" + "---|" * len(TRAJECTORY_COLUMNS)]
    for r in rows:
        lines.append("| " + " | ".join(cell(r[c])
                                       for c in TRAJECTORY_COLUMNS) + " |")
    return "\n".join(lines)


def _main_trajectory():
    """``bench.py --trajectory``: merge the per-round result files into one
    table between the trajectory markers in docs/benchmarks.md (replacing
    the previous merge; appended as a new section on first run)."""
    repo = os.path.dirname(os.path.abspath(__file__))
    rows = build_trajectory_rows(repo)
    if not rows:
        sys.exit("no BENCH_r*.json files found; nothing to merge")
    table = render_trajectory_table(rows)
    block = (f"{_TRAJ_BEGIN}\n{table}\n{_TRAJ_END}")
    doc = os.path.join(repo, "docs", "benchmarks.md")
    with open(doc) as f:
        text = f.read()
    if _TRAJ_BEGIN in text and _TRAJ_END in text:
        head, rest = text.split(_TRAJ_BEGIN, 1)
        _, tail = rest.split(_TRAJ_END, 1)
        text = head + block + tail
    else:
        text = (text.rstrip("\n")
                + "\n\n## Benchmark trajectory (merged per-round results)\n\n"
                + block + "\n")
    with open(doc, "w") as f:
        f.write(text)
    print(f"merged {len(rows)} round(s) into {doc}")


def main():
    threading.Thread(target=_watchdog, daemon=True).start()
    if EAGER:
        _main_eager()
    if CHAOS_BENCH:
        _main_chaos()
    if SERVING_BENCH:
        _main_serving()
    if SERVING_V2_BENCH:
        _main_serving_v2()
    if PREFIX_BENCH:
        _main_prefix()
    if AUTOSCALE_BENCH:
        _main_autoscale()
    if PLANIR_BENCH:
        _main_planir()
    if FLEET_BENCH:
        _main_fleet()
    if ROOFLINE_BENCH:
        _main_roofline()
    if SDC_BENCH:
        _main_sdc()
    if OVERLAP and ZERO:
        sys.exit("BENCH_OVERLAP / HOROVOD_MICROBATCHES>1 is incompatible "
                 "with HOROVOD_ZERO=1 (the ZeRO arena exchange is already "
                 "shard-based)")
    if OVERLAP and SCANLOOP:
        sys.exit("BENCH_OVERLAP and BENCH_SCANLOOP are separate configs; "
                 "set exactly one")

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50
    from horovod_tpu.training import make_flax_train_step

    hvd.init()
    n = hvd.size()
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    print(f"# devices: {n} x {dev0.device_kind} ({dev0.platform})",
          file=sys.stderr)
    if dev0.platform != "tpu" and not TINY:
        sys.exit(f"bench.py measures a TPU; found {dev0.platform!r}. "
                 "BENCH_TINY=1 runs the CPU plumbing config instead "
                 "(never a speed).")

    global_batch = BATCH * n
    key = jax.random.PRNGKey(0)
    if TINY:
        from horovod_tpu.models.resnet import BasicBlock, ResNet
        model = ResNet(stage_sizes=[1], block_cls=BasicBlock, num_filters=8,
                       num_classes=100, dtype=jnp.bfloat16)
        x = jax.random.normal(key, (global_batch, 32, 32, 3), jnp.bfloat16)
        y = jax.random.randint(key, (global_batch,), 0, 100, jnp.int32)
    else:
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                         space_to_depth=True)
        x = jax.random.normal(key, (global_batch, 224, 224, 3), jnp.bfloat16)
        y = jax.random.randint(key, (global_batch,), 0, 1000, jnp.int32)
    variables = model.init(key, x[:2].astype(jnp.float32), train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    params = hvd.replicate(params)
    batch_stats = hvd.replicate(batch_stats)
    zero_stats = None
    if ZERO:
        opt = optax.sgd(0.1, momentum=0.9)
        opt_state = hvd.zero_init(opt, params,
                                  compression=COMPRESSION or None)
        step = make_flax_train_step(model.apply, opt, zero_stage=1,
                                    zero_compression=COMPRESSION or None)
        zero_stats = hvd.zero_report(opt, params, n,
                                     compression=COMPRESSION or None)
        print("# zero1: "
              f"RS {zero_stats['reducescatter_bytes_per_chip']/2**20:.1f} + "
              f"AG {zero_stats['allgather_bytes_per_chip']/2**20:.1f} MiB/"
              "step/chip exchanged (replicated allreduce: "
              f"{zero_stats['replicated_allreduce_bytes_per_chip']/2**20:.1f}"
              " MiB); opt-state HBM "
              f"{zero_stats['opt_state_bytes_per_chip_zero1']/2**20:.1f} "
              "MiB/chip vs "
              f"{zero_stats['opt_state_bytes_per_chip_replicated']/2**20:.1f}"
              " MiB replicated", file=sys.stderr)
    else:
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                       compression=COMPRESSION or None)
        opt_state = hvd.replicate(opt.init(params))
        step = make_flax_train_step(model.apply, opt)

    gap_fraction = None
    overlap_fraction = None
    if SCANLOOP:
        # Steps-per-execution runner: SCAN_K steps per dispatch through
        # ONE lax.scan executable (same step body bitwise -- training.py),
        # host-dispatch-gap fraction measured per window.
        from horovod_tpu.training import make_flax_train_loop, shard_steps
        from horovod_tpu.timeline import DispatchGapMonitor
        loop = make_flax_train_loop(model.apply, opt,
                                    steps_per_execution=SCAN_K,
                                    zero_stage=1 if ZERO else 0)
        batch = shard_steps(
            jax.tree.map(lambda a: jnp.stack([a] * SCAN_K), (x, y)))
        calls = max(1, STEPS // SCAN_K)
        monitor = DispatchGapMonitor()
        for _ in range(2):  # warmup: compile + one warm window
            params, batch_stats, opt_state, losses = loop(
                params, batch_stats, opt_state, batch)
        float(losses[-1])
        rates = []
        for _ in range(WINDOWS):
            monitor.begin_window()
            t0 = time.perf_counter()
            for _ in range(calls):
                with monitor.dispatch():
                    params, batch_stats, opt_state, losses = loop(
                        params, batch_stats, opt_state, batch)
            with monitor.dispatch():
                float(losses[-1])  # forces the full window's step chain
            dt = time.perf_counter() - t0
            monitor.end_window()
            rates.append(calls * SCAN_K * global_batch / dt / n)
        gap_fraction = monitor.gap_fraction
        print(f"# scanloop k={SCAN_K}: {calls} dispatches/window, "
              f"host dispatch-gap fraction "
              f"{[round(g, 4) for g in monitor.windows]} "
              f"(mean {gap_fraction:.4f})", file=sys.stderr)
    elif OVERLAP:
        # Backward-overlap microbatched exchange.  The overlap fraction is
        # self-calibrating: compute_s comes from a no-exchange (bare
        # optimizer) step, comm_s from the single-shot step where the
        # monolithic post-backward exchange is fully exposed --
        # comm_s = t_singleshot - t_bare.  The monitor then reports how
        # much of that budget the microbatched step hides.
        from horovod_tpu.timeline import OverlapMonitor
        batch = hvd.shard_batch((x, y))
        step = make_flax_train_step(model.apply, opt, microbatches=MICRO_K)

        def _per_step(fn, p, bs, st, reps=max(4, STEPS // 2)):
            for _ in range(3):
                p, bs, st, loss = fn(p, bs, st, batch)
            float(loss)
            t0 = time.perf_counter()
            for _ in range(reps):
                p, bs, st, loss = fn(p, bs, st, batch)
            float(loss)
            return (time.perf_counter() - t0) / reps

        def _clone(t):
            return jax.tree.map(jnp.copy, t)

        bare_opt = optax.sgd(0.1, momentum=0.9)
        bare_step = make_flax_train_step(model.apply, bare_opt)
        compute_s = _per_step(bare_step, _clone(params), _clone(batch_stats),
                              hvd.replicate(bare_opt.init(params)))
        single_step = make_flax_train_step(model.apply, opt)
        single_s = _per_step(single_step, _clone(params),
                             _clone(batch_stats), _clone(opt_state))
        comm_s = max(0.0, single_s - compute_s)

        monitor = OverlapMonitor(compute_s, comm_s)
        for _ in range(2):  # warmup: compile + one warm window
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, batch)
        float(loss)
        rates = []
        for _ in range(WINDOWS):
            monitor.begin_window()
            t0 = time.perf_counter()
            for _ in range(STEPS):
                params, batch_stats, opt_state, loss = step(
                    params, batch_stats, opt_state, batch)
            float(loss)  # forces the full step chain
            dt = time.perf_counter() - t0
            monitor.end_window(STEPS)
            rates.append(STEPS * global_batch / dt / n)
        overlap_fraction = monitor.overlap_fraction
        print(f"# overlap k={MICRO_K}: compute {compute_s*1e3:.1f} ms, "
              f"single-shot {single_s*1e3:.1f} ms (exposed comm "
              f"{comm_s*1e3:.1f} ms); exchange-overlap fraction "
              f"{[round(w, 4) for w in monitor.windows]} "
              f"(mean {overlap_fraction:.4f})", file=sys.stderr)
    else:
        batch = hvd.shard_batch((x, y))

        # Warmup (compile + cache + one warm window).  float() is a
        # device->host fetch -- the only fence that really waits here (see
        # module docstring).
        for _ in range(8):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, batch)
        float(loss)

        rates = []
        for _ in range(WINDOWS):
            t0 = time.perf_counter()
            for _ in range(STEPS):
                params, batch_stats, opt_state, loss = step(
                    params, batch_stats, opt_state, batch)
            float(loss)  # forces the full step chain
            rates.append(
                STEPS * global_batch / (time.perf_counter() - t0) / n)
    rates = np.asarray(rates)
    ips = float(rates.mean())

    grad_bytes = sum(v.size * 4 for v in jax.tree.leaves(params))
    comp_stats = None
    if COMPRESSION:
        from horovod_tpu.collectives.compression import (parse_compression,
                                                         wire_payload_bytes)
        comp = parse_compression(COMPRESSION)
        if ZERO:
            # zero_report already prices the compressed param-delta
            # allgather; the ratio compares against the replicated
            # allreduce equivalent over the same params.
            wire = (zero_stats["reducescatter_bytes_per_chip"]
                    + zero_stats["allgather_bytes_per_chip"])
            raw = zero_stats["replicated_allreduce_bytes_per_chip"]
        else:
            from horovod_tpu.optim.distributed import ef_bucket_plan
            plan = ef_bucket_plan(jax.tree.leaves(params), None, comp)
            wire = sum(wire_payload_bytes(
                comp, sum(s.size for s in lspecs),
                jnp.dtype(dt).itemsize, n) for dt, lspecs in plan.buffers)
            raw = grad_bytes
        comp_stats = {"codec": COMPRESSION,
                      "wire_bytes_per_step": int(wire),
                      "uncompressed_bytes_per_step": int(raw),
                      "ratio": round(raw / max(wire, 1), 2)}
        print(f"# compression {COMPRESSION}: wire "
              f"{wire/2**20:.2f} MiB/step vs {raw/2**20:.1f} MiB "
              f"uncompressed ({comp_stats['ratio']:.1f}x)", file=sys.stderr)
    if n > 1:
        # Honest bus-BW bound (SURVEY.md section 7 hard part 4): each step
        # moves >= 2*(n-1)/n * grad_bytes per chip for a ring allreduce.
        bus = 2 * (n - 1) / n * grad_bytes * ips / global_batch * n
        print(f"# allreduce bus BW >= {bus/2**30:.2f} GiB/s/chip "
              "(lower bound from step time; includes compute overlap)",
              file=sys.stderr)
    print(f"# batch {BATCH}/chip, {WINDOWS}x{STEPS}-step windows: "
          f"{rates.round(1).tolist()} img/s/chip "
          f"(std {rates.std():.1f}); grad payload "
          f"{grad_bytes/2**20:.1f} MiB/step", file=sys.stderr)
    if not TINY:
        mfu = ips * FLOPS_PER_IMAGE / _bf16_peak(dev0.device_kind)
        print(f"# ~{ips*FLOPS_PER_IMAGE/1e12:.1f} TFLOP/s = {mfu:.1%} of "
              f"{dev0.device_kind} bf16 peak", file=sys.stderr)
    # vs_baseline is a same-config regression ratio; an env-overridden
    # config (BENCH_BATCH=...) would make it config drift, so emit null.
    same_config = _config() == BASELINE_CONFIG
    result = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/s/chip",
        "vs_baseline": round(ips / BASELINE, 4) if same_config else None,
        "config": _config(),
        "baseline_config": BASELINE_CONFIG,
        "device": device,
    }
    if zero_stats is not None:
        result["zero"] = zero_stats
    if gap_fraction is not None:
        result["dispatch_gap"] = round(gap_fraction, 4)
    if overlap_fraction is not None:
        result["overlap_fraction"] = round(overlap_fraction, 4)
        result["microbatches"] = MICRO_K
    if comp_stats is not None:
        result["compression"] = comp_stats
    # Static collective-consistency audit of the step ACTUALLY
    # benchmarked: a retrace (never a run), cross-checked against the
    # fusion/arena plan.  bench_guard gates on this block, so a bench
    # number can't ship from a step whose exchange drifted off-plan.  An
    # audit or metrics-snapshot failure is reported in the JSON line and
    # fails the run (exit 2); the measured number is still printed.
    errors = []
    try:
        from horovod_tpu.analysis import audit_step as _audit_step
        target = loop if SCANLOOP else step
        report = _audit_step(target, params, batch_stats, opt_state, batch,
                             batch_stats=batch_stats, name="bench:step")
        result["audit"] = dict(report.summary, ok=report.ok(),
                               findings=[f.render() for f in
                                         report.findings])
        print(f"# {report.render().splitlines()[0]}", file=sys.stderr)
    except Exception as e:
        result["audit"] = {"error": f"{type(e).__name__}: {e}"}
        errors.append("audit")
    try:
        from horovod_tpu.timeline.metrics import bench_block
        result["metrics"] = bench_block()
    except Exception as e:
        result["metrics"] = {"error": f"{type(e).__name__}: {e}"}
        errors.append("metrics")
    if errors:
        result["error"] = f"{' and '.join(errors)} failed (see block)"
    print(json.dumps(result), flush=True)
    # os._exit skips the slow atexit teardown; the result is printed.
    os._exit(2 if errors else 0)


if __name__ == "__main__":
    if "--trajectory" in sys.argv[1:]:
        _main_trajectory()
    else:
        main()
