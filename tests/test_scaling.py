"""Scaling-evidence harness: HLO accounting + analytic model units, plus
the in-process integration at 8 virtual devices (SURVEY.md section 6 /
section 7 hard part 5 -- the north-star 1->256 efficiency claim rests on
these mechanics)."""

import json
import os
import subprocess
import sys
from os.path import abspath, dirname

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hv
from horovod_tpu.utils import scaling

REPO = dirname(dirname(abspath(__file__)))


# ---------------------------------------------------------------------------
# Analytic model units.
# ---------------------------------------------------------------------------

def test_ring_allreduce_formula():
    # 2B(n-1)/n at bandwidth bw.
    assert scaling.ring_allreduce_seconds(100, 1, 10) == 0.0
    assert scaling.ring_allreduce_seconds(100, 2, 10) == pytest.approx(10.0)
    assert scaling.ring_allreduce_seconds(100, 4, 10) == pytest.approx(15.0)


def test_allreduce_switches_to_hierarchical_past_ici_domain():
    chip = scaling.ChipSpec("toy", 1.0, 8.0, ici_domain_chips=4,
                            dcn_gbps_per_chip=0.8)
    b = 1000.0
    within = scaling.allreduce_seconds(b, 4, chip)
    assert within == pytest.approx(
        scaling.ring_allreduce_seconds(b, 4, chip.ici_allreduce_bytes_per_s))
    beyond = scaling.allreduce_seconds(b, 8, chip)
    # Two-level: full ICI reduce-scatter+allgather plus a DCN allreduce of
    # the 1/s shard -- strictly more than the pure-ICI time, and strictly
    # less than pushing all bytes over DCN.
    assert beyond > within
    assert beyond < scaling.ring_allreduce_seconds(
        b, 8, chip.dcn_allreduce_bytes_per_s)


def test_predict_efficiency_bounds_and_monotonicity():
    pts = scaling.predict_efficiency(0.1, 100e6, scaling.V5E)
    assert pts[0].n == 1 and pts[0].eff_no_overlap == pytest.approx(1.0)
    for a, b in zip(pts, pts[1:]):
        assert b.eff_no_overlap <= a.eff_no_overlap + 1e-12
    for p in pts:
        assert p.eff_full_overlap >= p.eff_no_overlap
        assert 0.0 < p.eff_no_overlap <= 1.0


def test_rn50_config_predicts_north_star_efficiency():
    """The model at RN50's shape: a 100.7 ms step at batch 256 (an earlier
    runtime's reading, not reproduced: an input to the model, no speed of
    this repository) against the 97.7 MiB payload predicts >= 90% at 256
    v5e chips even with ZERO overlap -- the worst-case bound, not the
    overlap assumption."""
    pts = scaling.predict_efficiency(256 / 2542.27, 102.4e6, scaling.V5E)
    e256 = [p for p in pts if p.n == 256][0]
    assert e256.eff_no_overlap >= 0.90


# ---------------------------------------------------------------------------
# HLO parsing units.
# ---------------------------------------------------------------------------

_HLO_SAMPLE = """
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={}
  %arv = (f32[16]{0}, bf16[8]{0}) all-reduce(%a, %b), replica_groups={}
  %ags = f32[64,2]{1,0} all-gather-start(%y), dimensions={0}
  %agd = f32[64,2]{1,0} all-gather-done(%ags)
  %cp = bf16[32]{0} collective-permute(%z), source_target_pairs={{0,1}}
"""


_SCHEDULED_MODULE = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[128,256], p1: f32[256,256]) -> f32[128,256] {
  %p0 = f32[128,256]{1,0:T(8,128)} parameter(0)
  %p1 = f32[256,256]{1,0:T(8,128)} parameter(1)
  ROOT %d = f32[128,256]{1,0:T(8,128)} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main_spmd (param.0: f32[128,256], param.1: f32[256,256]) {
  %param.0 = f32[128,256]{1,0:T(8,128)} parameter(0)
  %param.1 = f32[256,256]{1,0:T(8,128)} parameter(1)
  %collective-permute-start.1 = (f16[1024]{0:T(1024)(128)(2,1)}, f16[1024]{0:T(1024)(128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%param.0), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %fusion.1 = f32[128,256]{1,0:T(8,128)} fusion(%param.0, %param.1), kind=kOutput, calls=%fused_computation.1
  %collective-permute-done.1 = f16[1024]{0:T(1024)(128)(2,1)} collective-permute-done(%collective-permute-start.1)
  %all-reduce = (f32[1000]{0:T(1024)}, f32[24]{0:T(128)}) all-reduce(%fusion.1, %param.1), channel_id=2, replica_groups={{0,1}}, to_apply=%add
  ROOT %tuple = (f32[128,256]{1,0:T(8,128)}) tuple(%fusion.1)
}
"""


def test_schedule_overlap_report_parses_scheduled_tpu_module():
    """The round-4 topology-AOT parser: async start/done pairs matched by
    name (TPU tuple shapes with nested tiling parens must not break it),
    sync collectives classified with variadic tuple payloads, fusion
    FLOPs costed through the called computation, and the eq-payload
    conversion (permute result = link bytes)."""
    rep = scaling.schedule_overlap_report(_SCHEDULED_MODULE, n_devices=2)
    assert len(rep.async_collectives) == 1
    op, payload, si, di = rep.async_collectives[0]
    assert op == "collective-permute" and payload == 2048 and di - si == 2
    assert len(rep.sync_collectives) == 1
    sop, sbytes, _ = rep.sync_collectives[0]
    assert sop == "all-reduce" and sbytes == 4096  # 4000 + 96 B variadic
    # The dot (2*128*256*256 flops) lies inside the async window.
    assert rep.async_window_seconds > 0
    assert rep.total_compute_seconds >= rep.async_window_seconds
    # Permute result bytes are LINK bytes: eq payload divides the ring
    # factor 2(n-1)/n = 1 at n=2.
    assert rep.async_eq_payload() == pytest.approx(2048)
    # Scheduled efficiency: sync fully exposed, async hidden up to the
    # window.
    pts = scaling.predict_efficiency_scheduled(0.01, rep, scaling.V5E,
                                               ns=(8,))
    assert pts[0].eff_full_overlap >= pts[0].eff_no_overlap
    # A 4x bandwidth derate can only lower the scheduled number.
    pts4 = scaling.predict_efficiency_scheduled(0.01, rep, scaling.V5E,
                                                ns=(8,),
                                                bandwidth_derate=4.0)
    assert pts4[0].eff_full_overlap <= pts[0].eff_full_overlap + 1e-12


def _topology_worker(*argv):
    """Run tests/_topology_worker.py deviceless (libtpu compiles for a
    topology with no chip attached) and return its JSON line."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_topology_worker.py"),
         *argv],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_topology_aot_schedule_smoke():
    """CI gate for deviceless AOT against the real TPU compiler: a tiny
    shard_map program compiled for v5e:2x4 must come back as a SCHEDULED
    module with the capability matrix ``schedule_overlap_report`` reads
    -- collective-permute async (start/done pair), all-reduce
    synchronous.  Toolchain drift that changes any of this fails here.  Runs in a subprocess (host-wide libtpu lock;
    this process is pinned to CPU)."""
    out = _topology_worker("v5e:2x4")
    assert out["is_scheduled"] is True
    assert out["n"] == 8
    assert out["async_ops"] == ["collective-permute"] and out["n_async"] >= 1
    assert out["sync_ops"] == ["all-reduce"]
    assert out["async_eq_payload"] > 0


def test_topology_aot_mosaic_compiles_auto_kernels():
    """Every Pallas family ``auto`` turns on for TPU must get through
    Mosaic (``interpret=False``) at the shapes ``chip_smoke.py`` runs --
    BERT-Large's flash fwd+bwd and LLAMA_1B's 8-slot split-KV decode.
    The decode kernel only ever ran interpreted before PR 21 and Mosaic
    refused it for b > 1; a family re-enabled under ``auto`` needs a
    case here first."""
    from horovod_tpu.ops import pallas
    assert set(pallas.registered_kernels()) - pallas._AUTO_XLA == {
        "flash", "flash_decode", "mla_decode", "moe_gmm", "ssm_decode"}
    out = _topology_worker("v5e:2x2", "kernels")
    # BERT-Large at T = 128 is one block: the head-group forward and ONE
    # backward (dq, dk, dv together), as is Mistral's 512-token prefill;
    # its 1,024-token prefill and the 8k prefill (keys 192 wide, values
    # 128 since PR 49) keep the blocked forward.  One split-KV call; the
    # latent-attention decode out of a 64-slot page pool; the grouped
    # expert matmul (gate and up
    # fused, then down) at a decode round's and at an 8k prefill's row
    # tiles, and at 16 experts of 2048 x 2048 (gate and up in column
    # slices); the page walk over rows of two key and two value heads (96
    # slots) and that model's 512-token prefill, 8 query heads over 2;
    # the same walk over rows of 16 key and 16 value heads (20 slots)
    # with its plane a traced scalar inside a rolled loop (ONE call in
    # the loop's body), and that model's 128-token prefill; the same walk
    # over TWO pools of eight heads a row, the dense decode step's, at
    # Mistral-7B's 32 slots (bfloat16) and LLAMA_1B's 8 (float32); since
    # PR 39 the walk over a ring of nine window pages a slot out of two
    # pools (``hvd_swa_decode``) and the banded prefill kernel
    # (``hvd_flash_swa_fwd``) over 8,192 and over 512 tokens; since PR 42
    # both walks at 28 query heads over 4 (a ring of 257 pages, a full
    # table of 576), the banded prefill under a window of 4,096 and the
    # grouped matmul with ReLU gates; since PR 48 one step of a
    # state-space recurrence for 80 slots in place over one plane of six
    # planes of 1,063,936-value float32 rows (``hvd_ssm_decode``).
    assert out == {"ssm_decode_b80": 1, "flash_bert_large": 2, "flash_mistral_prefill_512": 1,
                   "swa_decode_b64_h28": 1, "full_decode_b64_h28": 1,
                   "flash_swa_prefill_8k_w4096": 1, "moe_gmm_relu_decode": 2,
                   "swa_decode_b32": 1, "flash_swa_prefill_8k": 1,
                   "flash_swa_prefill_512": 1,
                   "flash_mistral_prefill_1024": 1, "flash_mla_prefill_8k": 1,
                   "head_group": ["flash_cca_prefill_512",
                                  "flash_loop_prefill_128",
                                  "flash_bert_large",
                                  "flash_mistral_prefill_512"],
                   "loop_decode_b20": 1, "flash_loop_prefill_128": 1,
                   "flash_decode_b8": 1, "mla_decode_b64": 1,
                   "moe_gmm_decode": 2, "moe_gmm_prefill_8k": 2,
                   "moe_gmm_wide_decode": 2, "moe_gmm_wide_prefill_512": 2,
                   "cca_decode_b96": 1, "flash_cca_prefill_512": 1,
                   "dense_decode_b32": 1, "dense_decode_f32_b8": 1}


def test_topology_aot_dense_decode_step_reads_the_pools_in_place():
    """Mistral-7B's decode step (four of its layers), compiled for one
    v5e chip: attention is ONE Mosaic function (the page walk, shared by
    every layer), both pools are aliased to their successors, the row
    writes are the only instructions as large as a pool, and nothing cut
    out of a pool is as large as one of its planes: no ``pool[layer]``,
    no gathered view of every slot, no relayout (the parent's step held
    a 100 MB slice and a 100 MB fusion a layer and 306 MB of temporaries
    where this holds 37)."""
    out = _topology_worker("v5e:2x2", "dense_step")
    assert out["attention"] == "walk" and out["mosaic_calls"] == 1
    assert out["aliased_params"] == out["pool_params"]
    assert out["pool_writes"] == 2 * 4       # K and V, a layer
    assert out["plane_sized"] == []
    assert out["temp_bytes"] < 3073 * 16 * 1024 * 2     # one plane
    # Over four chips each shard walks its own two heads' columns: the
    # Mosaic call compiles inside the ``shard_map``, in place as well.
    out = _topology_worker("v5e:2x2", "dense_step", "4", "4")
    assert out["attention"] == "walk" and out["mosaic_calls"] == 1
    assert out["aliased_params"] == out["pool_params"]
    assert out["pool_writes"] == 2 * 4 and out["plane_sized"] == []


def test_topology_aot_swa_step_fits_beside_its_cache_at_a_ragged_prompt():
    """K-EXAONE's cut compiled for one v5e chip: the decode step aliases
    all four pools to their successors, and a prompt of 6,000 tokens --
    no multiple of the 2,048 a layer's per-token work runs at a time --
    is chunked all the same (two chunks and a rest of 1,904: two
    instances of a routed layer's two expert matmuls, in the ONE lowered
    function each of the two kinds of routed layer has since PR 45,
    window and full: the seven routed layers call them) and fits beside
    the weights and both groups of pools.  Whole, the float32
    intermediates of an 8,192-token prompt were 4.6 GB of temporaries and
    did not."""
    out = _topology_worker("v5e:2x2", "swa_step", "32", "6000")
    step, pre = out["decode"], out["prefill_6000"]
    assert set(step["pool_params"]) <= set(step["aliased_params"])
    assert step["mosaic_calls"]["hvd_swa_decode"] == 1
    assert step["mosaic_calls"]["hvd_cca_decode"] == 1
    assert pre["mosaic_calls"]["hvd_moe_gmm"] == 2 * 2 * 2
    assert pre["mosaic_calls"]["hvd_flash_swa_fwd"] == 1
    assert pre["temp_bytes"] < 1.3e9
    assert pre["resident_with_cache"] < 16 * 2 ** 30


def test_topology_aot_small_step_fits_beside_its_cache():
    """SmallThinker's cut (the block's second instance, PR 42) compiled
    for one v5e chip at the cell's 64 slots: the decode step aliases all
    four pools (5.65 GB, the window group's 3.23 of it) to their
    successors and holds ONE walk function of each name for its 28 query
    heads over 4 and 16 expert matmuls with ReLU gates; an 8,192-token
    prompt, in four chunks (one instance of the two expert matmuls in
    the lowered function of each kind of layer, window and full, which
    the eight layers call), fits beside 7.93 GB of weights and the
    cache."""
    out = _topology_worker("v5e:2x2", "small_step", "64", "8192")
    assert out["weight_bytes"] == 7_933_875_200
    assert out["cache_bytes"] == 5_649_989_632
    step, pre = out["decode"], out["prefill_8192"]
    assert set(step["pool_params"]) <= set(step["aliased_params"])
    assert step["mosaic_calls"]["hvd_swa_decode"] == 1
    assert step["mosaic_calls"]["hvd_cca_decode"] == 1
    assert step["mosaic_calls"]["hvd_moe_gmm"] == 2 * 8
    assert step["temp_bytes"] < 0.1e9
    assert pre["mosaic_calls"]["hvd_moe_gmm"] == 2 * 2
    assert pre["mosaic_calls"]["hvd_flash_swa_fwd"] == 1
    assert pre["temp_bytes"] < 0.8e9
    assert pre["resident_with_cache"] < 15.0e9


def test_topology_aot_ssm_step_updates_its_state_in_place():
    """Falcon-H1-34B's cut (PR 48) compiled for one v5e chip at the
    cell's 80 slots: the decode step aliases both pools AND the 2.04 GB
    float32 slot state to their successors, holds one walk function and
    one state update a layer, and NOTHING as large as one plane of the
    state (340 MB) is a temporary: a second copy of the state does not
    fit, and ``state[plane]`` materialised was a plane copied a layer
    (seen here first: 352 MB of temporaries, 11 MB since).  A group of
    four 256-token prompts and a 512-token prompt alone, each a chunked
    scan, fit beside 10.51 GB of weights and the 3.05 GB cache."""
    out = _topology_worker("v5e:2x2", "ssm_step", "80", "512", "256x4")
    assert out["weight_bytes"] == 10_509_188_224
    assert out["cache_bytes"] == 3_049_586_688
    step = out["decode"]
    assert step["aliased_params"] == step["pool_params"]
    assert step["alias_bytes"] == out["cache_bytes"]
    assert step["mosaic_calls"]["hvd_cca_decode"] == 1
    assert step["mosaic_calls"]["hvd_ssm_decode"] == 6
    assert step["temp_bytes"] < out["state_plane_bytes"] / 10
    for name in ("prefill_512", "prefill_256x4"):
        pre = out[name]
        assert pre["mosaic_calls"]["hvd_flash_hg_fwd"] == 1
        assert pre["temp_bytes"] < 0.3e9
        assert pre["resident_with_cache"] < 14.2e9


def test_topology_aot_eva_step_walks_both_groups_out_of_one_pool():
    """EvaByte's cut (PR 51) compiled for one v5e chip at the cell's 24
    slots: ONE pair of pools of 4,441 pages a plane (24 x (56 growing +
    129 ring) + the scratch page: 9.31 GB) holds both groups, the decode
    step aliases both to their successors, holds one lowered walk under
    its own name (``hvd_eva_decode``: the composed table is index
    arithmetic, no gathered copy of a slot's rows) and nothing as large
    as a twentieth of a plane as a temporary (the in-round pooling reads
    24 ring pages a layer); a 7,500-byte prompt attends in four windows,
    four shapes of the blocked flash forward, and fits beside 3.26 GB of
    weights and the cache."""
    out = _topology_worker("v5e:2x2", "eva_step", "24", "7500")
    assert out["weight_bytes"] == 3_261_865_984
    assert out["cache_bytes"] == 9_313_452_032
    assert out["pool_shape"] == [8, 4441, 16, 4096]
    step, pre = out["decode"], out["prefill_7500"]
    assert step["aliased_params"] == step["pool_params"]
    assert step["alias_bytes"] == out["cache_bytes"]
    assert step["mosaic_calls"] == {"hvd_eva_decode": 1, "hvd_cca_decode": 0,
                                    "hvd_flash_fwd": 0, "hvd_flash_hg_fwd": 0}
    assert step["temp_bytes"] < out["plane_bytes"] / 20
    assert pre["mosaic_calls"]["hvd_flash_fwd"] == 4
    assert pre["temp_bytes"] < 1.4e9
    assert pre["resident_with_cache"] < 14.4e9


def test_topology_aot_prefill_write_scatters_whole_pages_in_place():
    """The program that follows every prefill (``kvcache._pool_set``)
    compiled for one v5e chip.  At Mistral's pool and a 512-token prompt:
    ONE scatter of 16 layers x 32 pages = 512 updates (8,192 when a row
    was an update), the page and the entry in an update's window.  At
    SmallThinker's window pool and the 4,095 rows a window plane keeps
    of an 8,192-token prompt: ONE program holds both the 255 pages and
    the 15 rows that enter the ring's first page past its start.  Either
    way the pool is aliased input to output, nothing is a temporary and
    no copy is as large as a plane of the pool: a bfloat16 page is one
    row of tiles, and a write that re-laid the pool out to get at it
    would cost more than the sixteen rows it replaces."""
    out = _topology_worker("v5e:2x2", "pool_write")
    assert out["mistral_512"]["scatters"] == [
        {"updates": 16 * 512 // 16, "window": [16, 1024]}]
    assert out["smallthinker_window_8192"]["scatters"] == [
        {"updates": 6 * 255, "window": [16, 512]},
        {"updates": 6 * 15, "window": [512]}]
    for case in out.values():
        assert case["compiled_scatters"] == len(case["scatters"])
        assert case["aliased"] is True
        assert case["temp_bytes"] < 2 ** 20
        assert case["plane_sized_copies"] == []


def test_topology_aot_exchange_is_one_many_operand_all_reduce():
    """The leaf-wise gradient exchange over 32 leaves (50 MB of fp16),
    compiled for the v5e: the step holds one all-reduce a leaf and XLA's
    combiner must make ONE all-reduce of them with every leaf an operand
    in its own tiled layout -- and put no flat buffer back (no
    concatenate, no dynamic-update-slice, no relayout copy).  If a
    toolchain stops doing that, ``allreduce_gradients`` pays a collective
    a leaf and this fails before any chip time is spent."""
    out = _topology_worker("v5e:2x2", "exchange")
    assert out["leaves"] == 32
    assert out["all_reduces"] == 1
    assert out["operands"] == 32 and out["tiled_operands"] == 1
    assert (out["concatenate"], out["dynamic-update-slice"],
            out["copy"]) == (0, 0, 0)


def test_optimized_stats_counts_and_bytes():
    st = scaling.optimized_collective_stats(_HLO_SAMPLE)
    assert st.counts == {"all-reduce": 2, "all-gather": 1,
                         "collective-permute": 1}
    assert st.bytes["all-reduce"] == 1024 * 4 + 16 * 4 + 8 * 2
    assert st.bytes["all-gather"] == 64 * 2 * 4   # -done half not recounted
    assert st.bytes["collective-permute"] == 32 * 2


_STABLE_SAMPLE = """
  %3 = "stablehlo.all_reduce"(%2) <{...}> ({
    body
  }) : (tensor<128xf32>) -> tensor<128xf32>
  %9 = "stablehlo.collective_permute"(%8) {...} : (tensor<4x2xbf16>)
       -> tensor<4x2xbf16>
"""


def test_emitted_stats_parses_stablehlo():
    st = scaling.emitted_collective_stats(_STABLE_SAMPLE)
    assert st.counts == {"all-reduce": 1, "collective-permute": 1}
    assert st.bytes["all-reduce"] == 128 * 4
    assert st.bytes["collective-permute"] == 4 * 2 * 2


# ---------------------------------------------------------------------------
# In-process integration on the 8-device mesh.
# ---------------------------------------------------------------------------

def test_train_step_wire_accounting_in_process(hvd, n_devices):
    """Compile a small real train step and check the full chain: emitted
    bucket structure == fusion planner, optimized payload == parameter
    bytes + loss, donation present."""
    import optax
    from horovod_tpu.controller.fusion import plan_buckets
    from horovod_tpu.training import make_train_step

    params = {"w": jnp.zeros((256, 128), jnp.float32),
              "b": jnp.zeros((128,), jnp.float32),
              "h": jnp.zeros((64, 64), jnp.bfloat16)}

    def loss_fn(p, batch):
        x, y = batch
        return (jnp.mean((x @ p["w"] + p["b"]) ** 2)
                + jnp.mean(p["h"].astype(jnp.float32) ** 2)
                + jnp.mean(y * 0.0))

    opt = hv.DistributedOptimizer(optax.sgd(0.1))
    params = hv.replicate(params)
    opt_state = hv.replicate(opt.init(params))
    step = make_train_step(loss_fn, opt)
    n = n_devices
    batch = hv.shard_batch((jnp.zeros((2 * n, 256), jnp.float32),
                            jnp.zeros((2 * n,), jnp.float32)))

    lowered = step.lower(params, opt_state, batch)
    emitted = scaling.emitted_collective_stats(lowered.as_text())
    # Two dtype buckets in the plan (f32 + bf16), but the elementwise
    # exchange builds none: one psum a leaf (3) + the loss mean.
    buckets = len(plan_buckets(jax.tree.leaves(params)).buffers)
    assert buckets == 2
    assert emitted.counts.get("all-reduce") == \
        len(jax.tree.leaves(params)) + 1

    # Emitted payload preserves wire dtypes exactly (bf16 stays bf16).
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    assert emitted.bytes.get("all-reduce") == param_bytes + 4  # + loss

    compiled = lowered.compile()
    text = compiled.as_text()
    st = scaling.optimized_collective_stats(text)
    # The CPU backend may upcast sub-f32 reductions (bf16 -> f32), so the
    # optimized bytes bound the emitted payload within that 2x on the
    # bf16 leaf -- equality holds for the f32 part.
    f32_bytes = sum(x.size * 4 for x in jax.tree.leaves(params)
                    if x.dtype == jnp.float32)
    assert f32_bytes + 4 <= st.bytes.get("all-reduce") <= param_bytes * 2
    assert scaling.has_buffer_donation(text)


def test_llama_8b_lora_projection_clears_north_star():
    """The model at a long step and a small payload: a 1.25 s step (an
    earlier runtime's reading for the 8B LoRA step, not reproduced: an
    input to the model, no speed of this repository) against the
    adapter-only payload (21.0M f32 = 84 MB; the trainable half's wire
    bytes are pinned in test_zoo_wire_accounting.py) projects >= 99% at
    256 v5e chips with ZERO overlap."""
    payload = 21.0e6 * 4  # the 8B's rank-8 adapters, f32 wire
    step_s = 4 / 3.2      # 4 seqs/step at 3.2 seq/s = 1.25 s/chip
    pts = scaling.predict_efficiency(step_s, payload, scaling.V5E)
    e256 = [p for p in pts if p.n == 256][0]
    assert e256.eff_no_overlap >= 0.99
