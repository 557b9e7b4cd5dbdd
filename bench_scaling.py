"""Scaling-evidence harness: compiled-HLO wire accounting + 1->256 projection.

BASELINE.json's north star (>=90% scaling efficiency, 1->256 chips,
ResNet-50 + BERT-Large) cannot be timed without a pod; this harness
produces the mechanical evidence instead (see
``horovod_tpu/utils/scaling.py`` for the method and model):

1. compiles the REAL train step for each model over virtual CPU meshes
   of 8/16/32 (and optionally 64) devices -- abstract (ShapeDtypeStruct)
   lowering, so no parameter memory is materialized;
2. parses the optimized HLO for collective counts and payload bytes, and
   the emitted StableHLO for the bucket structure the latency-hiding
   scheduler would see;
3. asserts the two gateable invariants: the per-chip equivalent
   allreduce payload matches the fusion planner's prediction, and it is
   INDEPENDENT of the mesh size (the defining property of allreduce data
   parallelism);
4. projects the 1->256-chip efficiency curve from measured single-chip
   step times (round-2 bench numbers) + the measured wire bytes +
   published v5e/v5p link bandwidths, reporting no-overlap and
   full-overlap bounds.

Usage::

    python bench_scaling.py                  # rn50 + bert-large, n=8/16/32
    python bench_scaling.py --models rn50 --ns 8 16
    python bench_scaling.py --models rn50-chunked --ns 8 16
                         # chunked RS+AG exchange (HOROVOD_EXCHANGE_CHUNK_MB)
                         # -- same eq-AR payload, zero bucket all-reduces
    python bench_scaling.py --models rn50-overlap --ns 8 16
                         # backward-overlap microbatched exchange
                         # (microbatches=4): k per-bucket reduce-scatters
                         # interleaved with backward + one final all-gather
                         # -- eq payload (k+1)/2 x the padded bucket bytes
    python bench_scaling.py --models rn50-powersgd --ns 8 16
                         # PowerSGD error-feedback exchange (rank 4): two
                         # factor psums per bucket, eq payload r*(m+c)*4 B
                         # per bucket (>=8x under the uncompressed row);
                         # also runs a CPU convergence-proxy parity check
                         # vs the uncompressed exchange.  (topk is bench.py
                         # -only: its allgather wire grows with n, so the
                         # mesh-invariance gate does not apply.)
    python bench_scaling.py --models rn50-hier --ns 64 256
                         # two-level ICI x DCN exchange (fp8 on the DCN
                         # leg only): per-leg bytes recorded at trace
                         # time must equal the plan_hier_legs closed
                         # form, and -- both meshes sharing the 32-chip
                         # ICI extent -- be identical across mesh sizes;
                         # the DCN hop must ride under the flat-AR wire
    python bench_scaling.py --worker rn50 8  # (internal) one subprocess

Prints one summary JSON line (machine-readable gate) after the tables.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

# Single-chip step times measured on a TPU v5e on an earlier runtime
# (July-August 2026, not reproduced on today's libtpu; docs/benchmarks.md).
# BASELINE.json.published is empty, so these are the only chip numbers the
# projection has until the S1 benchmark re-measures them.
MEASURED_STEP_SECONDS = {
    # 2,542 img/s/chip at batch 256.
    "rn50": 256 / 2542.27,
    # 354 seq/s/chip at batch 32, seq 128 (docs/benchmarks.md, round 2;
    # reproduced round 5: fp16 354.2 same-process as the fp8 row below).
    "bert-large": 32 / 354.0,
    # MEASURED round 5 (one process, back-to-back with fp16's 354.2:
    # bert_pretrain --compression fp16,fp8).  NB at n=1 the VHDD
    # exchange degenerates, so this is the codec config's COMPUTE step
    # time; the n>1 quantize/dequant cost was probed separately
    # (1.15 ms / 80M elements isolated => <=8.5 ms/step upper bound
    # for this payload's exchanges, overlapping like the exchanges --
    # honest bracket in docs/benchmarks.md) and is NOT in this number.
    # Replaces the round-4 _STEP_ALIASES borrow.
    "bert-large-fp8": 32 / 353.7,
    # The reference's OWN headline scaling table is Inception V3 /
    # ResNet-101 / VGG-16 at 128 GPUs (~90/90/68% of linear, SURVEY.md
    # section 6) -- these rows project the same three models at the same
    # scale from this repo's measured batch-128 single-chip step times
    # (docs/benchmarks.md).
    "resnet101": 128 / 1269.0,
    "inception-v3": 128 / 1325.0,
    "vgg16": 128 / 1001.0,
}

# Step-time aliases: variant configs measured by the same bench row.
# (Empty since round 5: every projected config has its own measured
# step time.  The mechanism stays for future variant configs.)
_STEP_ALIASES = {}

# Microbatch count for the -overlap variant (bench.py's counterpart is
# BENCH_OVERLAP=1 / HOROVOD_MICROBATCHES=4).
OVERLAP_K = 4

# PowerSGD rank for the -powersgd variant (bench.py's counterpart is
# HOROVOD_COMPRESSION=powersgd:4); parity bound for the CPU convergence
# proxy (final-loss ratio vs uncompressed after PARITY_STEPS on the tiny
# CNN -- the tests' EF parity bound is tighter, this is regression wire).
POWERSGD_RANK = 4
PARITY_STEPS = 30
PARITY_BOUND = 1.25

# Two-level exchange variant (--models rn50-hier --ns 64 256): virtual
# (dcn, ici) meshes sharing one ICI extent -- 64 = 2x32, 256 = 8x32 --
# so the padding quantum (lcm(256, n_ici)) and with it EVERY per-leg
# payload is identical across mesh sizes: the hier mesh-invariance gate
# is exact equality on per-leg bytes, not a tolerance band.  The DCN
# hop rides the fp8 codec (the contended-cross-slice configuration the
# autotuner's hierarchical axis selects); ICI legs stay full precision.
HIER_ICI = 32
HIER_DCN_CODEC = "fp8"

# 3D-parallelism variant (--models bert-3d --ns 8 16): DP x TP on one
# build_3d_mesh, dcn_size x (data, model) virtual meshes sharing the TP
# extent -- 8 = 2x(2,2), 16 = 2x(4,2).  Because tp=2 on both meshes, the
# LOCAL (tp-sharded) gradient leaves are identical across mesh sizes, so
# every fp16 DP-exchange bucket -- and with it the whole DP gradient leg
# -- must be BYTE-IDENTICAL: the 3D gate is exact equality against the
# explain_plan closed form over the local leaves, not a tolerance band.
THREED_TP = 2
THREED_DCN = 2

# CNN cases: (constructor kwargs, image size).  Spatial size does not
# affect gradient payload EXCEPT for VGG (the 224x224 fc1 holds most of
# its 138M params), so VGG compiles at full resolution; Inception needs
# enough resolution to survive its VALID-padded stem.
_CNN_CASES = {
    "rn50": ("ResNet50", {}, 64),
    "resnet101": ("ResNet101", {}, 64),
    "vgg16": ("VGG16", {"dropout_rate": 0.0}, 224),
    "inception-v3": ("InceptionV3", {"dropout_rate": 0.0}, 128),
}


def _build_case(model: str, n: int, per_chip_batch: int = 0):
    """Build (step_fn, abstract_args, expected) for one model on an
    n-device mesh, without materializing any parameter memory.

    ``per_chip_batch`` overrides the compile-speed default (CNNs: 2,
    BERT: 1).  Payloads are batch-invariant; the TOPOLOGY mode passes the
    bench batch so the scheduled-compute weighting matches the measured
    step time."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.controller.fusion import plan_buckets
    from horovod_tpu.training import (batch_sharding, make_flax_train_step,
                                      make_train_step, replicated_sharding)

    rep = replicated_sharding()
    bat = batch_sharding()

    def abstract(tree, sharding):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    cnn_base = model[:-4] if model.endswith("-fp8") else model
    chunked = model.endswith("-chunked")
    if chunked:
        cnn_base = model[:-len("-chunked")]
    overlap = model.endswith("-overlap")
    if overlap:
        cnn_base = model[:-len("-overlap")]
    efspec = ""
    if model.endswith("-powersgd"):
        cnn_base = model[:-len("-powersgd")]
        efspec = f"powersgd:{POWERSGD_RANK}"
    hier = model.endswith("-hier")
    if hier:
        cnn_base = model[:-len("-hier")]
    if cnn_base in _CNN_CASES:
        from horovod_tpu import models as zoo
        # fp32 params = the bench configuration's wire dtype; the -fp8
        # variant swaps the gradient exchange to the e4m3 codec
        # (alltoall shards -> f32 local reduce -> all_gather), quartering
        # the wire.  Measured (round 5, docs/benchmarks.md): on this
        # toolchain the exchange's ops compile SYNCHRONOUS -- the win is
        # wire volume, not overlap.  XLA may also lower a gather leg to
        # an f32 all-reduce of the dequantized shards, inflating the eq
        # payload ~20% over the pure-fp8 model below: run the topology
        # gate for this variant with --tolerance 0.25.
        fp8 = model.endswith("-fp8")
        ctor, kwargs, side = _CNN_CASES[cnn_base]
        m = getattr(zoo, ctor)(num_classes=1000, dtype=jnp.float32,
                               **kwargs)
        # The -overlap variant splits the per-chip batch into OVERLAP_K
        # microbatches, so it needs a divisible per-chip batch.
        pcb = per_chip_batch or (OVERLAP_K if overlap else 2)
        x = jax.ShapeDtypeStruct((pcb * n, side, side, 3), jnp.float32)
        y = jax.ShapeDtypeStruct((pcb * n,), jnp.int32)
        variables = jax.eval_shape(
            lambda k: m.init(k, jnp.zeros((1, side, side, 3),
                                          jnp.float32), train=True),
            jax.random.PRNGKey(0))
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        if hier:
            # Per-leg codec: full-precision ICI legs, fp8 on the DCN hop
            # only (the two-level exchange's reason to exist).
            comp_arg = f"ici:none,dcn:{HIER_DCN_CODEC}"
        else:
            comp_arg = efspec or (hvd.Compression.fp8 if fp8
                                  else hvd.Compression.none)
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1, momentum=0.9), compression=comp_arg)
        opt_state = jax.eval_shape(opt.init, params)
        step = make_flax_train_step(
            m.apply, opt, microbatches=OVERLAP_K if overlap else None)
        if efspec:
            # Error-feedback state: per-bucket residuals are [n, size],
            # sharded over the leading axis (the shard-map pytree-prefix
            # spec in training._opt_state_spec), inner state replicated.
            opt_abs = type(opt_state)(
                residuals=tuple(
                    jax.ShapeDtypeStruct(r.shape, r.dtype, sharding=bat)
                    for r in opt_state.residuals),
                inner=abstract(opt_state.inner, rep))
        else:
            opt_abs = abstract(opt_state, rep)
        args = (abstract(params, rep), abstract(stats, rep), opt_abs,
                (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=bat),
                 jax.ShapeDtypeStruct(y.shape, y.dtype, sharding=bat)))
        stats_leaves = len(jax.tree.leaves(stats))
        grad_leaves = jax.tree.leaves(params)
        # Emitted all-reduces: one per gradient leaf (the elementwise
        # exchange builds no fusion bucket; XLA's combiner groups the
        # psums when it compiles), one per mutated
        # BN-stat leaf, one for the loss mean.  The -chunked
        # variant (HOROVOD_EXCHANGE_CHUNK_MB, set by run_worker) replaces
        # every bucket all-reduce with reduce-scatter+all-gather chunks,
        # so only the BN-stat and loss all-reduces remain -- and each
        # chunk's RS(c)+AG(c) moves exactly one AR(c) of link wire, so
        # the equivalent-allreduce payload must MATCH the plain rn50 row
        # (chunk padding is <= n-1 elements per bucket tail: noise).
        buckets = len(plan_buckets(grad_leaves).buffers)
        stats_bytes = sum(l.size * l.dtype.itemsize
                          for l in jax.tree.leaves(stats))
        if fp8 or hier:
            # hier: the bucket exchange is RS + gathers, never an AR;
            # the gate on its structure lives in the hier rows below.
            expected_emitted = None
        elif efspec:
            # PowerSGD: TWO factor psums per bucket (P, then the
            # orthonormalized back-projection Q) replace the bucket
            # all-reduce.
            expected_emitted = 2 * buckets + stats_leaves + 1
        elif chunked or overlap:
            # Bucket exchange is RS(+AG), not all-reduces: only the
            # BN-stat and loss all-reduces remain.
            expected_emitted = stats_leaves + 1
        else:
            expected_emitted = len(grad_leaves) + stats_leaves + 1
        grad_bytes = sum(l.size * l.dtype.itemsize for l in grad_leaves)
        if fp8:
            grad_bytes //= 4  # e4m3 wire (+ one f32 scale per bucket)
        if overlap:
            # Backward-overlap exchange: per bucket, OVERLAP_K per-
            # microbatch reduce-scatters + ONE finalize all-gather, each
            # over the bucket padded to the microbatch quantum
            # (lcm(n, 256) -- mesh-invariant for n=8/16/32, so the eq
            # payload spread across mesh sizes is exactly zero).  RS(P)
            # and AG(P) each move one half-allreduce of wire, so the
            # equivalent-allreduce payload is (k+1)/2 x the padded bucket
            # bytes; the plan walks leaves in REVERSE (bucket-ready
            # order), which regroups but never resizes the total.
            from horovod_tpu.collectives.ops import microbatch_pad_quantum
            rspec = plan_buckets(grad_leaves, reverse=True)
            buckets = len(rspec.buffers)
            q = microbatch_pad_quantum(n)
            padded_bytes = 0
            for dt, lspecs in rspec.buffers:
                size = sum(s.size for s in lspecs)
                padded = size + (-size) % q
                padded_bytes += padded * jnp.dtype(dt).itemsize
            payload = (OVERLAP_K + 1) * padded_bytes / 2 + stats_bytes + 4
        elif efspec:
            # Low-rank factor wire per bucket: r*(m+c) f32 elements across
            # the two psums (mesh-invariant -- factor shapes depend only
            # on the bucket size), plus the untouched BN-stat and loss
            # all-reduces.
            from horovod_tpu.collectives.compression import (
                parse_compression, wire_payload_bytes)
            comp = parse_compression(efspec)
            payload = sum(
                wire_payload_bytes(comp, sum(s.size for s in lspecs),
                                   jnp.dtype(dt).itemsize, n)
                for dt, lspecs in plan_buckets(grad_leaves).buffers) \
                + stats_bytes + 4
        elif hier:
            # Per-leg closed form from the SAME planner the runtime's
            # spans.note_leg accounting mirrors: padded bucket at f32 on
            # both ICI legs, the 1/n_ici shard at one byte/element on
            # the fp8 DCN hop.  Bucket sums are mesh-invariant because
            # every bench mesh shares HIER_ICI.
            from horovod_tpu.controller.fusion import plan_hier_legs
            hier_legs = {}
            for dt, lspecs in plan_buckets(grad_leaves).buffers:
                bsize = sum(s.size for s in lspecs)
                for leg in plan_hier_legs(
                        bsize, dt, n_dcn=n // HIER_ICI, n_ici=HIER_ICI,
                        compression=f"ici:none,dcn:{HIER_DCN_CODEC}"):
                    hier_legs[leg.tag] = hier_legs.get(leg.tag, 0) \
                        + leg.nbytes
            payload = sum(hier_legs.values()) + stats_bytes + 4
        else:
            payload = grad_bytes + stats_bytes + 4
    elif model in ("bert-large", "bert-base", "bert-tiny",
                   "bert-large-fp8"):
        from horovod_tpu.models import (BERT_BASE, BERT_LARGE, BERT_TINY,
                                        Bert)
        cfg = {"bert-large": BERT_LARGE, "bert-base": BERT_BASE,
               "bert-tiny": BERT_TINY,
               "bert-large-fp8": BERT_LARGE}[model]
        m = Bert(cfg, dtype=jnp.float32)
        seq = 128
        pcb = per_chip_batch or 1
        tokens = jax.ShapeDtypeStruct((pcb * n, seq), jnp.int32)
        nsp = jax.ShapeDtypeStruct((pcb * n,), jnp.int32)
        params = jax.eval_shape(
            lambda k: m.init(k, jnp.zeros((1, seq), jnp.int32)),
            jax.random.PRNGKey(0))
        # The BASELINE config: Adasum reduction + fp16 wire compression;
        # the -fp8 variant swaps the wire to the e4m3 exchange codec.
        comp = (hvd.Compression.fp8 if model.endswith("-fp8")
                else hvd.Compression.fp16)
        opt = hvd.DistributedAdasumOptimizer(
            optax.adamw(1e-3), compression=comp)
        opt_state = jax.eval_shape(opt.init, params)

        def loss_fn(p, batch):
            toks, nsp_y = batch
            mlm, nsp_logits = m.apply(p, toks)
            l_mlm = optax.softmax_cross_entropy_with_integer_labels(
                mlm, toks).mean()
            l_nsp = optax.softmax_cross_entropy_with_integer_labels(
                nsp_logits, nsp_y).mean()
            return l_mlm + l_nsp

        step = make_train_step(loss_fn, opt)
        args = (abstract(params, rep), abstract(opt_state, rep),
                (jax.ShapeDtypeStruct(tokens.shape, tokens.dtype,
                                      sharding=bat),
                 jax.ShapeDtypeStruct(nsp.shape, nsp.dtype, sharding=bat)))
        grad_leaves = jax.tree.leaves(params)
        buckets = len(plan_buckets(grad_leaves).buffers)
        expected_emitted = None  # Adasum: ppermute levels, not one AR/bucket
        # fp16 wire halves the fp32 gradient payload; the fp8 exchange
        # codec quarters it (scales are one f32 per exchanged piece --
        # noise next to MiB-scale buckets).
        wire_itemsize = 1 if model.endswith("-fp8") else 2
        payload = sum(l.size * wire_itemsize for l in grad_leaves) + 4
    elif model == "bert-3d":
        # 3D config (--models bert-3d): BERT on a dcn x (data, model)
        # mesh from build_3d_mesh -- TP params via tp_param_specs,
        # fp16 DP exchange over the data axes only, Adam moments
        # mirrored onto the param shards.  The run_worker counterpart
        # re-traces the step and splits its psums by dtype: the fp16
        # ones ARE the DP gradient leg (TP activation psums and the
        # loss mean run at f32), gated byte-exactly against the
        # explain_plan closed form below.
        from jax.sharding import PartitionSpec
        from horovod_tpu.controller.fusion import explain_plan
        from horovod_tpu.models import BERT_TINY, Bert, bert_tp_apply
        from horovod_tpu.parallel import data_axes, tp_param_specs
        from horovod_tpu.training import mirror_opt_state_specs
        mesh = hvd.mesh()
        cfg = BERT_TINY
        m = Bert(cfg, dtype=jnp.float32)
        seq = 128
        pcb = per_chip_batch or 1
        gb = pcb * (n // THREED_TP)   # batch shards over the data axes
        tokens = jax.ShapeDtypeStruct((gb, seq), jnp.int32)
        nsp = jax.ShapeDtypeStruct((gb,), jnp.int32)
        params = jax.eval_shape(
            lambda k: m.init(k, jnp.zeros((1, seq), jnp.int32)),
            jax.random.PRNGKey(0))
        specs = tp_param_specs(params, axis="model")

        def loss_fn(p, batch):
            toks, nsp_y = batch
            mlm, nsp_logits = bert_tp_apply(p, cfg, toks, axis="model")
            l_mlm = optax.softmax_cross_entropy_with_integer_labels(
                mlm, toks).mean()
            l_nsp = optax.softmax_cross_entropy_with_integer_labels(
                nsp_logits, nsp_y).mean()
            return l_mlm + l_nsp

        opt = hvd.DistributedOptimizer(optax.adamw(1e-3),
                                       compression=hvd.Compression.fp16,
                                       axes=data_axes(mesh))
        oss = mirror_opt_state_specs(opt, params, specs)
        opt_state = jax.eval_shape(opt.init, params)
        step = make_train_step(loss_fn, opt, mesh=mesh, tp=THREED_TP,
                               param_specs=specs, opt_state_specs=oss)
        args = (abstract(params, rep), abstract(opt_state, rep),
                (jax.ShapeDtypeStruct(tokens.shape, tokens.dtype,
                                      sharding=bat),
                 jax.ShapeDtypeStruct(nsp.shape, nsp.dtype, sharding=bat)))
        # The DP exchange buckets the LOCAL (tp-sharded) leaves: shrink
        # every spec-named dim by the tp extent, then price the fp16
        # wire with the SAME planner call the runtime makes.  Local
        # shapes depend only on tp, never on the data extent -- the
        # cross-mesh equality gate rides on that.
        spec_leaves = jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(s, PartitionSpec))
        local_leaves = [
            jax.ShapeDtypeStruct(
                tuple(d // THREED_TP
                      if i < len(s) and s[i] is not None else d
                      for i, d in enumerate(leaf.shape)), leaf.dtype)
            for leaf, s in zip(jax.tree.leaves(params), spec_leaves)]
        plan_rows = explain_plan(local_leaves,
                                 compression=hvd.Compression.fp16,
                                 register=False)
        dp_leg_bytes = sum(r["wire_bytes"] for r in plan_rows)
        buckets = len(plan_rows)
        expected_emitted = None   # mixed psum dtypes; gated in _gate_3d
        payload = dp_leg_bytes
        threed_planned = {
            "dp_leg_bytes": int(dp_leg_bytes),
            "dp_buckets": buckets,
            "mesh": [THREED_DCN, n // (THREED_TP * THREED_DCN),
                     THREED_TP],
            "tp": THREED_TP,
        }
    elif model == "rn50-zero1":
        # ZeRO-1 bench config (``--models rn50-zero1``; bench.py's
        # counterpart is ``HOROVOD_ZERO=1``): bare SGD+momentum, gradients
        # reduce-scattered over the per-dtype arenas, each chip updates
        # its 1/n slice, params return via allgather.  Uncompressed
        # RS+AG moves one ring allreduce of wire, so the equivalent-
        # allreduce payload must match the replicated rn50 row while the
        # momentum HBM is 1/n per chip.
        from horovod_tpu import models as zoo
        from horovod_tpu.optim import zero as zmod
        m = zoo.ResNet50(num_classes=1000, dtype=jnp.float32)
        side = 64
        pcb = per_chip_batch or 2
        x = jax.ShapeDtypeStruct((pcb * n, side, side, 3), jnp.float32)
        y = jax.ShapeDtypeStruct((pcb * n,), jnp.int32)
        variables = jax.eval_shape(
            lambda k: m.init(k, jnp.zeros((1, side, side, 3),
                                          jnp.float32), train=True),
            jax.random.PRNGKey(0))
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        opt = optax.sgd(0.1, momentum=0.9)
        grad_leaves = jax.tree.leaves(params)
        spec = zmod.plan_arena(grad_leaves, n)
        shards = [jax.ShapeDtypeStruct((b.shard,), b.dtype)
                  for b in spec.buffers]
        inner = jax.eval_shape(opt.init, shards)
        zero_state = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype,
                                           sharding=bat), inner)
        step = make_flax_train_step(m.apply, opt, zero_stage=1)
        args = (abstract(params, rep), abstract(stats, rep), zero_state,
                (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=bat),
                 jax.ShapeDtypeStruct(y.shape, y.dtype, sharding=bat)))
        buckets = len(spec.buffers)   # one RS + one AG per dtype arena
        expected_emitted = None       # RS+AG exchange, not all-reduces
        arena_bytes = sum(b.padded * jnp.dtype(b.dtype).itemsize
                          for b in spec.buffers)
        payload = arena_bytes + \
            sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(stats)) \
            + 4
    elif model == "llama-lora":
        # BASELINE config 4 STRUCTURE check (tiny shape; the 8B payload
        # is pure arithmetic once the structure is proven): int8 frozen
        # base + with_frozen step -- the wire must carry ONLY the LoRA
        # adapters + loss.  A regression that leaks base grads (or the
        # frozen tree) onto the wire breaks the payload equality below.
        from horovod_tpu.models import (LLAMA_TINY, LlamaLM, merge_frozen,
                                        split_frozen)
        m = LlamaLM(LLAMA_TINY, dtype=jnp.float32, lora_rank=4,
                    base_dtype="int8")
        seq = 32
        pcb = per_chip_batch or 1
        toks = jax.ShapeDtypeStruct((pcb * n, seq), jnp.int32)
        params = jax.eval_shape(
            lambda k: m.init(k, jnp.zeros((1, seq), jnp.int32)),
            jax.random.PRNGKey(0))
        trainable, frozen = split_frozen(params)
        # Compression.none: the virtual-CPU backend upcasts bf16
        # reductions to f32, which would break the byte-exact equality
        # this case exists for (the structure proof needs no codec; the
        # production 8B config's bf16 wire just halves these bytes).
        opt = hvd.DistributedOptimizer(optax.adamw(1e-3))
        opt_state = jax.eval_shape(opt.init, trainable)

        def loss_fn(tp, fz, t):
            logits = m.apply(merge_frozen(tp, fz), t)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], t[:, 1:]).mean()

        step = make_train_step(loss_fn, opt, with_frozen=True)
        args = (abstract(trainable, rep), abstract(opt_state, rep),
                jax.ShapeDtypeStruct(toks.shape, toks.dtype, sharding=bat),
                abstract(frozen, rep))
        grad_leaves = jax.tree.leaves(trainable)
        buckets = len(plan_buckets(grad_leaves).buffers)
        expected_emitted = len(grad_leaves) + 1  # adapter leaves + loss
        # f32 adapters on the wire; the frozen tree must contribute 0.
        payload = sum(l.size * l.dtype.itemsize for l in grad_leaves) + 4
    else:
        raise SystemExit(f"unknown model {model!r}")
    expected = {
        "buckets": buckets,
        "expected_emitted_allreduces": expected_emitted,
        "predicted_payload_bytes": payload,
    }
    if efspec:
        expected["uncompressed_payload_bytes"] = \
            sum(l.size * l.dtype.itemsize for l in grad_leaves) \
            + stats_bytes + 4
    if hier:
        expected["hier_legs_planned"] = hier_legs
        # What a FLAT allreduce of the same buckets would put on every
        # link -- DCN included: the wire the two-level decomposition plus
        # the DCN codec exists to undercut on the slow cross-slice hop.
        expected["flat_allreduce_bytes"] = grad_bytes
    if model == "bert-3d":
        expected["threed_planned"] = threed_planned
    return step, args, expected


def run_worker(model: str, n: int, topology: str = "") -> None:
    """Compile one (model, n) case and print its stats as one JSON line.

    With ``topology`` (e.g. ``v5e:2x4``): deviceless AOT against the REAL
    TPU compiler via ``jax.experimental.topologies`` -- the optimized
    module is a scheduled TPU executable, so the sync/async collective
    split and window placement are read off the actual schedule (round-4
    evidence; no TPU hardware is attached).  Requires exclusive use of
    the in-process libtpu (the compiler takes a host-wide lockfile), so
    topology workers run sequentially.
    """
    if model.endswith("-chunked"):
        # The chunk knob must be in the environment before init()
        # snapshots the config; 4 MiB splits every >4 MiB fusion bucket.
        os.environ.setdefault("HOROVOD_EXCHANGE_CHUNK_MB", "4")

    import jax

    import horovod_tpu as hvd
    from horovod_tpu.utils import scaling

    schedule = None
    if topology:
        from jax.experimental import topologies

        from horovod_tpu.parallel.mesh import build_mesh
        td = topologies.get_topology_desc(platform="tpu",
                                          topology_name=topology)
        devs = list(td.devices)
        assert len(devs) == n, (len(devs), n)
        hvd.init(mesh=build_mesh(devs))
        # Compile at the bench per-chip batch so schedule weights match
        # the measured step (payloads themselves are batch-invariant).
        pcb = {"rn50": 8, "rn50-fp8": 8, "bert-large": 32,
               "bert-large-fp8": 32}.get(model, 0)
        step, args, expected = _build_case(model, n, per_chip_batch=pcb)
    else:
        from horovod_tpu.utils.platform import force_host_device_count
        force_host_device_count(n, cpu=True)
        if model.endswith("-hier"):
            from horovod_tpu.parallel.mesh import build_mesh
            if n % HIER_ICI:
                raise SystemExit(
                    f"-hier meshes are (n/{HIER_ICI}, {HIER_ICI}); "
                    f"n={n} does not divide")
            hvd.init(mesh=build_mesh(jax.devices()[:n], hierarchical=True,
                                     dcn_size=n // HIER_ICI))
        elif model == "bert-3d":
            from horovod_tpu.parallel.mesh import build_3d_mesh
            quantum = THREED_TP * THREED_DCN
            if n % quantum:
                raise SystemExit(
                    f"bert-3d meshes are {THREED_DCN}x(n/{quantum}, "
                    f"{THREED_TP}); n={n} does not divide")
            hvd.init(mesh=build_3d_mesh(
                jax.devices()[:n], data=n // quantum, model=THREED_TP,
                dcn_size=THREED_DCN))
        else:
            hvd.init()
        step, args, expected = _build_case(model, n)
    assert hvd.size() == n, (hvd.size(), n)
    lowered = step.lower(*args)
    hier_block = None
    if model.endswith("-hier"):
        # spans.note_leg fires at trace time (once per bucket per leg),
        # so after .lower() the recorder's registry holds the exchange's
        # OWN byte accounting -- the numbers the gate compares against
        # the plan_hier_legs closed form.
        from horovod_tpu.timeline.spans import recorder
        hier_block = {
            "mesh": [n // HIER_ICI, HIER_ICI],
            "legs_recorded": {
                k: int(v["nbytes"]) for k, v in recorder().legs.items()
                if k.startswith("hier/")},
        }
    threed_block = None
    if model == "bert-3d":
        # Re-trace the step and split its psums by dtype: the DP
        # gradient leg runs at the fp16 wire dtype, everything else
        # (TP activation psums, the loss mean) at f32 -- so the fp16
        # byte sum IS the DP leg, comparable byte-for-byte against
        # the explain_plan closed form in threed_planned.
        import jax.numpy as jnp
        from horovod_tpu.analysis.jaxpr_walk import collect_collectives
        inner = step
        while hasattr(inner, "_fn"):
            inner = inner._fn
        recs = collect_collectives(jax.make_jaxpr(inner)(*args))
        dp = [r for r in recs if r.kind == "psum"
              and r.dtype == "float16"]
        tp_psums = [r for r in recs if r.kind == "psum"
                    and "model" in r.axes]
        threed_block = {
            "mesh": expected["threed_planned"]["mesh"],
            "dp_psum_bytes": sum(
                r.elements * jnp.dtype(r.dtype).itemsize for r in dp),
            "dp_psum_count": len(dp),
            "dp_axes": sorted({a for r in dp for a in r.axes}),
            "tp_psum_count": len(tp_psums),
            "tp_psum_bytes": sum(
                r.elements * jnp.dtype(r.dtype).itemsize
                for r in tp_psums),
        }
    emitted = scaling.emitted_collective_stats(lowered.as_text())
    compiled = lowered.compile()
    text = compiled.as_text()
    opt_stats = scaling.optimized_collective_stats(text)
    if topology:
        rep = scaling.schedule_overlap_report(text, n_devices=n)
        schedule = {
            "sync": [(o, b) for o, b, _ in rep.sync_collectives],
            "async": [(o, b) for o, b, _, _ in rep.async_collectives],
            "sync_bytes": rep.sync_bytes,
            "sync_eq_payload": rep.sync_eq_payload(),
            "async_bytes": rep.async_bytes,
            "async_eq_payload": rep.async_eq_payload(),
            "async_window_seconds": rep.async_window_seconds,
            "total_compute_seconds": rep.total_compute_seconds,
            "n_instructions": rep.n_instructions,
        }

    # Equivalent allreduce payload: link-level wire bytes normalized by
    # the ring factor, comparable across mesh sizes and op mixes.
    wire = 0.0
    for op, b in opt_stats.bytes.items():
        if op == "all-reduce":
            wire += 2.0 * b * (n - 1) / n
        elif op == "all-gather":
            wire += b * (n - 1) / n
        elif op == "reduce-scatter":
            wire += b * (n - 1)
        elif op == "all-to-all":
            wire += b * (n - 1) / n
        else:                      # collective-permute: point-to-point
            wire += b
    eq_payload = wire / (2.0 * (n - 1) / n) if n > 1 else 0.0

    print(json.dumps({
        "model": model, "n": n,
        "emitted": {"counts": emitted.counts, "bytes": emitted.bytes},
        "optimized": {"counts": opt_stats.counts, "bytes": opt_stats.bytes},
        "wire_link_bytes": wire,
        "equivalent_allreduce_payload": eq_payload,
        "donation": scaling.has_buffer_donation(text),
        "schedule": schedule,
        "hier": hier_block,
        "threed": threed_block,
        **expected,
    }), flush=True)


def run_parity_worker(model: str, n: int,
                      steps: int = PARITY_STEPS) -> None:
    """Convergence proxy for the -powersgd variant: train the tiny CNN
    (bench.py's BENCH_TINY config) on a virtual CPU mesh for ``steps``
    steps with the error-feedback codec and uncompressed, same data and
    init, and print the final-loss ratio as one JSON line.  A proxy, not
    a benchmark: one repeated batch, so the loss must drop under both
    exchanges and the ratio bounds the codec's optimization drag
    (tests/test_compression_ef.py holds the tight bound)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(n, cpu=True)
    import horovod_tpu as hvd
    from horovod_tpu.models.resnet import BasicBlock, ResNet
    from horovod_tpu.training import make_flax_train_step

    hvd.init()
    assert model.endswith("-powersgd"), model
    spec = f"powersgd:{POWERSGD_RANK}"
    m = ResNet(stage_sizes=[1], block_cls=BasicBlock, num_filters=8,
               num_classes=10, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    gb = 4 * n
    x = jax.random.normal(key, (gb, 32, 32, 3), jnp.float32)
    y = jax.random.randint(key, (gb,), 0, 10, jnp.int32)

    def run(compression):
        # Fresh init per run (same key -> identical values): the donated
        # step consumes the replicated buffers, which can alias the init
        # tree, so reusing one init across runs reads deleted arrays.
        variables = m.init(key, x[:2], train=True)
        batch = hvd.shard_batch((x, y))
        params = hvd.replicate(variables["params"])
        stats = hvd.replicate(variables["batch_stats"])
        opt = hvd.DistributedOptimizer(optax.sgd(0.05, momentum=0.9),
                                       compression=compression)
        opt_state = hvd.replicate(opt.init(variables["params"]))
        step = make_flax_train_step(m.apply, opt)
        losses = []
        for _ in range(steps):
            params, stats, opt_state, loss = step(params, stats,
                                                  opt_state, batch)
            losses.append(float(loss))
        return losses

    base = run(None)
    comp = run(spec)
    tail = max(steps // 6, 1)
    b = float(np.mean(base[-tail:]))
    c = float(np.mean(comp[-tail:]))
    print(json.dumps({
        "parity_spec": spec, "steps": steps, "n": n,
        "loss_first": round(base[0], 4),
        "final_loss_uncompressed": round(b, 4),
        "final_loss_compressed": round(c, 4),
        "ratio": round(c / max(b, 1e-9), 4),
    }), flush=True)


def _spawn(model: str, n: int, timeout: int = 2400,
           topology: str = "", parity: bool = False) -> dict:
    # Autotune must not leak into workers: the tuned wrapper is a plain
    # function without .lower(), which the AOT accounting needs.
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "HOROVOD_AUTOTUNE", "HVD_TPU_AUTOTUNE",
                        # Per-case knobs: the -chunked worker sets its own
                        # chunk size; a stray ambient value must not leak
                        # into the baseline rows' accounting.
                        "HOROVOD_EXCHANGE_CHUNK_MB",
                        "HVD_TPU_EXCHANGE_CHUNK_MB",
                        "HOROVOD_STEPS_PER_EXEC",
                        "HVD_TPU_STEPS_PER_EXEC",
                        "HOROVOD_MICROBATCHES",
                        "HVD_TPU_MICROBATCHES",
                        # The -powersgd worker passes its codec through
                        # the optimizer argument, never the environment.
                        "HOROVOD_COMPRESSION", "HVD_TPU_COMPRESSION",
                        "HOROVOD_EF_RESIDUAL", "HVD_TPU_EF_RESIDUAL",
                        "HOROVOD_AUTOTUNE_CODEC", "HVD_TPU_AUTOTUNE_CODEC",
                        # The -hier worker builds its own two-level mesh;
                        # an ambient topology spec or autotuner hier axis
                        # must not re-mesh the flat baseline rows.
                        "HOROVOD_HIERARCHICAL", "HVD_TPU_HIERARCHICAL",
                        "HOROVOD_AUTOTUNE_HIER", "HVD_TPU_AUTOTUNE_HIER",
                        # The bert-3d worker builds its own 3D mesh; an
                        # ambient TP/pipeline/MoE knob must not re-mesh
                        # the flat baseline rows.
                        "HOROVOD_TP", "HVD_TPU_TP",
                        "HOROVOD_PIPELINE_STAGES",
                        "HVD_TPU_PIPELINE_STAGES",
                        "HOROVOD_MOE_COMPRESSION",
                        "HVD_TPU_MOE_COMPRESSION",
                        "HOROVOD_AUTOTUNE_MOE", "HVD_TPU_AUTOTUNE_MOE")}
    cmd = [sys.executable, os.path.abspath(__file__),
           "--parity" if parity else "--worker", model, str(n)]
    if topology:
        cmd += ["--topology", topology]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0:
        raise RuntimeError(
            f"worker {model}@{n} failed:\n{proc.stdout[-2000:]}\n"
            f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _gate_hier(model, rows, summary) -> bool:
    """Gates for the two-level (-hier) rows.

    H1: the bytes the exchange registered at trace time (spans.note_leg)
    equal the ``plan_hier_legs`` closed form, leg by leg.  H2: those
    per-leg payloads are IDENTICAL across mesh sizes (the meshes share
    the ICI extent, so padding, shard width, and codec wire all cancel
    -- any drift means the exchange picked up a mesh-shape dependence).
    H3: the emitted StableHLO carries the planned structure -- one
    reduce-scatter plus three all-gathers per bucket (quantized shard +
    scale over DCN, finalize over ICI), zero bucket all-reduces.  H4:
    the DCN hop's wire sits under what a flat allreduce would put on the
    same cross-slice links.
    """
    ok = True
    planned0 = rows[0]["hier_legs_planned"]
    flat = rows[0]["flat_allreduce_bytes"]
    buckets = rows[0]["buckets"]
    legs_match = invariant = True
    for r in rows:
        if r["hier"]["legs_recorded"] != r["hier_legs_planned"]:
            ok = legs_match = False
            print(f"FAIL: n={r['n']} recorded legs "
                  f"{r['hier']['legs_recorded']} != planner closed form "
                  f"{r['hier_legs_planned']}")
        if r["hier_legs_planned"] != planned0:
            ok = invariant = False
            print(f"FAIL: per-leg payloads vary with the mesh: "
                  f"n={r['n']} {r['hier_legs_planned']} != "
                  f"n={rows[0]['n']} {planned0}")
        rs = r["emitted"]["counts"].get("reduce-scatter", 0)
        ag = r["emitted"]["counts"].get("all-gather", 0)
        if rs != buckets or ag != 3 * buckets:
            ok = False
            print(f"FAIL: n={r['n']} emitted {rs} reduce-scatters / {ag} "
                  f"all-gathers; the {buckets}-bucket plan needs "
                  f"{buckets} / {3 * buckets}")
    dcn = planned0.get("hier/dcn_ar", 0)
    ratio = flat / dcn if dcn else 0.0
    if not 0 < dcn < flat:
        ok = False
        print(f"FAIL: DCN leg {dcn} B not under the flat-AR wire "
              f"{flat} B")
    for leg in sorted(planned0):
        print(f"- {leg}: {planned0[leg]/2**20:.2f} MiB/step "
              f"(mesh-invariant, == planner closed form)")
    print(f"- DCN hop vs flat AR on the cross-slice links: "
          f"{dcn/2**20:.2f} MiB vs {flat/2**20:.1f} MiB "
          f"({ratio:.1f}x reduction)")
    summary[model] = {
        "dcn_codec": HIER_DCN_CODEC,
        "ns": [r["n"] for r in rows],
        "meshes": {str(r["n"]): r["hier"]["mesh"] for r in rows},
        "legs": planned0,
        "total_wire_bytes": sum(planned0.values()),
        "flat_allreduce_bytes": flat,
        "dcn_vs_flat_ratio": round(ratio, 2),
        "legs_match_plan": legs_match,
        "mesh_invariant": invariant,
        "buckets": buckets,
    }
    return ok


def _gate_3d(model, rows, summary) -> bool:
    """Gates for the 3D (--models bert-3d) rows.

    D1: the fp16 psum bytes the traced step actually carries on the DP
    gradient leg equal the ``explain_plan`` closed form over the LOCAL
    (tp-sharded) leaves -- byte-exact, no tolerance.  D2: those bytes
    are IDENTICAL across the two virtual mesh shapes (both share tp=2,
    so the local leaves -- and every fp16 bucket -- are the same; any
    drift means the DP exchange picked up a mesh-shape dependence).
    D3: the DP psums span ONLY the data axes (a ``model``/``pipe`` name
    in a gradient psum means the exchange leaked into the
    model-parallel domain and tp ranks would stop diverging).  D4: the
    TP activation psums are present and their count is mesh-invariant
    (forward row-psums plus the Megatron-f backward merges depend on
    the model, never on the data extent).
    """
    ok = True
    planned0 = rows[0]["threed_planned"]
    traced0 = rows[0]["threed"]
    for r in rows:
        got, want = r["threed"], r["threed_planned"]
        if got["dp_psum_bytes"] != want["dp_leg_bytes"]:
            ok = False
            print(f"FAIL: n={r['n']} traced DP leg "
                  f"{got['dp_psum_bytes']} B != planner closed form "
                  f"{want['dp_leg_bytes']} B over the local leaves")
        if want["dp_leg_bytes"] != planned0["dp_leg_bytes"] or \
                got["dp_psum_bytes"] != traced0["dp_psum_bytes"]:
            ok = False
            print(f"FAIL: DP leg varies with the mesh: n={r['n']} "
                  f"{got['dp_psum_bytes']} B != n={rows[0]['n']} "
                  f"{traced0['dp_psum_bytes']} B")
        leaked = [a for a in got["dp_axes"] if a not in ("dcn", "data")]
        if leaked or not got["dp_axes"]:
            ok = False
            print(f"FAIL: n={r['n']} DP psums span {got['dp_axes']}; "
                  f"the gradient exchange must stay on the data axes")
        if got["tp_psum_count"] < 1 or \
                got["tp_psum_count"] != traced0["tp_psum_count"]:
            ok = False
            print(f"FAIL: n={r['n']} {got['tp_psum_count']} TP psums "
                  f"(n={rows[0]['n']} had {traced0['tp_psum_count']}); "
                  f"expected a positive mesh-invariant count")
    print(f"- DP gradient leg: {traced0['dp_psum_bytes']/2**20:.2f} "
          f"MiB/step fp16 over {planned0['dp_buckets']} bucket(s) "
          f"(mesh-invariant, == planner closed form)")
    print(f"- TP activation psums: {traced0['tp_psum_count']} f32 "
          f"({traced0['tp_psum_bytes']/2**20:.2f} MiB) over the model "
          f"axis; DP psum axes: {traced0['dp_axes']}")
    summary[model] = {
        "tp": planned0["tp"],
        "ns": [r["n"] for r in rows],
        "meshes": {str(r["n"]): r["threed"]["mesh"] for r in rows},
        "dp_leg_bytes": traced0["dp_psum_bytes"],
        "dp_buckets": planned0["dp_buckets"],
        "dp_axes": traced0["dp_axes"],
        "tp_psum_count": traced0["tp_psum_count"],
        "tp_psum_bytes": traced0["tp_psum_bytes"],
        "dp_leg_matches_plan":
            traced0["dp_psum_bytes"] == planned0["dp_leg_bytes"],
        "mesh_invariant": all(
            r["threed"]["dp_psum_bytes"] == traced0["dp_psum_bytes"]
            for r in rows),
    }
    return ok


def _write_3d_round(args, ts, ok) -> None:
    """``--out BENCH_r<k>.json`` after a bert-3d run: emit the round
    record shape bench.py --trajectory and tests/test_bench_guard.py's
    ``scan_3d_entries`` consume."""
    import re
    m = re.search(r"r(\d+)", os.path.basename(args.out))
    rec = {
        "n": int(m.group(1)) if m else 0,
        "cmd": "JAX_PLATFORMS=cpu python bench_scaling.py --models "
               + " ".join(args.models)
               + " --ns " + " ".join(str(n) for n in args.ns),
        "rc": 0 if ok else 1,
        "tail": f"3D exchange: DP gradient leg "
                f"{ts['dp_leg_bytes']/2**20:.2f} MiB fp16 over the data "
                f"axes, byte-equal to the planner closed form on the "
                f"local leaves and invariant across n={args.ns}; "
                f"{ts['tp_psum_count']} TP activation psums on the "
                f"model axis",
        "parsed": {
            "metric": "threed_dp_leg_mib",
            "value": round(ts["dp_leg_bytes"] / 2**20, 2), "unit": "MiB",
            # A virtual-CPU wire drill is never throughput-comparable to
            # the measured baseline config.
            "vs_baseline": None,
            "config": f"bert_tiny_3d_dcn{THREED_DCN}_tp{THREED_TP}"
                      f"_fp16dp",
            "baseline_config": "batch256_s2d_bf16",
            "threed": ts,
        },
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")


def _write_hier_round(args, hs, ok) -> None:
    """``--out BENCH_r<k>.json`` after a -hier run: emit the round record
    shape bench.py --trajectory and tests/test_bench_guard.py consume."""
    import re
    m = re.search(r"r(\d+)", os.path.basename(args.out))
    dcn, flat = hs["legs"]["hier/dcn_ar"], hs["flat_allreduce_bytes"]
    rec = {
        "n": int(m.group(1)) if m else 0,
        "cmd": "JAX_PLATFORMS=cpu python bench_scaling.py --models "
               + " ".join(args.models)
               + " --ns " + " ".join(str(n) for n in args.ns),
        "rc": 0 if ok else 1,
        "tail": f"hier exchange: DCN leg {dcn/2**20:.2f} MiB vs "
                f"{flat/2**20:.1f} MiB flat AR "
                f"({hs['dcn_vs_flat_ratio']}x); per-leg bytes match "
                f"plan_hier_legs on n={args.ns}",
        "parsed": {
            "metric": "hier_dcn_wire_reduction",
            "value": hs["dcn_vs_flat_ratio"], "unit": "x",
            # A virtual-CPU wire drill is never throughput-comparable to
            # the measured baseline config.
            "vs_baseline": None,
            "config": f"rn50_hier_ici{HIER_ICI}_{HIER_DCN_CODEC}dcn",
            "baseline_config": "batch256_s2d_bf16",
            "hier": hs,
        },
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")


def run_topology_mode(args) -> int:
    """Deviceless AOT against the real TPU compiler: compile each model
    for ``--topology`` and gate on the SCHEDULE (sync/async collective
    split read off the compiled module, not assumed)."""
    from horovod_tpu.utils import scaling

    n = 1
    for d in args.topology.split(":")[1].split("x"):
        n *= int(d)
    ok = True
    summary = {}
    for model in args.models:
        r = _spawn(model, n, topology=args.topology)
        sch = r["schedule"]
        predicted = r["predicted_payload_bytes"]
        total = sch["sync_bytes"] + sch["async_bytes"]
        print(f"\n## {model} @ {args.topology}: compiled TPU schedule")
        print(f"- instructions: {sch['n_instructions']}, est. compute "
              f"{sch['total_compute_seconds']*1e3:.1f} ms")
        print(f"- SYNC collectives: {len(sch['sync'])} "
              f"({sch['sync_bytes']/2**20:.1f} MiB) "
              f"{[(o, round(b/2**20, 2)) for o, b in sch['sync'][:6]]}")
        print(f"- ASYNC collectives: {len(sch['async'])} "
              f"({sch['async_bytes']/2**20:.1f} MiB), compute scheduled "
              f"inside windows: {sch['async_window_seconds']*1e3:.2f} ms")
        # Gate T1: the schedule accounts for the planner's payload
        # (equivalent-allreduce units on both sides).
        eq_total = sch.get("sync_eq_payload",
                           sch["sync_bytes"]) + sch["async_eq_payload"]
        drift = abs(eq_total - predicted) / predicted
        if drift > 2 * args.tolerance:
            ok = False
            print(f"FAIL: scheduled eq payload {eq_total/2**20:.1f} MiB "
                  f"deviates {drift:.1%} from planner "
                  f"{predicted/2**20:.1f} MiB")
        summary[model] = {
            "sync_bytes": sch["sync_bytes"],
            "async_bytes": sch["async_bytes"],
            "async_window_seconds": sch["async_window_seconds"],
        }
        if model in MEASURED_STEP_SECONDS or model in _STEP_ALIASES:
            step_s = MEASURED_STEP_SECONDS[_STEP_ALIASES.get(model, model)]
            rep = scaling.ScheduleReport(
                sync_collectives=[(o, b, 0) for o, b in sch["sync"]],
                async_collectives=[(o, b, 0, 0) for o, b in sch["async"]],
                async_window_seconds=sch["async_window_seconds"],
                total_compute_seconds=sch["total_compute_seconds"],
                n_instructions=sch["n_instructions"], n_devices=n)
            print(f"\n### {model}: efficiency from the COMPILED schedule "
                  f"(measured step {step_s*1e3:.1f} ms/chip; derate rows "
                  f"divide async link bandwidth)")
            print("| chips | t_comm v5e | no-overlap | compiled-schedule "
                  "| scheduled @4x derate |")
            print("|---|---|---|---|---|")
            for pt, pt4 in zip(
                    scaling.predict_efficiency_scheduled(
                        step_s, rep, scaling.V5E, ns=(8, 64, 256)),
                    scaling.predict_efficiency_scheduled(
                        step_s, rep, scaling.V5E, ns=(8, 64, 256),
                        bandwidth_derate=4.0)):
                print(f"| {pt.n} | {pt.comm_seconds*1e3:.2f} ms "
                      f"| {pt.eff_no_overlap:.1%} "
                      f"| {pt.eff_full_overlap:.1%} "
                      f"| {pt4.eff_full_overlap:.1%} |")
            e256 = scaling.predict_efficiency_scheduled(
                step_s, rep, scaling.V5E, ns=(256,))[0]
            e256d = scaling.predict_efficiency_scheduled(
                step_s, rep, scaling.V5E, ns=(256,),
                bandwidth_derate=4.0)[0]
            summary[model]["eff_256_v5e_scheduled"] = round(
                e256.eff_full_overlap, 4)
            summary[model]["eff_256_v5e_scheduled_derate4"] = round(
                e256d.eff_full_overlap, 4)
            # Gate T2 (headline CNN): the scheduled number itself clears
            # the >=90% north star at 256 chips.
            if model == "rn50" and e256.eff_full_overlap < 0.90:
                ok = False
                print("FAIL: rn50 scheduled efficiency below 90%")
    print()
    result = {"metric": "scaling_schedule", "ok": ok,
              "topology": args.topology, "models": summary}
    print(json.dumps(result), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    return 0 if ok else 1


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--worker", nargs=2, metavar=("MODEL", "N"))
    p.add_argument("--parity", nargs=2, metavar=("MODEL", "N"),
                   help="(internal) convergence-proxy subprocess for the "
                        "-powersgd variant")
    p.add_argument("--models", nargs="+",
                   default=["rn50", "bert-large"])
    p.add_argument("--ns", nargs="+", type=int, default=[8, 16, 32])
    p.add_argument("--topology", default="",
                   help="TPU topology (e.g. v5e:2x4): deviceless AOT "
                        "against the real TPU compiler; gates on the "
                        "compiled schedule instead of virtual-CPU HLO")
    p.add_argument("--tolerance", type=float, default=0.02,
                   help="relative tolerance for the payload invariants")
    p.add_argument("--out", default="",
                   help="also write the summary JSON to this file "
                        "(topology mode: the committed SCALING_r*.json "
                        "artifact)")
    args = p.parse_args()
    if args.worker:
        run_worker(args.worker[0], int(args.worker[1]),
                   topology=args.topology)
        return 0
    if args.parity:
        run_parity_worker(args.parity[0], int(args.parity[1]))
        return 0
    if args.topology:
        return run_topology_mode(args)

    from horovod_tpu.utils import scaling

    ok = True
    summary = {}
    for model in args.models:
        rows = [_spawn(model, n) for n in args.ns]
        payloads = [r["equivalent_allreduce_payload"] for r in rows]
        predicted = rows[0]["predicted_payload_bytes"]
        print(f"\n## {model}: wire accounting "
              f"(fusion buckets: {rows[0]['buckets']})")
        print("| n | emitted colls | optimized colls | wire bytes/chip | "
              "eq. AR payload | donation |")
        print("|---|---|---|---|---|---|")
        for r in rows:
            print(f"| {r['n']} | {sum(r['emitted']['counts'].values())} "
                  f"| {sum(r['optimized']['counts'].values())} "
                  f"| {r['wire_link_bytes']/2**20:.1f} MiB "
                  f"| {r['equivalent_allreduce_payload']/2**20:.1f} MiB "
                  f"| {r['donation']} |")
        if model.endswith("-hier"):
            # Two-level rows gate on per-leg equality with the planner
            # (exact), not the flat eq-AR drift band: the generic wire
            # normalization assumes every collective spans the full
            # mesh, which the whole point of the hier exchange is not
            # to do.  Donation must still hold.
            ok &= _gate_hier(model, rows, summary)
            if not all(r["donation"] for r in rows):
                ok = False
                print("FAIL: buffer donation missing")
            continue
        if model == "bert-3d":
            # 3D rows gate on the DP-leg/planner byte equality (exact),
            # not the flat eq-AR drift band: the TP activation psums
            # span only the model axis, which the generic full-mesh
            # wire normalization misprices by design.  Donation must
            # still hold.
            ok &= _gate_3d(model, rows, summary)
            if not all(r["donation"] for r in rows):
                ok = False
                print("FAIL: buffer donation missing")
            continue
        # Gate 1: payload matches the fusion planner's prediction.
        drift = abs(payloads[0] - predicted) / predicted
        if drift > args.tolerance:
            ok = False
            print(f"FAIL: payload {payloads[0]/2**20:.2f} MiB deviates "
                  f"{drift:.1%} from planner prediction "
                  f"{predicted/2**20:.2f} MiB")
        # Gate 2: payload is mesh-size invariant.
        spread = (max(payloads) - min(payloads)) / max(payloads)
        if spread > args.tolerance:
            ok = False
            print(f"FAIL: payload varies {spread:.1%} across n={args.ns}")
        # Gate 3: in-place update (donation) everywhere.
        if not all(r["donation"] for r in rows):
            ok = False
            print("FAIL: buffer donation missing")
        # Gate 4 (RN50): emitted bucket structure as planned.
        exp = rows[0]["expected_emitted_allreduces"]
        if exp is not None:
            got = rows[0]["emitted"]["counts"].get("all-reduce", 0)
            if got != exp:
                ok = False
                print(f"FAIL: emitted {got} all-reduces, planner expected "
                      f"{exp}")
        summary[model] = {
            "payload_bytes": payloads[0], "planner_bytes": predicted,
            "spread": spread, "buckets": rows[0]["buckets"],
        }
        # Gates 5+6 (-powersgd): the factor wire clears the >=8x
        # reduction target, and the CPU convergence proxy stays within
        # the parity bound of the uncompressed exchange.
        unc = rows[0].get("uncompressed_payload_bytes")
        if unc:
            ratio = unc / payloads[0]
            print(f"- wire: {payloads[0]/2**20:.2f} MiB eq-AR payload vs "
                  f"{unc/2**20:.1f} MiB uncompressed ({ratio:.1f}x)")
            summary[model]["wire_ratio_vs_uncompressed"] = round(ratio, 2)
            if ratio < 8.0:
                ok = False
                print(f"FAIL: compressed wire ratio {ratio:.1f}x below "
                      "the 8x target")
        if model.endswith("-powersgd"):
            pr = _spawn(model, min(args.ns), parity=True)
            print(f"- convergence proxy ({pr['steps']} steps, tiny CNN, "
                  f"n={pr['n']}): loss {pr['final_loss_compressed']} "
                  f"EF-compressed vs {pr['final_loss_uncompressed']} "
                  f"uncompressed (ratio {pr['ratio']}, bound "
                  f"{PARITY_BOUND})")
            summary[model]["parity"] = pr
            if not (pr["ratio"] <= PARITY_BOUND
                    and pr["final_loss_compressed"] < pr["loss_first"]):
                ok = False
                print(f"FAIL: EF convergence proxy outside bound "
                      f"({pr})")

        if model in MEASURED_STEP_SECONDS:
            step_s = MEASURED_STEP_SECONDS[model]
            print(f"\n### {model}: predicted scaling efficiency "
                  f"(measured step {step_s*1e3:.1f} ms/chip)")
            print("| chips | t_comm (v5e) | eff v5e no-ovl | eff v5e "
                  "full-ovl | eff v5p no-ovl | eff v5p full-ovl |")
            print("|---|---|---|---|---|---|")
            curve_e = scaling.predict_efficiency(step_s, payloads[0],
                                                 scaling.V5E)
            curve_p = scaling.predict_efficiency(step_s, payloads[0],
                                                 scaling.V5P)
            for pe, pp in zip(curve_e, curve_p):
                print(f"| {pe.n} | {pe.comm_seconds*1e3:.2f} ms "
                      f"| {pe.eff_no_overlap:.1%} "
                      f"| {pe.eff_full_overlap:.1%} "
                      f"| {pp.eff_no_overlap:.1%} "
                      f"| {pp.eff_full_overlap:.1%} |")
            e256 = [p for p in curve_e if p.n == 256][0]
            summary[model]["eff_256_v5e"] = [
                round(e256.eff_no_overlap, 4),
                round(e256.eff_full_overlap, 4)]
            e128 = [p for p in curve_e if p.n == 128][0]
            summary[model]["eff_128_v5e"] = [
                round(e128.eff_no_overlap, 4),
                round(e128.eff_full_overlap, 4)]

    print()
    print(json.dumps({"metric": "scaling_evidence", "ok": ok,
                      "models": summary}), flush=True)
    if args.out:
        hier_models = [m for m in summary if m.endswith("-hier")]
        if hier_models:
            _write_hier_round(args, summary[hier_models[0]], ok)
        elif "bert-3d" in summary:
            _write_3d_round(args, summary["bert-3d"], ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
