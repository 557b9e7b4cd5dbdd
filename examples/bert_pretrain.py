"""BERT pretraining (MLM + NSP) with Adasum + fp16 gradient compression.

BASELINE.json config: "BERT-Large pretrain (Adasum + fp16 grad compression)".
Synthetic-data benchmark in the style of the reference's
``*_synthetic_benchmark.py`` examples: fixed random token batches resident
on device, full fwd+bwd+update through the framework path per step.

Run (tiny config by default; --large for real BERT-Large)::

    python examples/bert_pretrain.py [--steps 30] [--cpu-devices 8] [--large]
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root importable

import argparse

from _harness import require_tpu, setup_devices, timed_training


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch-size", type=int, default=0,
                   help="global batch (default: 4 per device)")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--large", action="store_true",
                   help="real BERT-Large (needs TPU HBM)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize blocks (long-seq memory trade)")
    p.add_argument("--compression", default="fp16",
                   help="gradient wire codec(s): none/fp16/bf16/fp8, or a "
                        "comma list (e.g. fp16,fp8) benched back-to-back "
                        "IN ONE PROCESS, so the comparison shares one "
                        "compile cache, one chip and one warm-up")
    p.add_argument("--tp", type=int, default=0,
                   help="tensor-parallel extent: train 3D (DP x TP) on a "
                        "build_3d_mesh, Megatron-split encoder via "
                        "bert_tp_apply; params + Adam moments shard over "
                        "tp, so configs pure-DP cannot hold fit (see the "
                        "printed HBM report)")
    p.add_argument("--save-checkpoint", default="",
                   help="save the final params to this npz path (the 3D "
                        "step reassembles FULL kernels, so the file loads "
                        "straight into the serving plane)")
    p.add_argument("--cpu-devices", type=int, default=0)
    args = p.parse_args()

    setup_devices(args.cpu_devices)
    if args.large:
        require_tpu("--large", args.cpu_devices)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import BERT_LARGE, BERT_TINY, Bert

    if args.tp > 1:
        return main_3d(args)

    hvd.init()
    cfg = BERT_LARGE if args.large else BERT_TINY
    dtype = jnp.bfloat16 if jax.devices()[0].platform == "tpu" \
        else jnp.float32
    model = Bert(cfg, dtype=dtype, remat=args.remat)
    batch = args.batch_size or 4 * hvd.size()
    seq = min(args.seq_len, cfg.max_seq_len)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    nsp_labels = jnp.asarray(rng.randint(0, 2, (batch,)))

    params = model.init(jax.random.PRNGKey(0), tokens[:1])
    if hvd.rank() == 0:
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"devices={hvd.size()} params={n/1e6:.1f}M "
              f"batch={batch} seq={seq}")

    params = hvd.replicate(params)
    data = hvd.shard_batch((tokens, nsp_labels))

    def loss_fn(p, batch):
        toks, nsp_y = batch
        mlm, nsp = model.apply(p, toks)
        # Synthetic MLM objective: predict the token identity itself
        # (benchmark proxy -- real masking needs a corpus).
        l_mlm = optax.softmax_cross_entropy_with_integer_labels(
            mlm, toks).mean()
        l_nsp = optax.softmax_cross_entropy_with_integer_labels(
            nsp, nsp_y).mean()
        return l_mlm + l_nsp

    # The headline knobs for this workload: Adasum reduction + wire
    # compression (hvd.Adasum / Compression.fp16 parity; fp8 swaps in
    # the e4m3 exchange codec -- per-piece quantized VHDD permutes).
    codecs = [c.strip() for c in args.compression.split(",")]
    for codec in codecs:
        if hvd.rank() == 0 and len(codecs) > 1:
            print(f"--- codec: {codec}", flush=True)
        opt = hvd.DistributedAdasumOptimizer(
            optax.adamw(args.lr),
            compression=getattr(hvd.Compression, codec))
        # Donation consumes the params buffers (the benchmarked config);
        # copy only while another codec still needs the pristine tree.
        p = jax.tree.map(jnp.copy, params) \
            if codec is not codecs[-1] else params
        opt_state = opt.init(p)
        step = hvd.make_train_step(loss_fn, opt)
        p, _ = timed_training(step, p, opt_state, data, args.steps,
                              hvd.rank(), items_per_step=batch)
    if args.save_checkpoint and hvd.rank() == 0:
        from horovod_tpu.utils.checkpoint import save_checkpoint
        save_checkpoint(args.save_checkpoint, p)
        print(f"saved {args.save_checkpoint}")
    hvd.shutdown()


def main_3d(args):
    """DP x TP over one ``build_3d_mesh``: the PR 18 proof workload.

    The Megatron-split encoder (``models.bert_tp_apply``) shards every
    attention/FFN kernel and its Adam moments over the ``model`` axis
    while the fp16 gradient exchange, built over the DATA axes only,
    rides the two-level ICI x DCN decomposition whenever the data extent
    splits across slices.  The HBM report prints the per-device params +
    opt-state residency both ways: at BERT-Large scale pure-DP must hold
    the full ~1.3 GiB of fp32 params plus two Adam moments per device,
    where the tp-sharded step holds 1/tp of every kernel -- the configs
    this example exists to fit.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import BERT_LARGE, BERT_TINY, Bert, \
        bert_tp_apply
    from horovod_tpu.parallel import build_3d_mesh, data_axes, \
        tp_param_specs

    ndev = len(jax.devices())
    tp = args.tp
    if ndev % tp:
        raise SystemExit(f"--tp {tp} does not divide {ndev} devices")
    data = ndev // tp
    dcn = 2 if data % 2 == 0 and data >= 4 else 1
    mesh = build_3d_mesh(jax.devices(), data=data // dcn, model=tp,
                         dcn_size=dcn)
    hvd.init(mesh=mesh)
    cfg = BERT_LARGE if args.large else BERT_TINY
    model = Bert(cfg, dtype=jnp.float32)
    batch = args.batch_size or 4 * data
    seq = min(args.seq_len, cfg.max_seq_len)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    nsp_labels = jnp.asarray(rng.randint(0, 2, (batch,)))
    params = model.init(jax.random.PRNGKey(0), tokens[:1])
    specs = tp_param_specs(params, axis="model")

    # HBM report: params + Adam moments per device, pure-DP (everything
    # replicated) vs the 3D layout (tp-sharded kernels).
    from jax.sharding import PartitionSpec
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    leaves = jax.tree.leaves(params)
    full = sum(x.size * x.dtype.itemsize for x in leaves)
    local = sum(
        x.size * x.dtype.itemsize // (tp if any(s) else 1)
        for x, s in zip(leaves, spec_leaves))
    if hvd.rank() == 0:
        n = sum(x.size for x in leaves)
        print(f"devices={ndev} mesh=dcn{dcn} x (data{data // dcn}, "
              f"model{tp}) params={n / 1e6:.1f}M batch={batch} seq={seq}")
        print(f"HBM/device (params + 2 Adam moments): pure-DP "
              f"{3 * full / 2**20:.1f} MiB vs 3D {3 * local / 2**20:.1f} "
              f"MiB ({full / local:.2f}x)")

    def loss_fn(p, b):
        toks, nsp_y = b
        mlm, nsp = bert_tp_apply(p, cfg, toks, axis="model")
        l_mlm = optax.softmax_cross_entropy_with_integer_labels(
            mlm, toks).mean()
        l_nsp = optax.softmax_cross_entropy_with_integer_labels(
            nsp, nsp_y).mean()
        return l_mlm + l_nsp

    opt = hvd.DistributedOptimizer(
        optax.adamw(args.lr),
        compression=getattr(hvd.Compression,
                            args.compression.split(",")[0].strip()),
        axes=data_axes(mesh))
    oss = hvd.mirror_opt_state_specs(opt, params, specs)
    step = hvd.make_train_step(loss_fn, opt, mesh=mesh, tp=tp,
                               param_specs=specs, opt_state_specs=oss)
    opt_state = opt.init(params)
    data_dev = hvd.shard_batch((tokens, nsp_labels))
    params, _ = timed_training(step, params, opt_state, data_dev,
                               args.steps, hvd.rank(),
                               items_per_step=batch)
    if args.save_checkpoint and hvd.rank() == 0:
        from horovod_tpu.utils.checkpoint import save_checkpoint
        save_checkpoint(args.save_checkpoint, params)
        print(f"saved {args.save_checkpoint} (full kernels, "
              "serving-loadable)")
    hvd.shutdown()


if __name__ == "__main__":
    main()
