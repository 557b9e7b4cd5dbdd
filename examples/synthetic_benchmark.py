"""Synthetic-data throughput benchmark (reference
``examples/*_synthetic_benchmark.py`` / ``tf_cnn_benchmarks`` recipe,
SURVEY.md section 6).

Measures images/sec for any model-zoo network with synthetic device-
resident data through the full framework path (DistributedOptimizer fused
allreduce, bf16 compute, BN stat sync)::

    python examples/synthetic_benchmark.py --model resnet50
    python examples/synthetic_benchmark.py --model vgg16 --cpu-devices 8 \
        --image-size 32 --batch-size 8 --num-iters 3
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root importable

import argparse
import time


MODELS = ("lenet", "resnet50", "resnet101", "vgg16", "vgg19",
          "inception_v3")


def build_model(name: str, num_classes: int, dtype):
    from horovod_tpu import models as zoo
    if name == "lenet":
        return zoo.LeNet()
    if name == "resnet50":
        return zoo.ResNet50(num_classes=num_classes, dtype=dtype)
    if name == "resnet101":
        return zoo.ResNet101(num_classes=num_classes, dtype=dtype)
    if name == "vgg16":
        return zoo.VGG16(num_classes=num_classes, dropout_rate=0.0,
                         dtype=dtype)
    if name == "vgg19":
        return zoo.VGG19(num_classes=num_classes, dropout_rate=0.0,
                         dtype=dtype)
    if name == "inception_v3":
        return zoo.InceptionV3(num_classes=num_classes, dropout_rate=0.0,
                               dtype=dtype)
    raise SystemExit(f"unknown model {name!r}; choose from {MODELS}")


def default_image_size(name: str) -> int:
    return {"lenet": 28, "inception_v3": 299}.get(name, 224)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50", choices=MODELS)
    ap.add_argument("--batch-size", type=int, default=32,
                    help="per-chip batch size")
    ap.add_argument("--image-size", type=int, default=None)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--num-iters", type=int, default=10,
                    help="timed batches per measurement")
    ap.add_argument("--num-warmup", type=int, default=3)
    ap.add_argument("--fp32", action="store_true",
                    help="float32 compute instead of bfloat16")
    ap.add_argument("--compression", default="none",
                    choices=["none", "fp16", "bf16", "fp8"],
                    help="gradient wire codec for the fused allreduce")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force an N-device XLA:CPU mesh (testing)")
    args = ap.parse_args()

    if args.cpu_devices:
        from horovod_tpu.utils.platform import force_host_device_count
        force_host_device_count(args.cpu_devices, cpu=True, exact=True)

    import jax
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.training import make_flax_train_step

    hvd.init()
    n = hvd.size()
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    size = args.image_size or default_image_size(args.model)
    chans = 1 if args.model == "lenet" else 3
    model = build_model(args.model, args.num_classes, dtype)

    global_batch = args.batch_size * n
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (global_batch, size, size, chans), dtype)
    y = jax.random.randint(key, (global_batch,), 0, args.num_classes,
                           jnp.int32)
    variables = model.init(key, x[:2].astype(jnp.float32), train=True)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})

    opt = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9),
        compression=getattr(hvd.Compression, args.compression))
    params = hvd.replicate(params)
    batch_stats = hvd.replicate(batch_stats)
    opt_state = hvd.replicate(opt.init(params))
    step = make_flax_train_step(model.apply, opt)
    batch = hvd.shard_batch((x, y))

    if hvd.rank() == 0:
        print(f"model: {args.model}  devices: {n}  "
              f"global batch: {global_batch}  image: {size}")

    loss = None
    for _ in range(args.num_warmup):
        params, batch_stats, opt_state, loss = step(params, batch_stats,
                                                    opt_state, batch)
    if loss is not None:
        float(loss)  # device->host fetch: fences the warm-up

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        params, batch_stats, opt_state, loss = step(params, batch_stats,
                                                    opt_state, batch)
    float(loss)
    dt = time.perf_counter() - t0
    ips = args.num_iters * global_batch / dt
    if hvd.rank() == 0:
        print(f"{args.num_iters} iters in {dt:.2f}s -> "
              f"{ips:.1f} images/s total, {ips / n:.1f} images/s/chip")
    return 0


if __name__ == "__main__":
    sys_exit = main()
    raise SystemExit(sys_exit)
