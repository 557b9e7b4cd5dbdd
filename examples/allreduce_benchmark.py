"""Allreduce bandwidth benchmark (the BASELINE metric's second half).

Sweeps payload sizes through the IN-STEP collective path (a jitted
shard_map psum chain over the mesh -- the gradient hot path), reporting
algorithm bandwidth (payload/time) and the ring bus-bandwidth bound
``2 (n-1)/n * payload / time`` per chip, the standard NCCL-style
accounting the reference's benchmarks use.

The loop chains ITERS dependent allreduces inside one jit (each
iteration consumes the previous result, so XLA cannot elide or overlap
them away) and the timed region is fenced by a device->host value fetch
of the result.

Run::

    python examples/allreduce_benchmark.py --cpu-devices 8   # CPU mesh
    python examples/allreduce_benchmark.py                   # real chip
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root importable

import argparse
import time

from _harness import setup_devices


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--sizes-mb", default="1,4,16,64",
                   help="comma-separated payload sizes in MiB")
    p.add_argument("--iters", type=int, default=10,
                   help="chained allreduces per timed run")
    p.add_argument("--cpu-devices", type=int, default=0)
    args = p.parse_args()

    setup_devices(args.cpu_devices)
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.collectives import ops as cops

    hvd.init()
    mesh = hvd.mesh()
    n = hvd.size()
    axes = tuple(mesh.axis_names)
    iters = args.iters
    if hvd.rank() == 0:
        print(f"# {n} ranks, mesh {dict(zip(axes, mesh.devices.shape))}, "
              f"{iters} chained allreduces per run")

    def chain(x):
        def body(i, acc):
            # 1/n scale keeps values bounded so bf16/f32 never overflow.
            return cops.allreduce(acc, hvd.Sum, axes=axes) / n
        return jax.lax.fori_loop(0, iters, body, x)

    step = jax.jit(jax.shard_map(chain, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False))

    for mb in [float(s) for s in args.sizes_mb.split(",")]:
        elems = int(mb * (1 << 20) / 4)
        x = hvd.replicate(jnp.ones((elems,), jnp.float32), mesh)
        out = step(x)           # compile + warm
        float(out[0])
        t0 = time.perf_counter()
        out = step(x)
        _ = float(out[0])       # device->host fence
        dt = time.perf_counter() - t0
        per_op = dt / iters
        algo_bw = mb / 1024 / per_op
        bus_bw = 2 * (n - 1) / n * algo_bw
        if hvd.rank() == 0:
            print(f"{mb:8.1f} MiB  {per_op * 1e3:8.2f} ms/op  "
                  f"algo {algo_bw:7.2f} GiB/s  "
                  f"bus>= {bus_bw:7.2f} GiB/s/chip")


if __name__ == "__main__":
    main()
