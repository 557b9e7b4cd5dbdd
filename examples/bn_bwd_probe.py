"""BN(+relu+residual) BACKWARD glue: measured XLA cost vs the HBM floor.

An earlier runtime's per-op account (``rn50_bwd_roofline.py``; not
reproduced) attributed ~45 ms of the 60.7 ms ResNet-50 backward to
HBM-bound BN/relu/residual backward chains and left one lever untried:
a fused Pallas kernel reading each
activation + cotangent once per pass.  Before writing that kernel, this
probe establishes whether there is anything left to win: for each hot
BN site it differential-times (``_harness.differential_bench``) the
exact backward chain XLA compiles for

    out = relu(batch_norm_train(x) * gamma + beta + shortcut)

and compares against the two-pass exact-algorithm floor:

    pass 1 (reductions): read x, dy, out          -> 3N bytes
    pass 2 (apply):      read x, dy, out, write dx -> 4N bytes

(7N total at the tensor's dtype; the per-channel scalars are noise).
A measured/floor ratio near 1 REFUTES the kernel idea mechanically --
XLA is already at the memory roof; a large ratio is the case for Pallas.

Usage::

    python examples/bn_bwd_probe.py [--batch 256] [--shapes 56x64 28x512]
        [--kernel]   # time the Pallas two-pass kernels instead of XLA
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))
_sys.path.insert(0, _dir(_abs(__file__)))

import argparse
import time  # noqa: F401  (harness import side effects)

V5E_HBM = 819e9


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--shapes", nargs="+",
                   default=["56x64", "56x256", "28x128", "28x512"],
                   help="HxC sites (RN50 stage-2/3 hot shapes)")
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--spread", type=int, default=256,
                   help="scan-length spread; raise for sub-0.3ms ops so "
                        "the slope clears the dispatch jitter")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--kernel", action="store_true",
                   help="route the BN backward through the Pallas "
                        "two-pass kernels (ops.bn.bn_train, "
                        "HOROVOD_PALLAS_BN=1) instead of XLA's compiled "
                        "chain -- the direct A/B for the round-5 "
                        "refutation")
    args = p.parse_args()

    if args.kernel:
        import os
        os.environ["HOROVOD_PALLAS_BN"] = "1"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from _harness import differential_bench, nonlinear_tap
    from horovod_tpu.ops import bn as _bn

    dt = jnp.dtype(args.dtype)
    print(f"# devices: {jax.devices()}"
          + (" | BN backward: Pallas kernels" if args.kernel else ""))
    print("| shape | fwd ms | fwd+bwd ms | bwd ms | floor ms | "
          "bwd/floor |")
    print("|---|---|---|---|---|---|")

    total_bwd = total_floor = 0.0
    for spec in args.shapes:
        side, ch = (int(v) for v in spec.split("x"))
        shape = (args.batch, side, side, ch)
        key = jax.random.PRNGKey(0)
        x0 = jax.random.normal(key, shape, dt)
        sc = jax.random.normal(jax.random.PRNGKey(1), shape, dt)
        dy = jax.random.normal(jax.random.PRNGKey(2), shape, dt)
        gamma = jnp.ones((ch,), jnp.float32)
        beta = jnp.zeros((ch,), jnp.float32)

        def block(x, shortcut, g, b):
            if args.kernel:
                y = _bn.bn_train(x, g, b, 1e-5) + shortcut
            else:
                x32 = x.astype(jnp.float32)
                mean = jnp.mean(x32, axis=(0, 1, 2))
                var = jnp.var(x32, axis=(0, 1, 2))
                xhat = (x32 - mean) / jnp.sqrt(var + 1e-5)
                y = (xhat * g + b).astype(x.dtype) + shortcut
            return jax.nn.relu(y)

        # sc/dy ride in the CARRY, not as closures: closed-over arrays
        # embed as HLO constants (411 MB of them at the 56x256 site).
        def make_fwd():
            def body(carry, _):
                x, sc_, dy_ = carry
                out = block(x, sc_, gamma, beta)
                x2, s = nonlinear_tap(x, out)
                return (x2, sc_, dy_), s
            return body

        def make_fwdbwd():
            def body(carry, _):
                x, sc_, dy_ = carry
                out, vjp = jax.vjp(block, x, sc_, gamma, beta)
                dx, dsc, dg, db = vjp(dy_)
                x2, s1 = nonlinear_tap(x, dx)
                x2, s2 = nonlinear_tap(x2, dsc)
                return (x2, sc_, dy_), s1 + s2
            return body

        carry0 = (x0, sc, dy)
        f_s, f_ok = differential_bench(make_fwd, carry0, args.iters,
                                       k_spread=args.spread)
        fb_s, fb_ok = differential_bench(make_fwdbwd, carry0, args.iters,
                                         k_spread=args.spread)
        bwd = max(fb_s - f_s, 1e-9)
        nbytes = int(np.prod(shape)) * dt.itemsize
        floor = 7 * nbytes / V5E_HBM
        tag = "" if (f_ok and fb_ok) else " (low signal)"
        print(f"| {shape} | {f_s*1e3:.3f} | {fb_s*1e3:.3f} "
              f"| {bwd*1e3:.3f} | {floor*1e3:.3f} "
              f"| {bwd/floor:.2f}x{tag} |", flush=True)
        total_bwd += bwd
        total_floor += floor
    print(f"\ntotals: bwd {total_bwd*1e3:.2f} ms vs floor "
          f"{total_floor*1e3:.2f} ms ({total_bwd/total_floor:.2f}x)")
    return 0


if __name__ == "__main__":
    _sys.exit(main())
