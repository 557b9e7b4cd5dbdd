"""NHWC-vs-NCHW probe for ResNet-50's backward convolutions, on the chip.

The per-op roofline (``rn50_op_roofline.py``, on an earlier runtime)
measured the backward pass at 3.0x the forward's wall
time with only 2x its FLOPs; round 2 INFERRED the dgrad/wgrad convs ran
~1.5x slower per FLOP (this probe and ``rn50_bwd_roofline.py`` later
showed the kernels are in fact near peak and the gap is HBM-bound glue).
The one layout knob in user hands is the MODEL's data layout; this probe
answers, by measurement: would an NCHW ResNet be faster?  (Measured
answer on an earlier runtime, July-August 2026: no -- NCHW loses on
backward.)

Method: for each stride-1 SAME 3x3 conv shape in RN50 (where the FLOPs
live; Cin==Cout so cotangents chain shape-stably), time forward, dgrad
(``jax.vjp`` w.r.t. the input -- exactly the transposed conv the train
step's backward runs), and wgrad (vjp w.r.t. the kernel) in BOTH
layouts, with the differential scan-chain method (fixed dispatch
overhead and jitter cancel in the slope between a K1- and K2-iteration
program; every output is consumed through a non-linear full-tensor tap
so XLA can neither dead-code nor algebraically collapse the chain --
see the verify skill notes).

Usage::

    python examples/conv_layout_probe.py [--batch 256] [--iters 8]
        [--configs 3]
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root
_sys.path.insert(0, _dir(_abs(__file__)))        # examples/ (_harness)

import argparse

V5E_BF16_PEAK = 197e12

# RN50's stride-1 SAME 3x3 bottleneck convs (NHWC shapes at batch B).
CONFIGS = [
    # (H=W, C) -- one per stage, FLOP-heaviest first.
    (56, 64),
    (28, 128),
    (14, 256),
    (7, 512),
]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--configs", type=int, default=3,
                   help="how many of the stage shapes to probe")
    p.add_argument("--start", type=int, default=0,
                   help="first stage shape index (run one per process: "
                        "each shape costs ~12 compiles)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from _harness import differential_bench as bench, nonlinear_tap as tap

    results = []
    for hw, c in CONFIGS[args.start:args.start + args.configs]:
        flops = 2 * args.batch * hw * hw * c * 3 * 3 * c
        for layout in ("NHWC", "NCHW"):
            if layout == "NHWC":
                dn = ("NHWC", "HWIO", "NHWC")
                xs = (args.batch, hw, hw, c)
                ws = (3, 3, c, c)
            else:
                dn = ("NCHW", "OIHW", "NCHW")
                xs = (args.batch, c, hw, hw)
                ws = (c, c, 3, 3)
            key = jax.random.PRNGKey(0)
            x0 = jax.random.normal(key, xs, jnp.bfloat16)
            w0 = jax.random.normal(key, ws, jnp.bfloat16) * 0.01

            def conv(xi, wi):
                return lax.conv_general_dilated(
                    xi, wi, window_strides=(1, 1), padding="SAME",
                    dimension_numbers=dn)

            def fwd_body():
                def body(carry, _):
                    return tap(carry, conv(carry, w0))
                return body

            def dgrad_body():
                # carry is the cotangent; its vjp output (x_bar) has the
                # same shape (stride-1 SAME, Cin==Cout), so it chains.
                def body(carry, _):
                    _y, vjp = jax.vjp(lambda xi: conv(xi, w0), x0)
                    (xbar,) = vjp(carry)
                    return tap(carry, xbar)
                return body

            def wgrad_body():
                def body(carry, _):
                    _y, vjp = jax.vjp(lambda wi: conv(x0, wi), w0)
                    (wbar,) = vjp(carry)
                    return tap(carry, wbar)
                return body

            row = {"shape": f"{hw}x{hw}x{c}", "layout": layout}
            for name, mk in (("fwd", fwd_body), ("dgrad", dgrad_body),
                             ("wgrad", wgrad_body)):
                secs, ok = bench(mk, x0, args.iters)
                tf = flops / secs / 1e12
                ok = ok and tf * 1e12 <= 1.05 * V5E_BF16_PEAK
                row[name] = (secs * 1e3, tf, ok)
                print(f"{row['shape']:>12} {layout} {name:>5}: "
                      f"{secs*1e3:7.3f} ms  {tf:6.1f} TFLOP/s "
                      f"({tf/ (V5E_BF16_PEAK/1e12) :5.1%} peak)"
                      f"{'' if ok else '  [low signal]'}", flush=True)
            results.append(row)

    # Summary: per-shape NCHW/NHWC speedup per direction.
    print("\n| shape | dir | NHWC ms | NCHW ms | NCHW speedup |")
    print("|---|---|---|---|---|")
    by_shape = {}
    for r in results:
        by_shape.setdefault(r["shape"], {})[r["layout"]] = r
    for shape, d in by_shape.items():
        if len(d) != 2:
            continue
        for name in ("fwd", "dgrad", "wgrad"):
            a, b = d["NHWC"][name], d["NCHW"][name]
            note = "" if (a[2] and b[2]) else " (low signal)"
            print(f"| {shape} | {name} | {a[0]:.3f} | {b[0]:.3f} "
                  f"| {a[0]/b[0]:.2f}x{note} |")
    return 0


if __name__ == "__main__":
    _sys.exit(main())
