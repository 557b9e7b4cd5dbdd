"""Per-op roofline for the ResNet-50 BACKWARD pass, on the chip.

``rn50_op_roofline.py`` measured the forward convs at 66-85% of peak but
the whole backward at ~17.5% MFU (3.0x the forward's wall time on 2x the
FLOPs), and ``conv_layout_probe.py`` showed the stride-1 3x3 backward
convs run near peak in isolation -- so the sink is NOT those kernels.
This probe closes the account: it harvests every convolution the
backward jaxpr ACTUALLY contains -- dgrads appear as input-dilated
(``lhs_dilation > 1``) convs for strided layers, wgrads as
batch-contracting convs -- and times each in isolation with the
differential scan-chain method.

For a dilated conv two FLOP numbers differ: "naive" counts every MAC of
the lowered op (zeros included -- what the MXU executes if the lowering
cannot skip the inserted zeros), "effective" divides by
``prod(lhs_dilation)`` (the useful work, equal to the forward conv's
FLOPs).  A config running at high naive but low effective rate is
multiplying zeros -- the classic strided-dgrad tax.

Usage::

    python examples/rn50_bwd_roofline.py [--batch 256] [--cap 10]
        [--start 0]
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root
_sys.path.insert(0, _dir(_abs(__file__)))        # examples/ (_harness)

import argparse

V5E_BF16_PEAK = 197e12
V5E_HBM_GBPS = 819e9


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--cap", type=int, default=10)
    p.add_argument("--start", type=int, default=0,
                   help="skip the first N configs (resume across runs: "
                        "each config costs ~2 compiles)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from horovod_tpu.models import ResNet50

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     space_to_depth=True)
    x = jnp.ones((args.batch, args.image_size, args.image_size, 3),
                 jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0),
                           x[:2].astype(jnp.float32), train=False)

    def loss_of(p, xb):
        logits = model.apply({"params": p,
                              "batch_stats": variables["batch_stats"]},
                             xb, train=False)
        l32 = logits.astype(jnp.float32)
        return jnp.sum(l32 * l32) * 1e-6

    jaxpr = jax.make_jaxpr(jax.grad(loss_of))(variables["params"], x)

    convs = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "conv_general_dilated":
                lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
                out = eqn.outvars[0].aval
                prm = eqn.params
                convs.append((
                    tuple(lhs.shape), str(lhs.dtype),
                    tuple(rhs.shape), str(rhs.dtype),
                    tuple(out.shape),
                    tuple(prm["window_strides"]),
                    tuple(map(tuple, prm["padding"])),
                    tuple(prm["lhs_dilation"]),
                    tuple(prm["rhs_dilation"]),
                    prm["dimension_numbers"],
                    prm["feature_group_count"],
                    prm["batch_group_count"],
                ))
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    walk(getattr(inner, "jaxpr", inner))
    walk(jaxpr.jaxpr)

    def naive_flops(cfg):
        (lhs_s, _lt, rhs_s, _rt, out_s, _st, _pad, _ld, _rd, dn,
         fg, _bg) = cfg
        # MACs of the lowered op: every output element contracts the
        # full (possibly dilated) kernel window.
        out_spatial = [out_s[i] for i in dn.out_spec[2:]]
        cout = out_s[dn.out_spec[1]]
        nb = out_s[dn.out_spec[0]]
        k_spatial = [rhs_s[i] for i in dn.rhs_spec[2:]]
        # rhs's in-feature dim is already per-group, so no fg factor.
        cin_per_group = rhs_s[dn.rhs_spec[1]]
        return (2 * nb * int(np.prod(out_spatial)) * cout
                * int(np.prod(k_spatial)) * cin_per_group)

    from collections import Counter
    counts = Counter(convs)
    uniq = sorted(counts, key=lambda c: -naive_flops(c) * counts[c])
    total_fl = sum(naive_flops(c) * counts[c] for c in uniq)
    print(f"# backward jaxpr: {len(convs)} convs, {len(uniq)} distinct, "
          f"{total_fl/1e9:.1f} naive GFLOP total", file=_sys.stderr)

    from _harness import differential_bench, nonlinear_tap

    def bench(cfg, iters):
        (lhs_s, lt, rhs_s, rt, _out, strides, padding, ld, rd, dn,
         fg, bg) = cfg
        key = jax.random.PRNGKey(1)
        xb = jax.random.normal(key, lhs_s, jnp.dtype(lt))
        w = (jax.random.normal(key, rhs_s, jnp.dtype(rt)) * 0.01)

        def make_body():
            def body(carry, _):
                y = lax.conv_general_dilated(
                    carry, w, window_strides=strides,
                    padding=list(padding), lhs_dilation=ld,
                    rhs_dilation=rd, dimension_numbers=dn,
                    feature_group_count=fg, batch_group_count=bg)
                return nonlinear_tap(carry, y)
            return body

        return differential_bench(make_body, xb, iters)

    sel = uniq[args.start:args.start + args.cap]
    skipped_fl = total_fl - sum(naive_flops(c) * counts[c] for c in sel)
    print("| lhs x rhs | strides | lhs_dil | n | ms/op | naive TFLOP/s | "
          "eff TFLOP/s | % peak (eff) |")
    print("|---|---|---|---|---|---|---|---|")
    total_time = 0.0
    low_signal_n = 0
    for cfg in sel:
        (lhs_s, _lt, rhs_s, _rt, _o, strides, _pad, ld, _rd, _dn,
         _fg, _bg) = cfg
        secs, ok = bench(cfg, args.iters)
        nf = naive_flops(cfg)
        ef = nf / int(np.prod(ld))
        n = counts[cfg]
        naive_tf = nf / secs / 1e12
        eff_tf = ef / secs / 1e12
        # Naive rate legitimately exceeds peak for dilated convs (XLA
        # skips the inserted zeros); only the EFFECTIVE rate is bounded
        # by physics, so the above-peak sanity cap applies to it.
        ok = ok and eff_tf * 1e12 <= 1.05 * V5E_BF16_PEAK
        if ok:
            total_time += secs * n
        else:
            low_signal_n += n
        tag = "" if ok else " (low signal)"
        print(f"| {lhs_s} x {rhs_s} | s{strides} | {ld} | {n} "
              f"| {secs*1e3:.3f} | {naive_tf:.1f} | {eff_tf:.1f} "
              f"| {eff_tf*1e12/V5E_BF16_PEAK:.0%}{tag} |", flush=True)
    caveat = (f"; {low_signal_n} low-signal convs EXCLUDED from the sum"
              if low_signal_n else "")
    print(f"\nselected configs sum (reliable rows only): "
          f"{total_time*1e3:.1f} ms/backward{caveat} "
          f"(skipped tail: {skipped_fl/1e9:.1f} naive GFLOP)")
    return 0


if __name__ == "__main__":
    _sys.exit(main())
