"""One flash-attention forward call at a served prefill's shape, timed on
the device's clock.

The blocked prefill kernels (``hvd_flash_fwd``, ``hvd_flash_swa_fwd``)
are a few calls of 2-20 ms inside a ``jit__prefill`` program that also
holds a prompt's matmuls; the serving cells see them only through a
traced window.  This probe runs one kernel alone at the shape a cell
gives it, under ``jax.profiler``, and reads the call's events off the
``XLA Ops`` line: milliseconds a call, microseconds a live block and the
share of the chip's bfloat16 peak the block's two products reach.  To
compare two trees, copy this file into the other tree's ``examples/``
and run both in one chip call (a chip belongs to one process at a time).

Usage::

    python examples/flash_prefill_probe.py [--out FILE.json]
        [--shapes exaone_full exaone_window joyai mistral_1024]
        [--calls 10] [--dtype bfloat16]

TPU only: a time from the Pallas interpreter is no reading.
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))

import argparse
import json
import os
import tempfile

# name: (query heads, key heads, tokens, head width, window[, value
# width]).  The first three are what an 8,192-token prompt hands the
# kernel in ``k_exaone_236b_mixed_offline`` (a full layer, a window layer)
# and ``joyai_llm_flash_offline_docs`` (values 128 wide beside keys of
# 192); ``joyai_padded`` is what that cell handed it before PR 49 (the
# values padded with zeros to the keys' 192: the one of the two a tree
# older than PR 49 can run); the last is Mistral's longest prompt.
SHAPES = {
    "exaone_full": (64, 8, 8192, 128, None),
    "exaone_window": (64, 8, 8192, 128, 128),
    "joyai": (32, 32, 8192, 192, None, 128),
    "joyai_padded": (32, 32, 8192, 192, None),
    "mistral_1024": (32, 8, 1024, 128, None),
}


def live_blocks(t: int, block: int, window) -> int:
    """Key blocks a head's grid runs the step's body for."""
    n = t // block
    if window is None:
        return n * (n + 1) // 2
    return sum(i - max(i * block - window + 1, 0) // block + 1
               for i in range(n))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--shapes", nargs="+", default=list(SHAPES))
    p.add_argument("--calls", type=int, default=10)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import peaks, xplane
    from horovod_tpu.ops import attention as attn

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("flash_prefill_probe: needs a TPU, found "
                         f"{device.platform}")
    peak = peaks.peaks_for(device.device_kind)["bf16_flops_per_s"]
    dtype = jnp.dtype(args.dtype)
    report = {"device_kind": device.device_kind, "dtype": str(dtype),
              "tree": _dir(_dir(_abs(__file__))), "shapes": {}}
    for name in args.shapes:
        heads, kv_heads, t, d, window, *dv = SHAPES[name]
        dv = dv[0] if dv else d
        keys = jax.random.split(jax.random.PRNGKey(len(name) + t), 3)
        q = jax.random.normal(keys[0], (1, heads, t, d), dtype)
        k = jax.random.normal(keys[1], (1, kv_heads, t, d), dtype)
        v = jax.random.normal(keys[2], (1, kv_heads, t, dv), dtype)
        fn = jax.jit(lambda q, k, v, w=window: attn.flash_attention(
            q, k, v, causal=True, window=w))
        out = jax.block_until_ready(fn(q, k, v))
        # Against the float32 reference on the first eight heads' last
        # 1,024 queries (the whole score matrix of 64 heads is 17 GB).
        hq = min(heads, 8)
        rep = heads // kv_heads
        with jax.default_matmul_precision("highest"):
            ref = attn.attention_reference(
                q[:, :hq, -1024:].astype(jnp.float32),
                jnp.repeat(k[:, :max(hq // rep, 1)], rep, 1)[:, :hq].astype(
                    jnp.float32),
                jnp.repeat(v[:, :max(hq // rep, 1)], rep, 1)[:, :hq].astype(
                    jnp.float32),
                causal=True, window=window)
        err = float(jnp.max(jnp.abs(
            out[:, :hq, -1024:].astype(jnp.float32) - ref)))
        # Values narrower than the keys: bit for bit the kept columns of
        # the call with the values padded to the keys' width.
        same = None if dv == d else bool(jnp.array_equal(out, fn(
            q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, d - dv),)))[..., :dv]))
        kernel = "hvd_flash_fwd" if window is None else "hvd_flash_swa_fwd"
        with tempfile.TemporaryDirectory() as logdir:
            jax.profiler.start_trace(logdir)
            for _ in range(args.calls):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            jax.profiler.stop_trace()
            ops = xplane.load_trace(xplane.find_xplane(logdir)).devices[0].ops
        events = [e.dur_ns for e in ops if e.name.startswith("%" + kernel)]
        if len(events) != args.calls:
            raise SystemExit(f"{name}: {len(events)} {kernel} events on the "
                             f"ops line, {args.calls} calls made")
        # XLA's own ops beside the kernel: the copies of operands into
        # the call's layout.
        beside = sum(e.dur_ns for e in ops
                     if not e.name.startswith("%" + kernel)) / args.calls
        block = attn._block(t, attn.DEFAULT_BLOCK_Q)
        blocks = heads * live_blocks(t, block, window)
        ms = float(np.median(events)) / 1e6
        flop = 2.0 * block * block * (d + dv)
        report["shapes"][name] = {
            "kernel": kernel, "q": [1, heads, t, d], "kv_heads": kv_heads,
            "value_width": dv, "equal_to_padded_call": same,
            "window": window, "calls": len(events),
            "ms_median": ms, "ms_min": min(events) / 1e6,
            "ms_max": max(events) / 1e6,
            "ms_other_ops_a_call": beside / 1e6, "live_blocks": blocks,
            "us_per_block": ms * 1e3 / blocks,
            "block_mxu_peak_pct": 100 * flop * blocks / (ms / 1e3) / peak,
            "max_abs_err_vs_f32_reference": err}
        print(f"{name:14s} {kernel:18s} {ms:8.3f} ms a call "
              f"({min(events) / 1e6:.3f}-{max(events) / 1e6:.3f}), "
              f"{blocks} live blocks of {block}, "
              f"{ms * 1e3 / blocks:.3f} us a block, "
              f"{report['shapes'][name]['block_mxu_peak_pct']:.1f}% of the "
              f"bfloat16 peak, {beside / 1e6:.3f} ms of other ops a call, "
              f"|err| {err:.4f}"
              + ("" if same is None else
                 f", equal to the padded call bit for bit: {same}"),
              flush=True)
    if args.out:
        os.makedirs(_dir(_abs(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    _sys.exit(main())
