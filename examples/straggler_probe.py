"""Straggler-attribution demo — catch a deterministically slow rank.

Runs the same small training loop once per virtual rank on a forced
8-device CPU mesh, with the chaos injector's ``slow`` fault stalling
exactly one rank's host thread at one step
(``slow@step=K,rank=R,secs=T``).  Every virtual rank writes its own
clock-anchored timeline JSON and feeds its per-step span summaries into
one :class:`~horovod_tpu.timeline.straggler.StragglerMonitor`; the probe
then runs the same merge the CLI exposes
(``python -m horovod_tpu.timeline --merge <dir>``), prints the merged
straggler/critical-path report, and asserts the monitor attributed the
injected delay to the right rank with a ``dispatch_gap``-dominated step.

Run::

    python examples/straggler_probe.py [--steps 12] [--slow-rank 5]
    python examples/straggler_probe.py --bench-json /tmp/straggler.json
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root importable

import argparse
import json
import os
import tempfile


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--cpu-devices", type=int, default=8,
                   help="virtual mesh size / number of simulated ranks")
    p.add_argument("--slow-rank", type=int, default=5)
    p.add_argument("--slow-step", type=int, default=4)
    p.add_argument("--slow-secs", type=float, default=0.25)
    p.add_argument("--trace-dir", default=None,
                   help="where per-rank timelines land (default: tmp)")
    p.add_argument("--bench-json", default=None,
                   help="also write the attribution (the straggler "
                        "block) as JSON to this path")
    args = p.parse_args()
    world = args.cpu_devices
    assert 0 <= args.slow_rank < world

    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(world, cpu=True, exact=True)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.elastic import chaos
    from horovod_tpu.timeline import Timeline
    from horovod_tpu.timeline import spans
    from horovod_tpu.timeline.__main__ import merge, _print_report
    from horovod_tpu.timeline.straggler import StragglerMonitor

    hvd.init()
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="straggler_")
    os.makedirs(trace_dir, exist_ok=True)
    spec = (f"seed=1;slow@step={args.slow_step},rank={args.slow_rank},"
            f"secs={args.slow_secs}")
    print(f"devices: {hvd.size()} ({jax.devices()[0].platform}), "
          f"chaos spec: {spec}\ntraces -> {trace_dir}")

    monitor = StragglerMonitor(world=world, stall_check_time=0.0)
    rec = spans.recorder()
    rec.add_listener(monitor.observe)

    rng = np.random.RandomState(0)
    init_params = {
        "w1": rng.randn(32, 64).astype(np.float32) * 0.1,
        "b1": np.zeros((64,), np.float32),
        "w2": rng.randn(64, 8).astype(np.float32) * 0.1,
        "b2": np.zeros((8,), np.float32)}

    def loss_fn(pr, batch):
        x, y = batch
        h = jnp.tanh(x @ pr["w1"] + pr["b1"])
        logits = h @ pr["w2"] + pr["b2"]
        return -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits) * jax.nn.one_hot(y, 8), axis=-1))

    # One sequential pass per virtual rank: each gets its own anchored
    # timeline, its own chaos injector (the slow fault only fires when
    # the injector's rank matches the fault's), and a fresh train step
    # so dispatch-gap accounting starts clean.
    for r in range(world):
        tl = Timeline(os.path.join(trace_dir, f"timeline_r{r}.json"),
                      rank=r, hostname=f"vrank{r}")
        rec.configure(rank=r, timeline=tl)
        chaos.reset()
        inj = chaos.install(spec, rank=r, size=world)

        params = hvd.replicate(init_params)
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        opt_state = hvd.replicate(opt.init(jax.device_get(init_params)))
        step = hvd.make_train_step(loss_fn, opt)
        batch_rng = np.random.RandomState(7)  # identical data every rank
        for i in range(1, args.steps + 1):
            x = jnp.asarray(batch_rng.randn(4 * hvd.size(), 32),
                            jnp.float32)
            y = jnp.asarray(batch_rng.randint(0, 8, 4 * hvd.size()),
                            jnp.int32)
            params, opt_state, loss = step(params, opt_state,
                                           hvd.shard_batch((x, y)))
            inj.on_step(i)  # the slow fault stalls HERE, between steps
        tl.close()
        rec.timeline = None
        fired = "slow" in inj.fired_kinds
        print(f"rank {r}: {args.steps} steps, loss {float(loss):.4f}"
              f"{'  <-- chaos slow fired' if fired else ''}")
        assert fired == (r == args.slow_rank), (r, inj.fired_kinds)
    chaos.reset()
    rec.remove_listener(monitor.observe)

    # Live-feed verdict (the monitor saw every rank's summaries).
    live = monitor.report()
    print("\nlive monitor verdict:")
    print(monitor.render())
    assert live["straggler_rank"] == args.slow_rank, live
    assert live["dominant_span"] == "dispatch_gap", live
    assert live["lateness_s"] > 0.0, live

    # Offline merge over the 8 anchored files -- same path as
    # `python -m horovod_tpu.timeline --merge`.
    out = os.path.join(trace_dir, "merged_timeline.json")
    rep = merge(trace_dir, out)
    print("\nmerged-trace verdict:")
    _print_report(rep)
    assert rep["ranks"] == world, rep["ranks"]
    assert rep["straggler"]["straggler_rank"] == args.slow_rank, \
        rep["straggler"]
    merged = json.load(open(out))
    assert isinstance(merged, list) and merged, "merged trace empty"
    pids = {e.get("pid") for e in merged}
    assert len(pids) == world, pids  # one pid per rank

    if args.bench_json:
        block = {
            "spec": spec, "world": world,
            "injected_rank": args.slow_rank,
            "injected_secs": args.slow_secs,
            "detected_rank": live["straggler_rank"],
            "dominant_span": live["dominant_span"],
            "lateness_s": round(live["lateness_s"], 6),
            "skew_s": round(live["skew_s"], 6),
            "merged_ranks": rep["ranks"],
            "merged_events": rep["events"]}
        entry = {
            "n": world,
            "cmd": ("JAX_PLATFORMS=cpu python examples/straggler_probe.py"
                    f" --steps {args.steps} --slow-rank {args.slow_rank}"
                    f" --slow-step {args.slow_step}"
                    f" --slow-secs {args.slow_secs}"),
            "rc": 0,
            "tail": monitor.render().splitlines()[0],
            "parsed": {
                "metric": "straggler_attribution",
                "value": block["lateness_s"],
                "unit": "seconds_late",
                "vs_baseline": None,
                "config": f"mlp_w{world}_slow{args.slow_secs}",
                "baseline_config": f"mlp_w{world}_slow{args.slow_secs}",
                "straggler": block}}
        with open(args.bench_json, "w") as f:
            json.dump(entry, f, indent=1)
        print(f"\nwrote straggler entry -> {args.bench_json}")

    hvd.shutdown()
    print("\nstraggler probe OK")


if __name__ == "__main__":
    main()
