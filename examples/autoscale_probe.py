"""Elastic serving control-plane demo — watch the closed loop act.

Runs the SLO-driven autoscaling drill on a forced 8-device virtual CPU
mesh: the :class:`~horovod_tpu.serving.ServingControlPlane` serves a
seeded Poisson load while a chaos spec fires *virtually* against the
fleet -- ``kill@`` marks a device dead mid-decode (mandatory shrink +
drain), ``slow@`` degrades a rank until the straggler monitor's
lateness EWMA has it evicted.  The probe then plays the monitoring
stack's part itself: HTTP-GETs the ``/metrics`` endpoint started by
``hvd.init()`` and asserts every ``horovod_ctl_*`` decision family is
present and consistent with the drill report (decisions, resizes,
evictions, drained requests, mesh-size gauge), and that nothing was
lost: every admitted request completed despite two mesh transitions,
with zero leaked KV pages.

Run::

    python examples/autoscale_probe.py [--requests 32] [--rate 40]
    python examples/autoscale_probe.py --bench-json /tmp/autoscale.json
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root importable

import argparse
import json
import os
import urllib.request

CTL_FAMILIES = (
    "horovod_ctl_decisions_total",
    "horovod_ctl_resizes_total",
    "horovod_ctl_evictions_total",
    "horovod_ctl_drained_requests_total",
    "horovod_ctl_mesh_size",
    "horovod_ctl_healthy_ranks",
)

DEFAULT_SPEC = "kill@step=20,rank=7;slow@step=35,rank=2,secs=0.2"


def _sample(text, prefix):
    """Sum the values of every sample line starting with ``prefix``."""
    total = 0.0
    for ln in text.splitlines():
        if ln.startswith(prefix):
            total += float(ln.split()[-1])
    return total


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate", type=float, default=40.0,
                   help="open-loop arrival rate (requests/s)")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--cpu-devices", type=int, default=8,
                   help="virtual fleet size (initial tensor-parallel "
                        "world)")
    p.add_argument("--chaos-spec", default=DEFAULT_SPEC,
                   help="kill@/slow@ spec fired virtually against the "
                        "fleet (chaos.py grammar)")
    p.add_argument("--bench-json", default=None,
                   help="also write the drill's counts (the autoscale "
                        "block) as JSON to this path")
    args = p.parse_args()

    # The endpoint port must be configured before init; 0 = ephemeral.
    os.environ.setdefault("HOROVOD_METRICS_PORT", "0")
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(args.cpu_devices, cpu=True, exact=True)
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.core.state import global_state
    from horovod_tpu.models import LLAMA_SERVE, LlamaLM
    from horovod_tpu.serving import (LoadSpec, PolicyConfig,
                                     ServingControlPlane, generate)

    hvd.init()
    server = global_state().metrics_server
    world = args.cpu_devices
    print(f"devices: {hvd.size()} ({jax.devices()[0].platform}), "
          f"/metrics on port {server.port}")
    print(f"chaos spec: {args.chaos_spec}")

    cfg = LLAMA_SERVE
    model = LlamaLM(cfg, dtype=jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 4), jnp.int32))
    policy_cfg = PolicyConfig(
        interval_s=0.05, ttft_slo_s=2.0, queue_high=20,
        occupancy_low=0.15, hysteresis=2, cooldown_s=0.3,
        evict_lateness_s=0.05, drain_steps=8)
    plane = ServingControlPlane(
        cfg, params, devices=jax.devices()[:world], initial_tp=world,
        policy_config=policy_cfg, chaos_spec=args.chaos_spec,
        slots=args.slots, page_size=8, max_len=64)

    spec = LoadSpec(num_requests=args.requests, rate_rps=args.rate,
                    prompt_lens=(4, 8, 16), output_lens=(8, 16, 24),
                    vocab_size=cfg.vocab_size, seed=11)
    rep = plane.serve(generate(spec))

    print(f"\nserved {rep.serving.completed}/{rep.serving.num_requests} "
          f"requests across {rep.resizes} resize(s): mesh "
          f"{rep.mesh_size_initial} -> {rep.mesh_size_final}, dead "
          f"{rep.dead_ranks}, evicted {rep.evicted_ranks}")
    print(f"drain: {rep.drained_completed} completed on the old mesh, "
          f"{rep.drained_reprefilled} re-prefilled, "
          f"{rep.drain_leaked_pages} leaked pages")
    print(f"SLO violation: {rep.slo_violation_s:.3f}s "
          f"(TTFT objective {policy_cfg.ttft_slo_s}s)")
    for d in rep.decisions:
        if d["action"] != "hold":
            print(f"  step {d['step']:3d}: {d['action']} "
                  f"({d['reason']}) -> tp {d['target_size']}")
    assert rep.lost_requests == 0, rep.as_dict()
    assert rep.drain_leaked_pages == 0, rep.as_dict()
    assert rep.dead_ranks and rep.evicted_ranks, rep.as_dict()
    assert rep.mesh_size_final < rep.mesh_size_initial, rep.as_dict()

    # --- scrape the live endpoint, like Prometheus would -----------------
    url = f"http://127.0.0.1:{server.port}/metrics"
    text = urllib.request.urlopen(url, timeout=10).read().decode()
    families = [ln.split()[2] for ln in text.splitlines()
                if ln.startswith("# TYPE ")]
    print(f"\nscraped {url}: {len(families)} metric families")
    missing = [f for f in CTL_FAMILIES if f not in families]
    assert not missing, f"ctl families absent from /metrics: {missing}"

    decisions = _sample(text, "horovod_ctl_decisions_total")
    resizes = _sample(text, "horovod_ctl_resizes_total")
    evictions = _sample(text, "horovod_ctl_evictions_total")
    drained = _sample(text, "horovod_ctl_drained_requests_total")
    mesh_size = _sample(text, "horovod_ctl_mesh_size")
    for ln in text.splitlines():
        if ln.startswith(("horovod_ctl_decisions_total",
                          "horovod_ctl_resizes_total",
                          "horovod_ctl_evictions_total",
                          "horovod_ctl_drained_requests_total",
                          "horovod_ctl_mesh_size")):
            print("  " + ln)
    assert decisions == len(rep.decisions), (decisions, len(rep.decisions))
    assert resizes == rep.resizes, (resizes, rep.resizes)
    assert evictions >= len(rep.evicted_ranks) + len(rep.dead_ranks), \
        (evictions, rep.evicted_ranks, rep.dead_ranks)
    assert drained == rep.drained_completed + rep.drained_reprefilled, \
        (drained, rep.drained_completed, rep.drained_reprefilled)
    assert mesh_size == rep.mesh_size_final, (mesh_size, rep.mesh_size_final)

    if args.bench_json:
        block = {
            "world": world,
            "initial_tp": rep.mesh_size_initial,
            "final_tp": rep.mesh_size_final,
            "chaos_spec": args.chaos_spec,
            "decisions": rep.decision_counts,
            "resizes": rep.resizes,
            "evicted_ranks": rep.evicted_ranks,
            "dead_ranks": rep.dead_ranks,
            "drained_completed": rep.drained_completed,
            "drained_reprefilled": rep.drained_reprefilled,
            "drain_leaked_pages": rep.drain_leaked_pages,
            "lost_requests": rep.lost_requests,
            "slo_violation_s": round(rep.slo_violation_s, 3),
            "slo_budget_s": 30.0,
            "requests": rep.serving.num_requests,
            "completed": rep.serving.completed,
            "rejected": rep.serving.rejected}
        entry = {
            "n": world,
            "cmd": ("JAX_PLATFORMS=cpu python examples/autoscale_probe.py"
                    f" --requests {args.requests} --rate {args.rate}"
                    f" --slots {args.slots}"),
            "rc": 0,
            "tail": (f"autoscale: mesh {block['initial_tp']}->"
                     f"{block['final_tp']}, {block['completed']}/"
                     f"{block['requests']} requests, "
                     f"{block['lost_requests']} lost"),
            "parsed": {
                "metric": "autoscale_slo_violation_seconds",
                "value": block["slo_violation_s"],
                "unit": "s",
                "vs_baseline": None,
                "config": f"llama_serve_ctl_w{world}_slots{args.slots}",
                "baseline_config":
                    f"llama_serve_w{world}_slots{args.slots}",
                "autoscale": block}}
        with open(args.bench_json, "w") as f:
            json.dump(entry, f, indent=1)
        print(f"wrote autoscale entry -> {args.bench_json}")

    hvd.shutdown()
    print(f"\nautoscale probe OK (mesh {rep.mesh_size_initial} -> "
          f"{rep.mesh_size_final}, {rep.serving.completed} requests, "
          f"0 lost)")


if __name__ == "__main__":
    main()
