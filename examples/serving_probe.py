"""Serving observability demo — scrape your own inference engine.

Runs the continuous-batching serving drill on a forced 8-device virtual
CPU mesh: a tensor-parallel decode step over the named-sharding mesh, a
seeded open-loop load from :mod:`horovod_tpu.serving.loadgen`, and the
Prometheus ``/metrics`` endpoint started by ``hvd.init()``.  The probe
then plays the monitoring stack's part itself: HTTP-GETs the endpoint
and asserts every request-lifecycle family the scheduler exports is
present and consistent (submitted == admitted == completed counters,
TTFT/per-token latency histograms with populated buckets), and that the
span layer attributed per-leg decode time to the row-parallel
collectives (``serving_decode/layer*/{attn_wo,mlp_down}``).

``--long-prompts`` switches to the kilotoken mixture (512/2048/4096
weighted, :func:`horovod_tpu.serving.loadgen.long_prompt_spec`) with
chunked flash prefill (``--prefill-chunk`` tokens per slice interleaved
with decode steps), and additionally asserts the
``serving_prefill_chunk`` span leg fired.

Run::

    python examples/serving_probe.py [--requests 16] [--rate 50]
    python examples/serving_probe.py --long-prompts [--prefill-chunk 512]
    python examples/serving_probe.py --bench-json /tmp/serving.json
"""

import sys as _sys
from os.path import abspath as _abs, dirname as _dir
_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root importable

import argparse
import json
import os
import urllib.request

SERVING_FAMILIES = (
    "horovod_serving_requests_total",
    "horovod_serving_tokens_total",
    "horovod_serving_queue_depth",
    "horovod_serving_batch_occupancy",
    "horovod_serving_ttft_seconds",
    "horovod_serving_token_latency_seconds",
)


def _sample(text, prefix):
    """Sum the values of every sample line starting with ``prefix``."""
    total = 0.0
    for ln in text.splitlines():
        if ln.startswith(prefix):
            total += float(ln.split()[-1])
    return total


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--rate", type=float, default=50.0,
                   help="open-loop arrival rate (requests/s)")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--cpu-devices", type=int, default=8,
                   help="virtual mesh size (tensor-parallel world)")
    p.add_argument("--long-prompts", action="store_true",
                   help="serve the 512/2048/4096 kilotoken mixture "
                        "through chunked flash prefill")
    p.add_argument("--prefill-chunk", type=int, default=512,
                   help="chunk length for --long-prompts (0 = whole "
                        "prompt at once)")
    p.add_argument("--bench-json", default=None,
                   help="also write the run's report (the serving "
                        "block) as JSON to this path")
    args = p.parse_args()

    # The endpoint port must be configured before init; 0 = ephemeral.
    os.environ.setdefault("HOROVOD_METRICS_PORT", "0")
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(args.cpu_devices, cpu=True, exact=True)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import horovod_tpu as hvd
    from jax.sharding import Mesh
    from horovod_tpu.core.state import global_state
    from horovod_tpu.models import LLAMA_SERVE, LlamaLM
    from horovod_tpu.serving import (LoadSpec, ServingEngine, generate,
                                     long_prompt_spec)
    from horovod_tpu.timeline import spans

    hvd.init()
    server = global_state().metrics_server
    world = args.cpu_devices
    print(f"devices: {hvd.size()} ({jax.devices()[0].platform}), "
          f"/metrics on port {server.port}")

    cfg = LLAMA_SERVE
    model = LlamaLM(cfg, dtype=jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 4), jnp.int32))
    mesh = Mesh(np.asarray(jax.devices(), dtype=object).reshape(world),
                ("tp",))
    if args.long_prompts:
        # Kilotoken mixture through chunked prefill: kilotoken
        # admissions slice into --prefill-chunk forwards interleaved
        # with decode steps, so the live batch keeps emitting (the
        # TTFT-p99 gate's workload).
        engine = ServingEngine(cfg, params, mesh=mesh, slots=args.slots,
                               page_size=8, max_len=4608,
                               prefill_chunk=args.prefill_chunk)
        spec = long_prompt_spec(num_requests=args.requests,
                                rate_rps=min(args.rate, 2.0),
                                vocab_size=cfg.vocab_size, seed=11)
    else:
        engine = ServingEngine(cfg, params, mesh=mesh, slots=args.slots,
                               page_size=8, max_len=64)
        spec = LoadSpec(num_requests=args.requests, rate_rps=args.rate,
                        prompt_lens=(4, 8, 16), output_lens=(4, 8),
                        vocab_size=cfg.vocab_size, seed=11)
    requests = generate(spec)
    report = engine.serve(requests)
    print(f"served {report.completed}/{report.num_requests} requests: "
          f"{report.tokens_per_s:.1f} tokens/s, "
          f"TTFT p50 {report.ttft_p50_s * 1e3:.1f} ms "
          f"p99 {report.ttft_p99_s * 1e3:.1f} ms, "
          f"occupancy {report.mean_occupancy:.2f}")
    assert report.completed == args.requests, report

    # --- scrape the live endpoint, like Prometheus would -----------------
    url = f"http://127.0.0.1:{server.port}/metrics"
    text = urllib.request.urlopen(url, timeout=10).read().decode()
    families = [ln.split()[2] for ln in text.splitlines()
                if ln.startswith("# TYPE ")]
    print(f"\nscraped {url}: {len(families)} metric families")
    missing = [f for f in SERVING_FAMILIES if f not in families]
    assert not missing, f"serving families absent from /metrics: {missing}"

    submitted = _sample(text, 'horovod_serving_requests_total'
                              '{event="submitted"}')
    completed = _sample(text, 'horovod_serving_requests_total'
                              '{event="completed"}')
    decode_tok = _sample(text, 'horovod_serving_tokens_total'
                               '{phase="decode"}')
    ttft_count = _sample(text, "horovod_serving_ttft_seconds_count")
    lat_buckets = sum(1 for ln in text.splitlines()
                      if ln.startswith("horovod_serving_token_latency"
                                       "_seconds_bucket"))
    for ln in text.splitlines():
        if ln.startswith(("horovod_serving_requests_total",
                          "horovod_serving_tokens_total",
                          "horovod_serving_batch_occupancy")):
            print("  " + ln)
    assert submitted == completed == args.requests, (submitted, completed)
    assert ttft_count == args.requests, ttft_count
    assert decode_tok > 0 and lat_buckets > 0, (decode_tok, lat_buckets)

    # --- span attribution ------------------------------------------------
    # Runtime legs: close the step and read the per-leg host timings the
    # recorder accumulated for prefill/decode dispatch.
    rec = spans.recorder()
    summary = rec.step_boundary(rec.step, report.wall_s)
    want_legs = ["serving_prefill", "serving_decode"]
    if args.long_prompts and args.prefill_chunk:
        # Kilotoken admissions must have gone through the chunked path.
        want_legs.append("serving_prefill_chunk")
    for leg in want_legs:
        got = summary["legs"].get(leg)
        assert got and got["count"] > 0 and got["secs"] > 0, (leg, summary)
    assert summary["legs"]["serving_decode"]["count"] == \
        report.decode_steps, summary
    # Trace-time legs: every row-parallel collective inside the compiled
    # decode step registered its wire payload, one leg per psum site.
    for li in range(cfg.num_layers):
        for leg in (f"serving_decode/layer{li}/attn_wo",
                    f"serving_decode/layer{li}/mlp_down"):
            assert leg in rec.legs, (leg, sorted(rec.legs))
            assert rec.legs[leg]["nbytes"] > 0, (leg, rec.legs[leg])
    print(f"\nspan legs attributed: serving_prefill "
          f"({summary['legs']['serving_prefill']['count']} dispatches) + "
          f"serving_decode ({report.decode_steps} steps) + "
          f"{2 * cfg.num_layers} in-step collective legs")

    if args.bench_json:
        block = {
            "world": world, "slots": args.slots,
            "requests": report.num_requests,
            "completed": report.completed,
            "rejected": report.rejected,
            "prompt_tokens": report.prompt_tokens,
            "new_tokens": report.new_tokens,
            "decode_steps": report.decode_steps,
            "tokens_per_s": round(report.tokens_per_s, 2),
            "ttft_p50_ms": round(report.ttft_p50_s * 1e3, 3),
            "ttft_p99_ms": round(report.ttft_p99_s * 1e3, 3),
            "token_latency_p50_ms":
                round(report.token_latency_p50_s * 1e3, 3),
            "token_latency_p99_ms":
                round(report.token_latency_p99_s * 1e3, 3),
            "batch_occupancy": round(report.mean_occupancy, 4)}
        entry = {
            "n": world,
            "cmd": ("JAX_PLATFORMS=cpu python examples/serving_probe.py"
                    f" --requests {args.requests} --rate {args.rate}"
                    f" --slots {args.slots}"),
            "rc": 0,
            "tail": (f"serving: {block['tokens_per_s']} tokens/s over "
                     f"{block['requests']} requests"),
            "parsed": {
                "metric": "serving_tokens_per_sec",
                "value": block["tokens_per_s"],
                "unit": "tokens/s",
                "vs_baseline": None,
                "config": f"llama_serve_w{world}_slots{args.slots}",
                "baseline_config":
                    f"llama_serve_w{world}_slots{args.slots}",
                "serving": block}}
        with open(args.bench_json, "w") as f:
            json.dump(entry, f, indent=1)
        print(f"wrote serving entry -> {args.bench_json}")

    hvd.shutdown()
    print(f"\nserving probe OK ({report.tokens_per_s:.1f} tokens/s, "
          f"world {world})")


if __name__ == "__main__":
    main()
