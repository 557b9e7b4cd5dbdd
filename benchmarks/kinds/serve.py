"""The ``serve`` kind: how a served cell's window is driven and which
numbers come out of it.

Set-up builds ONE ``ServingEngine`` (the family's ``Program``), warms it
with a stream of the cell's own prompt lengths, and hands that same
engine to the window: the request stream of the cell's traffic, offered
at the rate fixed there.  Once the window has closed, a sample of the
finished requests (drawn from the seed, the longest in it) goes through
the family's plain reference: the widest gap by which a served token's
logit lies below the reference's best decides ``correct``, with the
count of requests that did not finish and the state of the page pool.
"""

import gc
import time

import numpy as np

from ..lib import checks, loadgen, stats
from ..lib.tracing import annotate

SAMPLE = 4      # finished requests that go through the reference


def warm_stream(traffic: dict, vocab: int):
    """One request of each prompt length, all at t = 0, a few tokens out:
    compiles the decode step and every prefill the window will use."""
    rng = np.random.RandomState(0)
    return [loadgen.GenRequest(
        rid=i, prompt=rng.randint(0, vocab, size=int(n)).astype(np.int32),
        max_new_tokens=4, arrival_s=0.0)
        for i, n in enumerate(traffic["prompt_lens"])]


def instrument(engine, tracer, seconds: float, trace_rounds: int) -> dict:
    """Wrap this instance's ``decode_once`` and ``_do_prefill`` in host
    annotations, count rounds, and switch the profiler on for
    ``trace_rounds`` decode rounds once two thirds of ``seconds`` have
    gone.  Starting and stopping the profiler stalls the host loop for
    seconds while requests keep arriving, so a traced run's request
    latencies are the profiler's; what the readers take from requests
    they take from those admitted before the trace started.  Returns the
    counters the readers get."""
    c = {"rounds": 0, "traced_rounds": 0, "traced_live_tokens": 0,
         "t0": None, "trace_started_at": None}
    decode_once, do_prefill = engine.decode_once, engine._do_prefill

    def traced_decode(st, now):
        if c["t0"] is None:
            c["t0"] = time.perf_counter()
        if (tracer is not None and not tracer.on and not tracer.done
                and time.perf_counter() - c["t0"] >= 2 * seconds / 3):
            # On the engine's clock: requests admitted before this moment
            # were queued while no profiler was starting or stopping.
            c["trace_started_at"] = now()
            tracer.start()
        if tracer is not None and tracer.on:
            slots = engine._decode_slots()
            c["traced_live_tokens"] += int(
                sum(int(engine.cache.lengths[s]) + 1 for s in slots))
            c["traced_rounds"] += 1
        with annotate("decode_once"):
            out = decode_once(st, now)
        c["rounds"] += 1
        if (tracer is not None and tracer.on
                and c["traced_rounds"] >= trace_rounds):
            tracer.stop()
        return out

    def traced_prefill(*args, **kwargs):
        if c["t0"] is None:
            c["t0"] = time.perf_counter()
        with annotate("prefill"):
            return do_prefill(*args, **kwargs)

    engine.decode_once = traced_decode
    engine._do_prefill = traced_prefill

    def restore():
        # The wrappers hold the engine and the engine holds them: undo
        # that, or the page pool outlives the engine until a collection.
        del engine.decode_once, engine._do_prefill

    c["restore"] = restore
    return c


def request_metrics(requests, wall_s: float, admitted_before=None) -> dict:
    """Per-request latencies from the engine's arrival-faithful clock.  A
    request that failed counts as a miss: it gets the window's length.
    ``queue_wait_ms`` and ``ttft_admitted_ms`` cover the requests
    admitted before ``admitted_before`` (all, where it is None)."""
    miss_ms = wall_s * 1e3
    ttft, tpot, wait, ttft_admitted = [], [], [], []
    failed = 0
    for r in requests:
        done = (r.done_s is not None and r.first_token_s is not None
                and len(r.tokens) >= r.max_new_tokens)
        if not done:
            failed += 1
            ttft.append(miss_ms)
            tpot.append(miss_ms)
            continue
        ttft.append((r.first_token_s - r.arrival_s) * 1e3)
        if admitted_before is None or r.admit_s < admitted_before:
            wait.append((r.admit_s - r.arrival_s) * 1e3)
            ttft_admitted.append(ttft[-1])
        if len(r.tokens) > 1:
            tpot.append((r.done_s - r.first_token_s) * 1e3
                        / (len(r.tokens) - 1))
    return {"ttft_ms": ttft, "tpot_ms": tpot, "queue_wait_ms": wait,
            "ttft_admitted_ms": ttft_admitted, "failed": failed}


def pick_sample(requests, seed: int, n: int = SAMPLE):
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    done = [r for r in requests if len(r.tokens) >= r.max_new_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens),
                                       -r.rid))
    rest = [r for r in done if r is not longest]
    rng = loadgen.seed_rng(seed, stream=2)
    picks = [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [longest] + picks


def run(ctx) -> dict:
    fam, cfg, traffic = ctx.family, ctx.config, ctx.traffic
    prog = fam.Program(cfg, traffic, ctx.chips, ctx.seed, log=ctx.log)
    ctx.log(f"program built at {ctx.setup_done():.2f} s")
    eng = prog.engine
    vocab = cfg["vocab_size"]
    warm = eng.serve(prog.requests(warm_stream(traffic, vocab)))
    if warm.completed != len(traffic["prompt_lens"]):
        raise RuntimeError(f"warm-up finished {warm.completed} requests")
    gen = loadgen.generate(traffic, ctx.seed, ctx.seconds, vocab)
    ctx.log(f"traffic: {loadgen.describe(gen)}")
    requests = prog.requests(gen)
    counters = instrument(eng, ctx.tracer, ctx.seconds,
                          int(traffic.get("trace_rounds", 100)))
    setup_s = ctx.setup_done()
    ctx.log(f"warmed up at {setup_s:.2f} s")

    with ctx.compiles.counting():
        try:
            report = eng.serve(requests)
        finally:
            if ctx.tracer is not None:
                ctx.tracer.stop()
    peak = ctx.memory_peak()
    m = request_metrics(requests, report.wall_s,
                        counters["trace_started_at"])
    failed = m["failed"]
    ctx.log(f"window: {report.completed}/{len(requests)} requests, "
            f"{report.new_tokens} tokens in {report.wall_s:.4f} s, "
            f"{report.decode_steps} decode rounds, rejected "
            f"{report.rejected}, mean occupancy "
            f"{report.mean_occupancy:.4f}, ttft p50 "
            f"{stats.median(m['ttft_ms']):.2f} ms, tpot p50 "
            f"{stats.median(m['tpot_ms']):.2f} ms, compilations inside "
            f"the window: {ctx.compiles.count}")
    end_to_end = {
        "serve_tokens_per_s": report.new_tokens / report.wall_s,
        "ttft_p95_ms": stats.percentile(m["ttft_ms"], 95),
        "tpot_p95_ms": stats.percentile(m["tpot_ms"], 95),
        "setup_s": setup_s}
    counters.update({
        "mean_occupancy": report.mean_occupancy,
        "queue_wait_ms": m["queue_wait_ms"],
        "ttft_ms": m["ttft_admitted_ms"], "wall_s": report.wall_s,
        "decode_steps": report.decode_steps})
    drained = prog.pool_drained()
    sample = [(np.asarray(r.prompt), list(r.tokens))
              for r in pick_sample(requests, ctx.seed)]
    pad_to = max(int(p) for p in traffic["prompt_lens"]) + max(
        int(o) for o in traffic["output_lens"])
    counters.pop("restore")()
    prog.free_engine()
    eng = None
    gc.collect()

    t0 = time.perf_counter()
    gaps = fam.served_gaps(cfg, prog.params, sample, pad_to,
                           with_control=bool(getattr(ctx, "with_control", "")))
    if "control_logit_gap_max" in gaps:
        # benchmarks/tools/limits.py only.
        ctx.log("control " + checks.Check(
            "served_logit_gap_max", gaps["control_logit_gap_max"],
            cfg["limits"]["served_logit_gap_max"]).line())
    ctx.log(f"reference: {len(sample)} requests, "
            f"{gaps['tokens_compared']} served tokens in "
            f"{time.perf_counter() - t0:.2f} s")
    out = [
        checks.Check("served_logit_gap_max",
                     gaps["served_logit_gap_max"] if sample else
                     float("inf"), cfg["limits"]["served_logit_gap_max"]),
        checks.Check("requests_not_finished", float(failed), 0),
        checks.Check("pool_pages_left_live", 0.0 if drained else 1.0, 0),
        checks.Check("compilations_inside_window",
                     float(ctx.compiles.count), 0)]
    return {"attempted": len(requests), "failed": failed,
            "end_to_end": end_to_end, "counters": counters, "checks": out,
            "memory_peak_bytes": peak}
