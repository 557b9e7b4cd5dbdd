"""Find a served cell's knee, once, on the chip: the cell's traffic at
several fixed rates through ONE engine (set-up is paid once).

    python benchmarks/tools/sweep.py --workload <cell> --rates 1.5,2,2.5 \
        --seconds 30 --seed 7

For each rate: requests finished, tokens a second, the engine's wall time
against the last arrival (a drain much longer than a request's own time
means a backlog), and time to first token by thirds of the arrival order
(a queue that grows shows as a third third far above the first).  The
knee is the highest rate without a growing backlog; the cell's traffic
file then fixes four fifths of it.  Not part of a benchmark run.
"""

import argparse
import importlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from benchmarks import run as bench_run
    from benchmarks.kinds import serve
    from benchmarks.lib import loadgen, stats
    data = bench_run.load_cell(ROOT, args.workload)
    config, traffic = data["config"], data["traffic"]

    devices = bench_run.open_chips(1, "sweep.py: " + args.workload)
    if devices is None:
        return 1
    import horovod_tpu as hvd
    hvd.init(devices=devices)
    family = importlib.import_module(
        "benchmarks.families." + config["family"])
    prog = family.Program(config, traffic, 1, args.seed)
    eng = prog.engine
    vocab = config["vocab_size"]
    eng.serve(prog.requests(serve.warm_stream(traffic, vocab)))
    for rate in (float(r) for r in args.rates.split(",")):
        t = dict(traffic, arrival="poisson", rate_rps=rate,
                 requests_per_second_of_window=rate)
        t.pop("num_requests", None)
        gen = loadgen.generate(t, args.seed, args.seconds, vocab)
        reqs = prog.requests(gen)
        rep = eng.serve(reqs)
        m = serve.request_metrics(reqs, rep.wall_s)
        n = len(reqs)
        thirds = [stats.median(m["ttft_ms"][i * n // 3:(i + 1) * n // 3])
                  for i in range(3)]
        last = max(g.arrival_s for g in gen)
        done_last = max(r.done_s for r in reqs if r.done_s is not None)
        print(f"rate {rate}: {rep.completed}/{n} finished, "
              f"{rep.new_tokens / rep.wall_s:.1f} tokens/s, wall "
              f"{rep.wall_s:.2f} s, last arrival {last:.2f} s, last done "
              f"{done_last:.2f} s, occupancy {rep.mean_occupancy:.3f}, "
              f"ttft p50/p95 {stats.median(m['ttft_ms']):.0f}/"
              f"{stats.percentile(m['ttft_ms'], 95):.0f} ms, ttft p50 by "
              f"thirds {[round(x) for x in thirds]}, tpot p50/p95 "
              f"{stats.median(m['tpot_ms']):.1f}/"
              f"{stats.percentile(m['tpot_ms'], 95):.1f} ms, queue wait "
              f"p95 {stats.percentile(m['queue_wait_ms'], 95):.0f} ms",
              flush=True)
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
