"""Read the numbers a cell's limits are set from, on the chip.

    python benchmarks/tools/limits.py --workload <cell> --seeds 1,2,3 \
        --seconds 8 [--control fp8|int8]

For each seed, in ONE process (set-up is paid once): the cell's kind runs
as in a benchmark run -- the program against the plain reference -- and
prints every number compared beside its limit.  With ``--control`` the
reference is also put in the program's place, computed in fp8 or int8 (the
nearest precisions below the configuration's bfloat16), and its numbers
are printed as ``control check ...``: a limit goes above the largest
sound number and below the smallest control number (PERF.md section 2
has the readings).  Not part of a benchmark run.
"""

import argparse
import importlib
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", choices=("", "fp8", "int8"), default="")
    args = ap.parse_args(argv)
    from benchmarks import run as bench_run
    data = bench_run.load_cell(ROOT, args.workload)
    chips = int(data["cell"]["chips"])

    devices = bench_run.open_chips(chips, "limits.py: " + args.workload)
    if devices is None:
        return 1
    import horovod_tpu as hvd
    hvd.init(devices=devices)
    config = data["config"]
    kind = importlib.import_module("benchmarks.kinds." + config["kind"])
    family = importlib.import_module(
        "benchmarks.families." + config["family"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()

        def log(msg, seed=seed):
            print(f"seed {seed} {msg}", flush=True)

        ctx = bench_run.make_context(data, seed, args.seconds, "", devices,
                                     family, log)
        ctx.with_control = args.control
        out = kind.run(ctx)
        for c in out["checks"]:
            log(c.line())
        log(f"end_to_end {out['end_to_end']} attempted {out['attempted']} "
            f"failed {out['failed']} peak {out['memory_peak_bytes']} "
            f"took {time.time() - t0:.1f} s")
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
