"""Median length of the host's span round one train step's dispatch in
the traced window: the ``hvd.train_step`` step annotations that
``training._InstrumentedStep`` enters round the jitted call.  How far the
host is from setting the pace: the device's step is ``train_step_ms``.

A program that enters no such annotation (one from before PR 24) is read
from the benchmark's own span round the same call and its feed,
``bench.train_step_call``, and the reader says so.
"""

from benchmarks.lib import hostspans, stats


def read(ctx):
    steps = hostspans.named(hostspans.of_run(ctx), "train_step")
    durs = [s.dur_ns for s in steps]
    if not durs:
        durs = [e.dur_ns for e in ctx.trace.host
                if e.name == "bench.train_step_call"]
        ctx.log("train dispatch: no hvd.train_step span in this trace (a "
                "program from before PR 24); reading the benchmark's "
                f"own {len(durs)} bench.train_step_call spans")
    if not durs:
        return None
    ctx.log("train dispatch: %d steps, median %.4f ms, longest %.4f ms" % (
        len(durs), stats.median(durs) / 1e6, max(durs) / 1e6))
    return stats.median(durs) / 1e6
