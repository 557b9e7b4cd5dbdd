"""The ``train`` kind: how a training cell's window is driven and which
numbers come out of it.

Set-up builds ONE object -- the family's ``Program``: the compiled step
with its state -- drives it from the seed through its first three steps
(through ``Program.call``, the window's own call and feed), and hands that
same object to the window.  After the window the program's state is freed
and the family's plain reference follows the same three steps; the
comparison of the two decides ``correct``.
"""

import collections
import time

import jax
import numpy as np

from ..lib import checks
from ..lib.tracing import annotate

FIRST_STEPS = 3
# The host runs at most this many steps ahead of the device: each loop
# turn waits for the loss of the step LAG back, which stalls nothing.
LAG = 4


def first_step_checks(got: dict, ref: dict, limits: dict) -> list:
    """Each step's loss, the first gradient's norms and the parameters'
    change, program against reference, each beside its limit."""
    out = []
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        out.append(checks.Check(f"loss_step{i + 1}_rel_gap",
                                abs(a - b) / abs(b), limits["loss_rel_gap"]))
    out.append(checks.Check(
        "first_grad_norm_p99_leaf_gap",
        checks.p99_norm_gap(got["grad_norms"], ref["grad_norms"]),
        limits["grad_norm_gap"]))
    # Not by the worst leaf: Adam divides a gradient by its own size, so
    # on a leaf whose true gradient is zero (a key projection's bias) the
    # program's rounding noise becomes a full-size update and the
    # reference's does not (PERF.md section 2 has the readings).
    out.append(checks.Check(
        "param_change_norm_total_gap",
        checks.total_norm_gap(got["change_norms"], ref["change_norms"]),
        limits["change_norm_gap"]))
    return out


def drive_first_steps(prog) -> dict:
    losses = [float(prog.call(0))]
    grad_norms = prog.first_gradient_norms()
    for i in range(1, FIRST_STEPS):
        losses.append(float(prog.call(i)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": prog.change_norms()}


def window(prog, seconds: float, tracer=None, trace_steps: int = 20) -> dict:
    """Drive steps for ``seconds``; with ``tracer``, the profiler is on
    for ``trace_steps`` fenced steps after the first third."""
    losses, pending = [], collections.deque()
    i = FIRST_STEPS
    pre = None          # (steps, seconds) of the stretch before the trace
    t0 = time.perf_counter()

    def drain():
        while pending:
            losses.append(float(pending.popleft()))

    while True:
        with annotate("train_step_call"):
            pending.append(prog.call(i))
        i += 1
        if len(pending) > LAG:
            with annotate("wait_loss"):
                losses.append(float(pending.popleft()))
        now = time.perf_counter() - t0
        if tracer is not None and pre is None and now >= seconds / 3:
            drain()
            pre = (len(losses), time.perf_counter() - t0)
            tracer.start()
            for _ in range(trace_steps):
                with annotate("train_step_call"):
                    pending.append(prog.call(i))
                i += 1
            with annotate("fence"):
                drain()
            tracer.stop()
            continue
        if now >= seconds:
            break
    drain()
    wall = time.perf_counter() - t0
    return {"losses": losses, "wall_s": wall, "steps": len(losses),
            "pre_trace": pre}


def run(ctx) -> dict:
    fam, cfg, traffic = ctx.family, ctx.config, ctx.traffic
    prog = fam.Program(cfg, traffic, ctx.chips, ctx.seed, log=ctx.log)
    ctx.log(f"program built at {ctx.setup_done():.2f} s")
    got = drive_first_steps(prog)
    # One more fenced call so that nothing of the first steps' readings
    # is still in flight when the window opens.
    jax.block_until_ready(prog.params)
    ctx.log(f"first steps: losses {got['losses']}")
    setup_s = ctx.setup_done()

    with ctx.compiles.counting():
        w = window(prog, ctx.seconds, ctx.tracer,
                   int(traffic.get("trace_steps", 20)))
    peak = ctx.memory_peak()
    finite = [bool(np.isfinite(x)) for x in w["losses"]]
    tokens = prog.tokens_per_step * w["steps"]
    rate = tokens / w["wall_s"] / ctx.chips
    ctx.log(f"window: {w['steps']} steps in {w['wall_s']:.4f} s, "
            f"{prog.tokens_per_step} tokens a step, loss "
            f"{w['losses'][0]:.4f} -> {w['losses'][-1]:.4f}, "
            f"compilations inside the window: {ctx.compiles.count}")
    spread = prog.replica_spread()
    counters = {"tokens_per_step": prog.tokens_per_step,
                "steps": w["steps"], "wall_s": w["wall_s"],
                "sequences_per_chip": int(traffic["sequences_per_chip"]),
                "seq_len": int(traffic["seq_len"]),
                "trace_steps": int(traffic.get("trace_steps", 20)),
                "wire_bytes_per_step": prog.wire_bytes_per_step()}
    if w["pre_trace"]:
        steps, secs = w["pre_trace"]
        counters["pre_trace_tokens_per_s_per_chip"] = (
            prog.tokens_per_step * steps / secs / ctx.chips)
    if ctx.tracer is not None:
        ctx.log(f"memory_analysis of the compiled step: "
                f"{prog.memory_analysis()}")
    shapes = prog.shapes
    prog.free()

    t0 = time.perf_counter()
    ref = fam.ref_first_steps(cfg, traffic, ctx.chips, ctx.seed, shapes,
                              steps=FIRST_STEPS, log=ctx.log)
    ctx.log(f"reference: {FIRST_STEPS} steps in "
            f"{time.perf_counter() - t0:.2f} s, losses {ref['losses']}")
    out = first_step_checks(got, ref, cfg["limits"])
    ctx.log("leaf gaps, first gradient: " + checks.gap_figures(
        got["grad_norms"], ref["grad_norms"]))
    ctx.log("leaf gaps, parameter change: " + checks.gap_figures(
        got["change_norms"], ref["change_norms"]))
    if getattr(ctx, "with_control", ""):
        # benchmarks/tools/limits.py only: the reference in the program's
        # place, computed in the lower precision, through the same
        # comparison.
        ctl = fam.ref_first_steps(cfg, traffic, ctx.chips, ctx.seed, shapes,
                                  steps=FIRST_STEPS, quant=ctx.with_control)
        for c in first_step_checks(ctl, ref, cfg["limits"]):
            ctx.log("control " + c.line())
        ctx.log("control leaf gaps, first gradient: " + checks.gap_figures(
            ctl["grad_norms"], ref["grad_norms"]))
        ctx.log("control leaf gaps, parameter change: " + checks.gap_figures(
            ctl["change_norms"], ref["change_norms"]))
    out.append(checks.Check("replica_leaves_that_differ", float(spread), 0))
    out.append(checks.Check("window_steps_with_nonfinite_loss",
                            float(finite.count(False)), 0))
    out.append(checks.Check("compilations_inside_window",
                            float(ctx.compiles.count), 0))
    return {"attempted": w["steps"], "failed": finite.count(False),
            "end_to_end": {"train_tokens_per_s_per_chip": rate,
                           "setup_s": setup_s},
            "counters": counters, "checks": out, "memory_peak_bytes": peak}
