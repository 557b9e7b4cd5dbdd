"""Model FLOP/s utilization: the family's operations a token (forward and
backward, nothing recomputed) times the tokens a second a chip of the
stretch before the traced sub-window, over the chip's bf16 peak."""


def read(ctx):
    rate = ctx.counters.get("pre_trace_tokens_per_s_per_chip")
    if rate is None:
        return None
    flops = ctx.family.flops_per_token(ctx.config,
                                       ctx.counters["seq_len"])
    return 100.0 * flops * rate / ctx.peaks["bf16_flops_per_s"]
