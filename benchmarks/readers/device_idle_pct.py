"""Share of the traced window in which no operation ran on the chip:
100 * (1 - busy / window), busy and window as the result line's
``device`` gives them (per-chip union on the ops line, mean over chips)."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
