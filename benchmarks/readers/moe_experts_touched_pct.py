"""The share of a routed model's experts that a traced decode round
touches: the mean of the ``experts_touched`` attribute of the program's
``decode.bookkeep`` spans (experts, summed over the routed layers, that
at least one live slot chose in that round) over routed layers times
experts.  It says how much of the expert weights a round streams."""

from benchmarks.lib import hostspans


def touched(ctx):
    """``experts_touched`` of each traced round, oldest first; empty
    where the program files no such attribute."""
    out = []
    for span in hostspans.named(hostspans.of_run(ctx), "decode.bookkeep"):
        value = span.stats.get("experts_touched")
        if value is not None:
            out.append(int(float(value)))
    return out


def read(ctx):
    layers = getattr(ctx.family, "moe_layers", None)
    rounds = touched(ctx)
    if layers is None or not rounds:
        return None
    total = layers(ctx.config) * ctx.config["n_routed_experts"]
    return 100.0 * sum(rounds) / len(rounds) / total
