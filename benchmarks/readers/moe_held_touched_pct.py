"""The share of the experts HELD HERE that a traced decode round touches,
for a model of whose routed experts this chip holds a share: the mean,
over the traced window's whole rounds, of the ``experts_touched``
attribute of the ``decode.bookkeep`` span that retired the round (held
experts, summed over the routed layers, that at least one live slot chose
in it) over routed layers times experts held.  It says how much of the
held expert weights a round streams."""

from benchmarks.lib import rounds


def read(ctx):
    layers = getattr(ctx.family, "moe_layers", None)
    share = ctx.config.get("share")
    found = rounds.booked(ctx, "experts_touched")
    if layers is None or share is None or not found:
        return None
    total = layers(ctx.config) * share["experts_held"]
    return 100.0 * sum(v for _, v in found) / len(found) / total
