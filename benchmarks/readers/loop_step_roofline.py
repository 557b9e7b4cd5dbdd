"""A looped model's decode program against the least bytes it must move.
A round streams every layer's weights once a PASS (pass t + 1 of the
first layer needs pass t of the last: no order of the loops saves a
stream) and the head once, and reads the keys and values of every live
token in every plane: the family's ``weight_bytes_per_round`` a traced
round plus its ``kv_bytes_per_token`` a live token the benchmark's
wrapper counted, over peak bytes/s, over the device time of the decode
program's events on the modules line.  A family that is not looped has
no ``weight_bytes_per_round``: nothing to read."""

from benchmarks.lib import xplane


def read(ctx):
    per_round = getattr(ctx.family, "weight_bytes_per_round", None)
    live = ctx.counters.get("traced_live_tokens")
    if per_round is None or not live:
        return None
    rounds, ns = xplane.name_sums(ctx.trace.devices[0].modules,
                                  ctx.family.DECODE_MODULE)
    if not rounds:
        return None
    least_s = ((rounds * per_round(ctx.config)
                + live * ctx.family.kv_bytes_per_token(ctx.config))
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9)
